#!/usr/bin/env python3
"""Calibration runs on the chip, all in one process: the rate sweep
that finds a mix's knee, and the readings the correctness limit is set
from (the program's widest gap and the float8 control's, per seed).

    python3 bench/calibrate.py sweep --workload yi-6b.mixed \\
        --rates 0.2 0.3 0.4 --seconds 40
    python3 bench/calibrate.py control --workload yi-6b.mixed \\
        --seeds 11 12 13 --seconds 20

Each run prints one JSON line.  ``control`` exits 1 if the control
comes out correct on any seed: the limit must fail it.  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--seeds", type=int, nargs="*", default=[1])
    args = ap.parse_args(argv)
    sys.path.insert(0, run.SRC)
    bench, w, cfgfile, mix = run.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    import harness
    metrics = run.cell_metrics(bench, args.workload, False)
    if args.what == "sweep":
        for rate in args.rates:
            t = time.perf_counter()
            out = harness.run_cell(cfgfile, mix, args.seeds[0],
                                   args.seconds, False, metrics,
                                   t_proc=t, rate=rate, check=False)
            vals = {k: v["value"] for k, v in
                    out["result"]["metrics"].items()}
            print(json.dumps({"rate": rate, **vals, **out["info"],
                              "failed": out["result"]["failed"]}),
                  flush=True)
        return 0
    passed = []
    for seed in args.seeds:
        t = time.perf_counter()
        out = harness.run_cell(cfgfile, mix, seed, args.seconds, False,
                               metrics, t_proc=t, control=True)
        ctl = out.get("control", {"correct": True})
        if ctl["correct"]:
            passed.append(seed)
        print(json.dumps({"seed": seed,
                          "program_gap_max": out["check"]["logit_gap_max"][
                              "value"],
                          "control_correct": ctl["correct"],
                          **{k: out["info"].get(k) for k in (
                              "control_gap_max", "control_not_argmax",
                              "checked_tokens", "tokens_not_argmax",
                              "gap_max_per_request", "sample", "check_s")}}),
              flush=True)
    if passed:
        print(f"the control came out correct on seeds {passed}: the limit "
              f"does not separate it", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
