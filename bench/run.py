#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload yi-6b.mixed --seed 7 --seconds 40 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  With ``--trace 0`` the run reports
the cell's end-to-end metrics; with ``--trace 1`` it records a profiler
trace of the window and reports its per-layer metrics, each read by
``bench/metrics/<name>.py``.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``check``, the numbers
compared with their limits; the same numbers are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")


def cell_metrics(bench: dict, cell: str, trace: bool):
    """The metrics a cell reports: the end-to-end ones untraced, the
    per-layer ones traced.  A metric with a ``workloads`` list is the
    listed cells'; an end-to-end metric without one is every cell's; a
    per-layer metric without one is every cell's that reports the metric
    it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def load_cell(name: str):
    import harness
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgfile = harness.load_json(HERE, "configs", f"{w['config']}.json")
    import traffic
    return bench, w, cfgfile, traffic.load_mix(w["traffic"])


def report(out: dict) -> None:
    """Info lines, then the check's numbers as the last lines of
    standard error, then the result as the last line of standard
    output."""
    print(json.dumps({"info": out["info"]}), flush=True)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = dict(out["result"])
    line["check"] = out["check"]
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"no program under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    bench, w, cfgfile, mix = load_cell(args.workload)

    import jax
    devs = jax.devices()
    print(f"device: {devs[0].platform} {devs[0].device_kind!r} x {len(devs)}",
          file=sys.stderr)
    if devs[0].platform != "tpu":
        print("no TPU: this benchmark runs only on the chip", file=sys.stderr)
        return 1
    if len(devs) < w["chips"]:
        print(f"{args.workload} needs {w['chips']} chips, found {len(devs)}",
              file=sys.stderr)
        return 1

    import harness
    out = harness.run_cell(cfgfile, mix, args.seed, args.seconds,
                           bool(args.trace),
                           cell_metrics(bench, args.workload, bool(args.trace)),
                           t_proc=T_PROC)
    report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
