#!/usr/bin/env python3
"""Chip smoke test: the normal serving path, end to end, on a TPU.

    python3 chip_smoke.py              # one chip: yi-6b at full width
    python3 chip_smoke.py --chips 4    # four chips: qwen3-14b on a (1, 4) mesh

One process drives ``launch/serve.py``'s own ``main`` in-process:
``ServingEngine`` -> ``ServingLoop`` -> ``JaxEngineBackend`` on the paged
KV pool with chunked prefill, random bf16 weights from seed 0, eight
requests from the ``mixed`` workload arriving at once (short chat
prompts and long documents clipped to the context, so the lengths fall
on both sides of the first bucket split and long prompts span many
prefill chunks).
It then recomputes the served tokens of the shortest and the longest
request with the cache-free ``transformer.forward`` on
the same params and devices, and fails unless every request got its
full ``max_new_tokens`` and each served token is the recompute's argmax
(a mismatch passes only where the recompute's top-2 logit margin is
below ``MARGIN_TOL``).  The last line of stdout is one JSON object
naming the device; it is printed only when every check held.

Sizing on one v5e chip (16 GiB HBM, ``bytes_limit`` 15.75 GiB).  The
decode step donates the pool and updates it in place; the chunk step
donates nothing, so it holds its input and output caches at once:
  * weights: yi-6b in bf16, 6.06e9 params = 11.29 GiB;
  * KV: 64 KiB per token in bf16 (32 layers x 2 x 4 kv heads x 128);
  * decode pool: 8,192 tokens (64 pages of 128) = 0.5 GiB, once in a
    decode step (11.79 GiB in all);
  * chunked-prefill batch cache: 4 slots x 4,096 tokens = 1 GiB, twice
    in a chunk step, plus (4, 32, 128, 4096) f32 chunk scores = 0.25
    GiB; with the resident pool, 14.06 GiB in all.
  Those totals are the compiler's memory analysis of the two steps
  (tests/test_tpu_compile.py).  Chunks of 128 tokens (the
  scheduler's pad multiple) keep the prefill shapes to one width.  The
  engine awaits a chunk step's input cache before it enqueues the step,
  so no third batch cache is ever live.
Four chips: qwen3-14b (29.6 GB of bf16 weights, more than one chip
holds) at its published 32,768-token context, params and KV heads split
over ``model`` by ``sharding/partition.py``.  Per device: weights 6.88
GiB; a 49,152-token pool (160 KiB per token, 1.5 long requests) 1.88
GiB; a chunk step over 2 slots x 32,768 tokens 12.22 GiB with the
weights, 14.1 GiB with the pool.  The same checks, plus that the pool
and the block tables live on all four devices.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

# a served token may differ from the recompute's argmax only where the
# recompute's top-2 logit gap is below this: a near-tie that bf16
# reduction order alone can flip (the logits of the random-weight
# model are ~N(0, 1), so typical top-2 gaps are ~0.2)
MARGIN_TOL = 0.05

PHASES = {
    1: ["--arch", "yi-6b", "--paged", "--chunk", "128", "--slots", "4",
        "--pool-tokens", "8192", "--requests", "8", "--dataset", "mixed",
        "--rps", "1000"],
    4: ["--arch", "qwen3-14b", "--model", "4", "--paged", "--chunk", "128",
        "--slots", "2", "--pool-tokens", "49152", "--requests", "8",
        "--dataset", "mixed", "--rps", "1000"],
}


def greedy_check(cfg, params, reqs, outputs, pad: int = 128):
    """Teacher-forced recompute of each request's served tokens with the
    cache-free forward pass.  Returns (tokens checked, near-tie flips,
    failures) where a failure is a mismatch with a top-2 margin of at
    least MARGIN_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as tfm

    fwd = jax.jit(lambda p, t, at: tfm.forward(cfg, p, tokens=t, at=at)[0])
    checked = flips = 0
    failures = []
    for r in reqs:
        served = list(outputs[r.rid])
        seq = np.concatenate([np.asarray(r.tokens[:r.prompt_len], np.int32),
                              np.asarray(served[:-1], np.int32)])
        width = -(-len(seq) // pad) * pad      # causal: tail padding is inert
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(seq)] = seq
        at = r.prompt_len - 1 + np.arange(len(served))
        rows = np.asarray(fwd(params, jnp.asarray(toks), jnp.asarray(at)),
                          np.float32)                       # (n, vocab)
        top2 = np.sort(rows, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        want = rows.argmax(-1)
        for i, tok in enumerate(served):
            checked += 1
            if tok == want[i]:
                continue
            if margin[i] < MARGIN_TOL:
                flips += 1
            else:
                failures.append((r.rid, i, int(tok), int(want[i]),
                                 float(margin[i])))
    return checked, flips, failures


def _placement(engine, n_dev: int):
    """Check the paged pool's K/V leaves and block tables span every
    device of the mesh; return each device's bytes in use."""
    import jax
    pool = engine.backend.pool_cache
    leaves = {"block_tables": pool["block_tables"]}
    for gi, slots in enumerate(pool["groups"]):
        for j, slot in enumerate(slots):
            for k in ("k", "v"):
                if k in slot:
                    leaves[f"groups[{gi}][{j}].{k}"] = slot[k]
    bad = [name for name, x in leaves.items()
           if len(x.sharding.device_set) != n_dev]
    if bad:
        raise SystemExit(f"not placed on all {n_dev} devices: {bad}")
    kv = [x for name, x in leaves.items() if name != "block_tables"]
    if any(x.sharding.shard_shape(x.shape) == x.shape for x in kv):
        raise SystemExit("KV pool leaves are replicated, not sharded")
    shards = sorted({f"{x.shape} -> {x.sharding.shard_shape(x.shape)}"
                     for x in kv})
    print(f"pool placement: {len(kv)} K/V leaves, per-device shard "
          f"{shards}; block tables on {n_dev} devices")
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()]


def run(chips: int) -> dict:
    import jax
    from repro.core.serving_loop import WallClock
    from repro.core.telemetry import Tracer
    from repro.launch import serve

    # the program's compile listener: one serve.compile span per phase
    # JAX spends tracing, lowering and compiling, so set-up is reported
    # apart from serving
    compiles = Tracer(WallClock())
    t0 = time.perf_counter()
    engine = serve.main(PHASES[chips])
    wall = time.perf_counter() - t0
    setup_s = sum(e["dur"] for e in compiles.events
                  if e["name"] == "serve.compile") * 1e-6

    reqs = engine.requests
    short = [r.rid for r in reqs
             if len(engine.outputs.get(r.rid, ())) != r.max_new_tokens
             or r.finished < 0 or r.dropped]
    if short:
        raise SystemExit(f"requests not served in full: {short}")
    served = sum(len(engine.outputs[r.rid]) for r in reqs)
    lens = sorted(r.prompt_len for r in reqs)
    # whether the live queue splits depends on how many requests have
    # arrived at each scheduling tick, which the host's speed decides;
    # the lengths themselves must straddle the first split point and
    # span more than one prefill chunk
    boundary = engine.sched.buckets.l_max // 2
    print(f"served {len(reqs)}/{len(reqs)} requests in full, {served} "
          f"tokens; prompt lengths {lens}; "
          f"{engine.sched.buckets.n_splits} bucket splits")
    if not lens[0] < boundary <= lens[-1]:
        raise SystemExit(f"prompt lengths do not straddle the first "
                         f"bucket boundary {boundary}")
    if lens[-1] <= engine.backend.chunk_tokens:
        raise SystemExit("no prompt spans more than one prefill chunk")
    n_prefill, n_decode = engine.backend.compiled_shapes()
    print(f"compiled shapes: {n_prefill} prefill + {n_decode} decode; "
          f"setup (trace+compile) {setup_s:.1f} s, serve "
          f"{wall - setup_s:.1f} s")
    if n_decode != 1 or n_prefill != engine.n_prefill_shapes:
        raise SystemExit(f"retraced: {n_prefill} prefill executables for "
                         f"{engine.n_prefill_shapes} shapes, {n_decode} "
                         f"decode executables for one shape")

    if chips > 1:
        per_dev = _placement(engine, chips)
        print(f"bytes in use per device: {per_dev}")

    by_len = sorted(reqs, key=lambda r: r.prompt_len)
    ref = [by_len[0], by_len[-1]]
    t1 = time.perf_counter()
    checked, flips, failures = greedy_check(engine.cfg, engine.params, ref,
                                            engine.outputs)
    print(f"forward recompute of requests {[r.rid for r in ref]} "
          f"(prompts {[r.prompt_len for r in ref]}): {checked} tokens "
          f"checked, {flips} near-tie flips (margin < {MARGIN_TOL}), "
          f"{len(failures)} mismatches; {time.perf_counter() - t1:.1f} s")
    if failures:
        raise SystemExit(f"served tokens disagree with the forward "
                         f"recompute (rid, step, served, want, margin): "
                         f"{failures}")

    stats = jax.devices()[0].memory_stats() or {}
    print(f"device 0 memory: bytes_limit {stats.get('bytes_limit')}, "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return {"served_tokens": served}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=sorted(PHASES),
                    help="1: yi-6b on one chip; 4: qwen3-14b on a (1, 4) "
                         "mesh, and no other phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind!r} x {len(devs)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this test runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1
    run(args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
