"""Serving launcher: BucketServe on the unified serving loop.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        [--backend jax|sim] [--chunk 128] [--paged --page-size 128] \
        [--requests 32] [--dataset mixed] [--data 2 --model 2]

``--backend jax`` (default) runs the real engine: jitted prefill/decode
with slot-pool continuous batching; ``--chunk N`` enables chunked
prefill (decode iterations interleave between N-token prompt chunks);
``--paged`` swaps the per-slot KV caches for the shared page pool
(block-table admission + youngest-preemption, DESIGN.md §3) — the
scheduler then runs the ceil-to-page Eq. (6) memory model.
``--backend sim`` drives the SAME scheduler through the analytic cost
model instead — both are ExecutionBackends under one ServingLoop
(core/serving_loop.py), which is how the cost model's scheduling
behaviour is validated against real execution.

``--sessions N --turns T`` serves a multi-turn conversation workload
through the KV retention layer (core/retention.py): each finished
turn's transcript stays retained (full pages on the radix, partial
tail pinned under the session key for ``--session-ttl`` seconds) and
the next turn of the same conversation resumes past it instead of
re-prefilling (DESIGN.md §3 "Session retention"; implies
--prefix-cache and therefore --paged).

On a CPU use --smoke (reduced config, real execution).  On a TPU the
same entrypoint serves the full config in bf16 with random weights from
a fixed seed; ``--data/--model`` register a host mesh
(sharding/context.py), and params and KV caches are laid out on it by
repro/sharding/partition.py.  The scheduler's memory budget is the
device's own ``bytes_limit``.  ``main(argv)`` runs in-process and
returns the engine (``chip_smoke.py`` drives it that way); a run that
the wall limit cuts short exits nonzero.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import functools
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config, list_archs
from repro.core import (BucketServeScheduler, GoodputScheduler,
                        MemoryBudget, SchedulerConfig)
from repro.core.engine import ServingEngine
from repro.core.faults import FaultPlan
from repro.core.recovery import LoopCheckpoint
from repro.core.simulator import A100X4, CostModel, Simulator
from repro.core.telemetry import Tracer, validate_perfetto
from repro.data.trace import TraceRecorder, TraceWorkload
from repro.data.workload import DEFAULT_CLASS_MIX, WorkloadSpec, generate
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tfm
from repro.sharding import context as shctx
from repro.sharding import partition


REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
# the budget a CPU --smoke run schedules against: its host reports no
# device memory, so it plans as if on one 16 GiB v5e
SMOKE_HBM_BYTES = 16 * 2 ** 30


def use_compile_cache() -> None:
    """Persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    points (JAX reads it itself), else one fixed directory inside the
    checkout — the path is part of the cache key, so it never moves."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def device_hbm_bytes(smoke: bool) -> int:
    """The scheduler's per-device memory: what the device reports as
    ``bytes_limit``.  Only a CPU smoke run, whose host reports none, falls
    back to ``SMOKE_HBM_BYTES``."""
    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit:
        return int(limit)
    if smoke and dev.platform == "cpu":
        return SMOKE_HBM_BYTES
    raise SystemExit(f"{dev.platform} device {dev.device_kind!r} reports "
                     "no memory bytes_limit; refusing to guess a budget")


def init_params(cfg, mesh, seed: int = 0):
    """Random bf16 weights from ``seed``, built by one jitted program —
    straight into their ``partition.param_specs`` layout when a mesh is
    given, so no device ever holds the whole model."""
    make = functools.partial(tfm.init_params, cfg, dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(seed)
    if mesh is None:
        return jax.jit(make)(key)
    specs = partition.param_specs(cfg, jax.eval_shape(make, key), mesh)
    return jax.jit(make, out_shardings=partition.to_shardings(mesh, specs))(
        key)


def _shorten(reqs, max_seq_len: int) -> None:
    """Keep runs short: prompts end 16 tokens inside the window and
    every request asks for 8 new tokens (a prompt the generator clipped
    to the window would otherwise be left room for one)."""
    for r in reqs:
        r.prompt_len = min(r.prompt_len, max_seq_len - 16)
        r.max_new_tokens = 8


def _sched_config(args) -> SchedulerConfig:
    return SchedulerConfig(
        max_batch=args.slots, trigger=args.trigger,
        memory_model="paged" if args.paged else "sum",
        page_size=args.page_size)


def _make_sched(cfg, budget, args):
    """--sched picks the queue policy: arrival-order BucketServe or the
    deadline-slack goodput scheduler (DESIGN.md §8) — same buckets,
    same Eq.-(6) controller, different candidate ordering."""
    cls = GoodputScheduler if args.sched == "goodput" \
        else BucketServeScheduler
    return cls(cfg, budget, _sched_config(args))


def _tail_line(res) -> str:
    """Percentile tails (overall + per class) — what the benchmark
    gates read; means hide exactly the burst tail this PR is about."""
    out = (f"tails: TTFT p50/p95/p99 {res.p50('ttft'):.3f}/"
           f"{res.p95('ttft'):.3f}/{res.p99('ttft'):.3f} s, "
           f"TPOT p50/p95/p99 {res.p50('tpot') * 1e3:.1f}/"
           f"{res.p95('tpot') * 1e3:.1f}/{res.p99('tpot') * 1e3:.1f} ms, "
           f"{res.incomplete()} incomplete")
    for c in res.classes():
        out += (f"\nclass {c}: TTFT p50/p95/p99 "
                f"{res.p50('ttft', c):.3f}/{res.p95('ttft', c):.3f}/"
                f"{res.p99('ttft', c):.3f} s, TPOT p99 "
                f"{res.p99('tpot', c) * 1e3:.1f} ms, "
                f"attainment {res.slo_attainment(c):.2f}, "
                f"goodput {res.goodput(c):.3f} req/s")
    if res.classes():
        out += (f"\ngoodput {res.goodput():.3f} req/s "
                f"({res.server_rps():.3f} finished req/s)")
    return out


def _finish_timeline(args, tracer) -> None:
    """Export + schema-validate the Perfetto timeline (--trace-out)."""
    if tracer is None:
        return
    doc = tracer.save(args.trace_out)
    errs = validate_perfetto(doc)
    n_ev = sum(1 for e in doc["traceEvents"] if e.get("ph") != "M")
    if errs:
        for e in errs[:10]:
            print(f"[trace] INVALID: {e}")
        raise SystemExit(f"--trace-out produced an invalid trace "
                         f"({len(errs)} schema violations)")
    print(f"[trace] {n_ev} events on {len(tracer._tracks)} tracks -> "
          f"{args.trace_out} (open in ui.perfetto.dev)")


def _make_sim(cfg, args, plan=None, recorder=None, tracer=None):
    hw = A100X4
    budget = MemoryBudget(hbm_bytes_per_device=hw.hbm_bytes,
                          n_devices=hw.decode_chips,
                          weight_bytes=cfg.param_count() * 2)
    sched = _make_sched(cfg, budget, args)
    sim = Simulator(sched, CostModel(cfg, hw), mode="disagg",
                    decode_slot_cap=args.slots, chunk_tokens=args.chunk,
                    paged=args.paged, page_size=args.page_size,
                    kv_pool_tokens=args.pool_tokens,
                    prefix_cache=args.prefix_cache,
                    session_ttl=args.session_ttl if args.sessions else None,
                    host_pool_tokens=args.host_pool_tokens,
                    spill_bw=args.spill_bw * 1e9,
                    spill_dtype=args.spill_dtype,
                    slice_tokens=args.slice_tokens,
                    recorder=recorder, tracer=tracer,
                    fault_plan=plan)
    return sim, sched


def _fault_line(res, plan) -> str:
    """Recovery counters under an armed plan — what the chaos smoke
    greps; replays of the same SPEC must print this line verbatim."""
    return (f"faults[{plan.spec()}]: {res.fault_events} injected, "
            f"{res.fault_retries} retried, {res.fault_kills} killed, "
            f"{res.quarantined} quarantined; restore channel: "
            f"{res.restore_stalls} stalls, {res.restore_retries} retries, "
            f"{res.restore_failures} failures, {res.restore_sheds} sheds, "
            f"{res.restore_timeouts} timeouts, "
            f"{res.corruptions} corruptions")


def _run_sim(cfg, args, reqs, recorder=None, tracer=None, plan=None):
    """Cost-model pass over the identical workload (validation mode)."""
    sim, sched = _make_sim(cfg, args, plan, recorder, tracer)
    # recovery backoff + restart penalties inflate virtual makespan
    # under an armed plan — give the storm room to finish
    res = sim.run(reqs, time_limit=40000.0 if plan is not None else 3600.0)
    prefix_info = ""
    if args.prefix_cache:
        prefix_info = (f"prefix hits {res.prefix_hits}/{res.prefix_lookups} "
                       f"({res.prefix_hit_rate():.2f}), "
                       f"{res.prefill_tokens_skipped} prompt tokens "
                       f"skipped, {res.prefix_pages_saved} pages saved; ")
    if args.sessions:
        prefix_info += (
            f"session hits {res.session_hits}/{res.session_lookups}, "
            f"{res.session_hit_tokens} transcript tokens restored, "
            f"{res.tail_pages_reused} tails reused, "
            f"{res.sessions_expired} expired; ")
    if args.kv_spill:
        prefix_info += (
            f"spill[{args.spill_dtype}]: {res.spilled_pages} pages "
            f"({res.spilled_bytes} B) out, "
            f"{res.restored_pages} back ({res.restored_tokens} tokens, "
            f"{res.restored_bytes} B), "
            f"{res.spill_drops} dropped, "
            f"{res.spill_hold_events} holds; ")
    print(f"[sim] served {len(res.finished())}/{len(reqs)} requests in "
          f"{res.makespan:.2f} virtual s; {res.throughput_tok_s():.0f} tok/s; "
          f"SLO {res.slo_attainment():.2f}; OOM {res.oom_events}; "
          f"peak pool {res.peak_pool}; preemptions {res.preempt_events}; "
          f"{prefix_info}"
          f"buckets: {[(b.low, b.up) for b in sched.buckets.buckets]}")
    print(f"[sim] {_tail_line(res)}")
    print(f"[sim] kv util (time-weighted) {res.kv_util_time_weighted:.2f}; "
          f"padding waste {res.padding_waste_ratio():.3f}; "
          f"blame {_fmt_blame(res.blame())}")
    if plan is not None:
        print(f"[sim] {_fault_line(res, plan)}")
    return res


def _transcript(backend, r):
    """Full token path: prompt (slice promotion included) + synthetic
    generated continuation past the promoted boundary — the identity
    the drain/resume smoke compares bit-for-bit."""
    toks = [] if r.tokens is None else \
        [int(t) for t in r.tokens[:r.prompt_len]]
    gen = backend.generated_tokens(r)[r.sliced_tokens:]
    return toks + [int(t) for t in gen]


def _drain_resume_sim(cfg, args, reqs, plan):
    """--drain-after smoke: reference run, a second run checkpointed at
    T virtual seconds (drain -> JSON round-trip), then a COLD loop
    resuming the checkpoint.  Every request must finish exactly once
    across the drained+resumed pair with token ids bit-identical to the
    uninterrupted reference, else exit nonzero (the CI gate greps the
    identity line)."""
    t = args.drain_after
    ref_sim, _ = _make_sim(cfg, args, plan)
    ref = ref_sim.run(copy.deepcopy(reqs), time_limit=40000.0)
    want = {r.rid: _transcript(ref_sim.loop.backend, r)
            for r in ref.requests if r.finished >= 0 and not r.dropped}

    sim1, _ = _make_sim(cfg, args, plan)
    res1 = sim1.run(copy.deepcopy(reqs), time_limit=40000.0, drain_at=t)
    ck = LoopCheckpoint.from_json(sim1.loop.drain().to_json())
    sim2, _ = _make_sim(cfg, args, plan)
    res2 = sim2.run(ck.restore_requests(), time_limit=40000.0,
                    resume_clock=ck.now)

    done1 = {r.rid: r for r in res1.requests
             if r.finished >= 0 and not r.dropped}
    done2 = {r.rid: r for r in res2.requests
             if r.finished >= 0 and not r.dropped}
    print(f"[drain] checkpoint at t={ck.now:.2f}s: {len(done1)} finished "
          f"pre-drain, {len(ck.requests)} in-flight/queued + "
          f"{len(ck.held_turns)} held turns serialized, "
          f"{len(done2)} finished after cold resume")
    if plan is not None:
        print(f"[drain] {_fault_line(res2, plan)}")
    errs = []
    if set(done1) & set(done2):
        errs.append(f"duplicated rids {sorted(set(done1) & set(done2))}")
    if set(done1) | set(done2) != set(want):
        lost = set(want) - (set(done1) | set(done2))
        extra = (set(done1) | set(done2)) - set(want)
        errs.append(f"lost {sorted(lost)} / extra {sorted(extra)}")
    for rid, r in done1.items():
        if rid in want and _transcript(sim1.loop.backend, r) != want[rid]:
            errs.append(f"rid {rid} diverged pre-drain")
    for rid, r in done2.items():
        if rid in want and _transcript(sim2.loop.backend, r) != want[rid]:
            errs.append(f"rid {rid} diverged after resume")
    if errs:
        raise SystemExit("[drain] resume NOT work-preserving: "
                         + "; ".join(errs))
    print(f"[drain] drain-resume token ids identical "
          f"({len(done1)}+{len(done2)}/{len(want)} requests, "
          f"checkpoint {len(ck.to_json())} B)")


def _fmt_blame(b) -> str:
    return "{" + ", ".join(f"{k}: {v:.3f}s" for k, v in b.items()) + "}"


def _finish_trace(args, recorder) -> None:
    if recorder is None:
        return
    print("batch log:", recorder.batch_log)
    if args.trace_record:
        recorder.save(args.trace_record,
                      meta={"arch": args.arch, "backend": args.backend,
                            "burst_factor": args.burst_factor})
        print(f"recorded {len(recorder.snapshots)} requests -> "
              f"{args.trace_record}")


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--backend", default="jax", choices=["jax", "sim"],
                    help="real JAX engine or analytic cost model")
    ap.add_argument("--chunk", type=int, default=None,
                    help="chunked-prefill span in tokens (default: whole "
                         "prompt)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV decode pool (block-table admission)")
    ap.add_argument("--page-size", type=int, default=128,
                    help="KV page size in tokens (with --paged)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix cache on the paged pool "
                         "(radix lookup + refcounted shared pages; "
                         "implies --paged)")
    ap.add_argument("--prefix-scenarios", type=int, default=0,
                    help="shared-prefix workload family: N distinct "
                         "system prompts with Zipf reuse (0 = classic "
                         "length-only workload)")
    ap.add_argument("--prefix-tokens", type=int, default=128,
                    help="tokens per shared system prompt (with "
                         "--prefix-scenarios)")
    ap.add_argument("--sessions", type=int, default=0,
                    help="multi-turn conversation workload: N sessions "
                         "of --turns turns each; enables the session "
                         "retention layer (implies --prefix-cache)")
    ap.add_argument("--turns", type=int, default=3,
                    help="turns per session (with --sessions)")
    ap.add_argument("--session-ttl", type=float, default=60.0,
                    help="seconds a finished conversation's KV stays "
                         "pinned awaiting the next turn")
    ap.add_argument("--think-time", type=float, default=0.0,
                    help="mean think-time gap (s) between a session's "
                         "turns; > --session-ttl exercises the "
                         "expiry/demote path (with --kv-spill the next "
                         "turn RESTORES instead of re-prefilling)")
    ap.add_argument("--kv-spill", action="store_true",
                    help="host-RAM spill tier under the retention layer "
                         "(core/retention.py): pressure/TTL eviction "
                         "copies cold retained pages device->host and a "
                         "later hit restores them instead of "
                         "re-prefilling (implies --prefix-cache)")
    ap.add_argument("--host-pool-tokens", type=int, default=None,
                    help="host-RAM spill budget in KV tokens (default: "
                         "4x the device pool)")
    ap.add_argument("--spill-bw", type=float, default=16.0,
                    help="host<->device link bandwidth in GB/s used to "
                         "price spill/restore transfers")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "int8"],
                    help="device KV pool precision: int8 halves the "
                         "per-token cache bytes, so the SAME HBM byte "
                         "budget holds ~2x the pages (Eq. 6 and the "
                         "paged pool are both byte-denominated)")
    ap.add_argument("--spill-dtype", default="bf16",
                    choices=["bf16", "int8", "int4"],
                    help="host spill tier precision: compressed spill "
                         "retains 2-4x more transcript pages under the "
                         "same --host-pool-tokens budget and each "
                         "restore moves proportionally fewer PCIe bytes")
    ap.add_argument("--pool-tokens", type=int, default=None,
                    help="total pooled KV tokens (default: slots x "
                         "cache_len — the contiguous pool's budget — on "
                         "the jax backend; the cost model's HBM-derived "
                         "KV budget on --backend sim)")
    ap.add_argument("--trace-record", default=None, metavar="PATH",
                    help="record this run's request stream to a "
                         "versioned JSONL trace (data/trace.py) that "
                         "replays bit-identically through either "
                         "backend")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the run's event timeline as Chrome "
                         "trace-event / Perfetto JSON "
                         "(core/telemetry.py Tracer; open in "
                         "ui.perfetto.dev — one track per bucket / "
                         "spill channel / executor)")
    ap.add_argument("--trace-replay", default=None, metavar="PATH",
                    help="serve a recorded trace instead of generating "
                         "a workload (arrival timestamps preserved; "
                         "smoke clamps are NOT applied — the trace is "
                         "authoritative)")
    ap.add_argument("--burst-factor", type=float, default=1.0,
                    help="> 1 switches to the heterogeneous trace "
                         "family: chat/longctx/batch class mix with "
                         "bursty diurnal arrivals peaking at this "
                         "multiple of --rps")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--dataset", default="mixed")
    ap.add_argument("--rps", type=float, default=8.0)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--trigger", default="waste",
                    choices=["majority", "waste"])
    ap.add_argument("--sched", default="bucket",
                    choices=["bucket", "goodput"],
                    help="queue policy: arrival-order BucketServe or "
                         "the deadline-slack goodput scheduler "
                         "(urgency-ordered buckets, slack-aware "
                         "preemption; DESIGN.md §8)")
    ap.add_argument("--slice-tokens", type=int, default=None,
                    help="slice-boundary preemption: a preempted decode "
                         "request keeps generated work up to the last "
                         "multiple of N tokens and resumes after "
                         "re-prefill instead of restarting")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="arm the deterministic fault injector "
                         "(core/faults.py), e.g. 'seed=7,"
                         "decode_step=0.02,restore_stall=0.3,stall_s=2'; "
                         "identical SPECs replay bit-identically on "
                         "either backend")
    ap.add_argument("--drain-after", type=float, default=None, metavar="T",
                    help="work-preserving drain/resume smoke (--backend "
                         "sim): checkpoint a run at T virtual seconds, "
                         "JSON round-trip, resume on a COLD loop and "
                         "require token ids bit-identical to an "
                         "uninterrupted reference")
    args = ap.parse_args(argv)
    plan = FaultPlan.parse(args.fault_plan) if args.fault_plan else None
    # an explicit host budget means the user wants the tier on — don't
    # silently discard their sizing because --kv-spill was omitted
    args.kv_spill = args.kv_spill or args.host_pool_tokens is not None
    args.prefix_cache = (args.prefix_cache or args.sessions > 0
                         or args.kv_spill)
    args.paged = args.paged or args.prefix_cache

    if args.smoke:
        cfg = get_smoke_config(args.arch, max_seq_len=256)
    else:
        cfg = get_config(args.arch)
    if args.kv_dtype == "int8" and cfg.kv_cache_dtype != "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if args.kv_spill and args.host_pool_tokens is None:
        args.host_pool_tokens = 4 * (args.pool_tokens
                                     or args.slots * cfg.max_seq_len)
    if not args.kv_spill:
        args.host_pool_tokens = None
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; serve prefill-only "
                         "workloads via max_new_tokens=1")

    if args.trace_replay:
        tw = TraceWorkload(args.trace_replay)
        reqs = tw.requests()
        print(f"replaying {len(reqs)} recorded requests from "
              f"{args.trace_replay} (meta: {tw.meta})")
    elif args.sessions:
        # multi-turn conversations: lengths are sized to FIT the
        # window up front (a later clamp would break the loop's
        # transcript composition, which must hit prompt_len exactly)
        per_turn = max(cfg.max_seq_len // (2 * args.turns) - 8, 8)
        spec = WorkloadSpec(dataset=args.dataset, rps=args.rps,
                            max_model_len=cfg.max_seq_len,
                            vocab_size=cfg.vocab_size,
                            sessions=args.sessions, turns=args.turns,
                            utterance_tokens=per_turn, max_new_tokens=8,
                            think_time_s=args.think_time)
        reqs = generate(spec)
    elif args.burst_factor > 1.0:
        # heterogeneous trace family: three-class mix under bursty
        # diurnal arrivals (per-class SLOs ride on each request)
        spec = WorkloadSpec(dataset=args.dataset, rps=args.rps,
                            n_requests=args.requests,
                            max_model_len=cfg.max_seq_len,
                            prefix_groups=args.prefix_scenarios,
                            prefix_tokens=args.prefix_tokens,
                            vocab_size=cfg.vocab_size,
                            class_mix=DEFAULT_CLASS_MIX,
                            burst_factor=args.burst_factor)
        reqs = generate(spec)
        _shorten(reqs, cfg.max_seq_len)
    else:
        spec = WorkloadSpec(dataset=args.dataset, rps=args.rps,
                            n_requests=args.requests,
                            max_model_len=cfg.max_seq_len,
                            prefix_groups=args.prefix_scenarios,
                            prefix_tokens=args.prefix_tokens,
                            vocab_size=cfg.vocab_size)
        reqs = generate(spec)
        _shorten(reqs, cfg.max_seq_len)

    # the recorder doubles as the replay checker: both a recorded run
    # and its replay print the formed-batch log, so CI can diff them
    recorder = TraceRecorder() if (args.trace_record
                                   or args.trace_replay) else None
    tracer = Tracer() if args.trace_out else None

    if args.drain_after is not None:
        if args.backend != "sim":
            raise SystemExit("--drain-after is a cost-model smoke: "
                             "use --backend sim")
        _drain_resume_sim(cfg, args, reqs, plan)
        return

    if args.backend == "sim":
        _run_sim(cfg, args, reqs, recorder, tracer, plan)
        _finish_trace(args, recorder)
        _finish_timeline(args, tracer)
        return

    mesh = None
    if args.data * args.model > 1:
        mesh = make_host_mesh(args.data, args.model)
        shctx.set_mesh(mesh)

    params = init_params(cfg, mesh)
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)}; params sharded")

    budget = MemoryBudget(
        hbm_bytes_per_device=device_hbm_bytes(args.smoke),
        n_devices=max(args.data * args.model, 1),
        weight_bytes=sum(x.nbytes for x in jax.tree.leaves(params)))
    sched = _make_sched(cfg, budget, args)
    engine = ServingEngine(cfg, params, sched, max_slots=args.slots,
                           cache_len=cfg.max_seq_len,
                           moe_impl="local", chunk_tokens=args.chunk,
                           paged=args.paged, page_size=args.page_size,
                           kv_pool_tokens=args.pool_tokens,
                           prefix_cache=args.prefix_cache,
                           session_ttl=args.session_ttl if args.sessions
                           else None,
                           host_pool_tokens=args.host_pool_tokens,
                           spill_bw=args.spill_bw * 1e9,
                           spill_dtype=args.spill_dtype,
                           slice_tokens=args.slice_tokens,
                           recorder=recorder, tracer=tracer,
                           fault_plan=plan)

    engine.submit(reqs)
    t0 = time.perf_counter()
    done = engine.run(max_wall_s=900)
    dt = time.perf_counter() - t0
    toks = sum(r.generated for r in done)
    paged_info = ""
    if args.paged:
        kv = engine.backend.kv
        paged_info = (f"pages: {kv.alloc.n_pages} x {kv.page_size} tok, "
                      f"free {kv.alloc.free_pages()}; "
                      f"peak pool {engine.result.peak_pool}; "
                      f"preemptions {engine.result.preempt_events}; ")
        if args.prefix_cache:
            r = engine.result
            paged_info += (
                f"prefix hits {r.prefix_hits}/{r.prefix_lookups} "
                f"({r.prefix_hit_rate():.2f}), {r.prefill_tokens_skipped} "
                f"prompt tokens skipped, {r.prefix_pages_saved} pages "
                f"saved, {r.shared_pages_peak} peak shared; ")
        if args.sessions:
            r = engine.result
            paged_info += (
                f"session hits {r.session_hits}/{r.session_lookups}, "
                f"{r.session_hit_tokens} transcript tokens restored, "
                f"{r.tail_pages_reused} tails reused, "
                f"{r.sessions_retained} retained; ")
        if args.kv_spill:
            r = engine.result
            paged_info += (
                f"spill[{args.spill_dtype}]: {r.spilled_pages} pages "
                f"({r.spilled_bytes} B) out, "
                f"{r.restored_pages} back ({r.restored_tokens} tokens, "
                f"{r.restored_bytes} B), "
                f"{r.spill_drops} dropped, "
                f"{r.spill_hold_events} holds; ")
    print(f"served {len(done)}/{len(reqs)} requests, {toks} tokens in "
          f"{dt:.1f}s; prefill shapes: {engine.n_prefill_shapes}; "
          f"decode steps interleaved between prefill chunks: "
          f"{engine.interleaved_decode_steps}; {paged_info}"
          f"buckets: {[(b.low, b.up) for b in sched.buckets.buckets]}")
    print(_tail_line(engine.result))
    print(f"kv util (time-weighted) "
          f"{engine.result.kv_util_time_weighted:.2f}; padding waste "
          f"{engine.result.padding_waste_ratio():.3f}; "
          f"blame {_fmt_blame(engine.result.blame())}")
    if plan is not None:
        print(_fault_line(engine.result, plan))
    _finish_trace(args, recorder)
    _finish_timeline(args, tracer)
    if engine.loop.wall_cut:
        raise SystemExit(f"wall limit cut the run: served {len(done)}/"
                         f"{len(reqs)} requests")
    return engine


if __name__ == "__main__":
    main()
