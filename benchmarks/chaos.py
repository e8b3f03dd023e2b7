"""Seeded fault-storm chaos gate (DESIGN.md §9, beyond-paper).

The heterogeneous burst workload (trace_replay's recipe at gate scale)
runs three times through the full paged/prefix/session serving stack:
once fault-free (the reference) and twice under an IDENTICAL seeded
:class:`FaultPlan` arming every injection site — transient decode-step
device errors, prefill-chunk failures, restore-channel stalls and hard
errors, host-slot bit-rot, maintain-tick hiccups.

CI gates (the harness, benchmarks/run.py, exits nonzero on any
AssertionError):
  (1) zero lost / zero duplicated requests: every submitted request
      ends terminal (finished or dropped), rids stay unique, and every
      finished request generated exactly ``max_new_tokens``;
  (2) invariants survive the storm: every latency ledger closes and
      conserves to 1e-6 (``fault_retry`` included) and the block
      allocator balances exactly (free + unique-live == n_pages,
      free-host + spilled == host_pages);
  (3) the storm is deterministic: both faulted runs produce
      bit-identical final request states AND bit-identical injector
      fire logs — chaos replays;
  (4) recovery is work-preserving, not merely survivable: storm
      goodput (output tok/s) stays within a bounded factor of the
      fault-free reference.
"""
from __future__ import annotations

import time

from repro.core.batcher import MemoryBudget
from repro.core.faults import FaultPlan
from repro.core.scheduler import BucketServeScheduler, SchedulerConfig
from repro.core.simulator import A100X4, CostModel, Simulator
from repro.data.workload import DEFAULT_CLASS_MIX, WorkloadSpec, generate

from .common import CFG, emit

PAGE = 128

# every site armed; rates hot enough that each recovery path fires at
# gate scale yet most requests still complete (the goodput gate needs a
# serving system, not a crash loop)
STORM = FaultPlan(seed=11, rates={
    "decode_step": 0.03, "prefill_chunk": 0.08, "restore_stall": 0.3,
    "restore_error": 0.3, "host_corrupt": 0.15, "maintain_tick": 0.05},
    stall_s=0.4)

# gate (4): recovery overhead bound.  Retries, backoff, restart
# penalties and quarantines cost real throughput; losing more than
# 60% of fault-free goodput at these rates means recovery is burning
# work it should preserve.
MIN_GOODPUT_RATIO = 0.4


def _run(plan, *, n, slots):
    budget = MemoryBudget(hbm_bytes_per_device=40 * 2 ** 30, n_devices=3,
                          weight_bytes=CFG.param_count() * 2)
    sched = BucketServeScheduler(CFG, budget, SchedulerConfig(
        max_batch=8, memory_model="paged", page_size=PAGE))
    sim = Simulator(sched, CostModel(CFG, A100X4), mode="disagg",
                    decode_slot_cap=slots, paged=True, page_size=PAGE,
                    kv_pool_tokens=16 * 1024, prefix_cache=True,
                    session_ttl=600.0, host_pool_tokens=64 * 1024,
                    fault_plan=plan)
    spec = WorkloadSpec(rps=6.0, n_requests=n,
                        max_model_len=CFG.max_seq_len,
                        vocab_size=CFG.vocab_size,
                        class_mix=DEFAULT_CLASS_MIX, burst_factor=4.0,
                        diurnal_period_s=40.0, burst_every_s=15.0,
                        burst_duration_s=4.0, prefix_groups=4,
                        prefix_tokens=2 * PAGE, sessions=8, turns=3,
                        think_time_s=2.0, seed=7)
    reqs = generate(spec)
    t0 = time.perf_counter()
    res = sim.run(reqs, time_limit=40000.0)
    return res, sim, len(reqs), time.perf_counter() - t0


def _states(res):
    return sorted((r.rid, r.finished, r.first_token, r.generated,
                   r.dropped, r.quarantined) for r in res.requests)


def _gate_terminal_conserved(res, n_submitted, name):
    rids = [r.rid for r in res.requests]
    assert len(rids) == len(set(rids)) == n_submitted, \
        f"{name}: {len(rids)} results for {n_submitted} submitted"
    for r in res.requests:
        assert r.finished >= 0 or r.dropped, \
            f"{name}: rid {r.rid} lost (neither finished nor dropped)"
        if r.finished >= 0 and not r.dropped:
            assert r.generated == r.max_new_tokens, \
                f"{name}: rid {r.rid} finished short/long"
        led = r.ledger
        assert led is not None and led.closed, \
            f"{name}: rid {r.rid} ledger left open"
        assert led.conserved(), \
            f"{name}: rid {r.rid} ledger residual {led.residual()}"


def _gate_alloc(sim, name):
    a = sim.loop.backend.kv.alloc
    assert a.free_pages() + a.live_pages() == a.n_pages, \
        f"{name}: device pages leaked"
    assert a.free_host_slots() + a.spilled_slots() == a.host_pages, \
        f"{name}: host slots leaked"


def main(quick: bool = False) -> None:
    n = 48 if quick else 120
    slots = 64
    runs = [("reference", None), ("storm", STORM), ("storm-replay", STORM)]
    rows, by_name, sims, counts = [], {}, {}, {}
    for name, plan in runs:
        res, sim, n_sub, wall = _run(plan, n=n, slots=slots)
        by_name[name], sims[name], counts[name] = res, sim, n_sub
        rows.append([
            "chaos", name, n_sub,
            sum(1 for r in res.requests if r.finished >= 0),
            sum(1 for r in res.requests if r.dropped),
            res.fault_events, res.fault_retries, res.fault_kills,
            res.quarantined, res.restore_stalls, res.restore_retries,
            res.restore_failures, res.restore_sheds, res.restore_timeouts,
            res.corruptions,
            f"{res.output_tok_s():.1f}", f"{res.slo_attainment():.3f}",
            f"{res.makespan:.2f}", f"{wall:.1f}"])
    emit(rows, ["table", "run", "submitted", "finished", "dropped",
                "faults", "retries", "kills", "quarantined", "stalls",
                "rst_retries", "rst_failures", "sheds", "timeouts",
                "corruptions", "out_tok_s", "slo_att", "makespan_s",
                "wall_s"])

    ref, storm = by_name["reference"], by_name["storm"]
    # gates (1) + (2) on every run, faulted or not
    for name in by_name:
        _gate_terminal_conserved(by_name[name], counts[name], name)
        _gate_alloc(sims[name], name)
    # the reference is actually fault-free and the storm actually stormed
    assert ref.fault_events == 0 and ref.quarantined == 0
    assert storm.fault_events > 0 and storm.fault_retries > 0, \
        "storm fired no faults — the plan is dead, the gate is vacuous"
    # gate (3): bit-identical replay
    assert _states(by_name["storm"]) == _states(by_name["storm-replay"]), \
        "storm replay diverged — fault decisions are not deterministic"
    assert sims["storm"].faults.log == sims["storm-replay"].faults.log, \
        "injector fire logs diverged between identical storm runs"
    # gate (4): bounded goodput degradation
    ratio = storm.output_tok_s() / max(ref.output_tok_s(), 1e-9)
    assert ratio >= MIN_GOODPUT_RATIO, \
        (f"storm goodput {storm.output_tok_s():.1f} tok/s is "
         f"{ratio:.2f}x the fault-free {ref.output_tok_s():.1f} — "
         f"recovery burned more than {1 - MIN_GOODPUT_RATIO:.0%} of "
         "the machine")
    print(f"claim,storm_goodput_ratio,{ratio:.3f}")
    print(f"claim,storm_slo_attainment,{storm.slo_attainment():.3f}")
    print(f"claim,storm_fault_events,{storm.fault_events}")
    print(f"claim,storm_quarantined,{storm.quarantined}")
    print()
