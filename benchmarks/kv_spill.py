"""Host-RAM KV spill tier: cold vs unpin vs spill, across spill dtypes.

Beyond-paper table (PR 5 + quantized tiers, DESIGN.md §3 "Host spill
tier" / "Tier precision"): the paged cost model serves the SAME
multi-turn conversation workload under an HBM pool deliberately too
small to retain every session —

* ``cold``  — paged pool only, no retention: every turn re-prefills its
  whole transcript (the pre-PR-3 floor);
* ``unpin`` — PR 4 retention: radix + session tails, but eviction under
  pressure DESTROYS retained pages, so squeezed-out sessions pay a full
  re-prefill on their next turn;
* ``spill-bf16/int8/int4`` — the host tier at each spill precision,
  all under the SAME ``host_pool_tokens`` budget.  The budget is a
  byte quantity (``host_tier_geometry``), so a compressed tier holds
  ~2x (int8) / ~3.5x (int4) more transcript pages AND each restore
  moves proportionally fewer PCIe bytes.

The host budget is deliberately TIGHT (a small multiple of the device
pool): the bf16 tier saturates and drops warm transcripts to its host
LRU, which is exactly the regime where compression pays.

CI gates (the harness, benchmarks/run.py, exits nonzero on any
AssertionError):
  (1) every run's composed prompts are BIT-IDENTICAL across all modes
      — a restore that corrupted or clamped transcripts shows up here;
  (2) the bf16 spill run re-prefills STRICTLY FEWER prompt tokens than
      the unpin run — the tier buys real work, not PR 4 savings;
  (3) int8/int4 spill moves STRICTLY FEWER bytes per spilled page than
      bf16 (compression actually happened on the wire);
  (4) at the same host budget, the int4 tier ends the run retaining
      >= 2x the bf16 tier's host pages (or, if saturation patterns
      differ, strictly fewer ``spill_drops``) AND spends strictly less
      total restore time — the quantized-tiers acceptance claim.
"""
from __future__ import annotations

import time

from repro.core.batcher import MemoryBudget
from repro.core.request import TaskType
from repro.core.scheduler import BucketServeScheduler, SchedulerConfig
from repro.core.simulator import A100X4, CostModel, Simulator
from repro.data.workload import WorkloadSpec, generate

from .common import CFG, emit

PAGE = 128


def _run(spec: WorkloadSpec, *, session_ttl, host_pool_tokens,
         pool_tokens: int, slots: int, prefix_cache: bool = True,
         spill_dtype: str = "bf16"):
    reqs = generate(spec)
    budget = MemoryBudget(hbm_bytes_per_device=A100X4.hbm_bytes,
                          n_devices=A100X4.decode_chips,
                          weight_bytes=CFG.param_count() * 2)
    sched = BucketServeScheduler(CFG, budget, SchedulerConfig(
        max_batch=slots, memory_model="paged", page_size=PAGE))
    sim = Simulator(sched, CostModel(CFG, A100X4), mode="disagg",
                    decode_slot_cap=slots, paged=True, page_size=PAGE,
                    kv_pool_tokens=pool_tokens, prefix_cache=prefix_cache,
                    session_ttl=session_ttl,
                    host_pool_tokens=host_pool_tokens,
                    spill_dtype=spill_dtype)
    t0 = time.perf_counter()
    res = sim.run(reqs, time_limit=14400.0)
    ids = {}
    for r in res.requests:
        ids[r.rid] = None if r.tokens is None else r.tokens.tolist()
    return res, ids, sim.backend, time.perf_counter() - t0


def main(quick: bool = False) -> None:
    sessions = 12 if quick else 24
    turns = 3
    utter = 384 if quick else 512
    slots = 8 if quick else 16
    # the pool holds one max-length request plus a few transcripts:
    # retention pressure is structural, not incidental
    pool_tokens = (40 if quick else 128) * PAGE
    # TIGHT host budget — host_tokens is a bf16-reference byte budget,
    # so this buys exactly 32 (96) bf16 slots but ~3.8x that many int4
    # slots.  Sized at roughly a third of the workload's spill demand so
    # the bf16 tier saturates and drops warm transcripts (the regime
    # compression rescues) while the int4 tier still holds everything
    host_tokens = (32 if quick else 96) * PAGE
    spec = WorkloadSpec(dataset="alpaca", rps=4.0, sessions=sessions,
                        turns=turns, utterance_tokens=utter,
                        max_new_tokens=32 if quick else 64,
                        think_time_s=2.0, task_type=TaskType.OFFLINE,
                        max_model_len=CFG.max_seq_len, seed=0,
                        vocab_size=CFG.vocab_size)
    modes = [("cold", dict(session_ttl=None, host_pool_tokens=None,
                           prefix_cache=False)),
             ("unpin", dict(session_ttl=600.0, host_pool_tokens=None))]
    for dt in ("bf16", "int8", "int4"):
        modes.append((f"spill-{dt}",
                      dict(session_ttl=600.0, host_pool_tokens=host_tokens,
                           spill_dtype=dt)))
    rows, by_mode, ids_by_mode, alloc_by_mode = [], {}, {}, {}
    for name, kw in modes:
        res, ids, backend, wall = _run(spec, pool_tokens=pool_tokens,
                                       slots=slots, **kw)
        by_mode[name] = res
        ids_by_mode[name] = ids
        alloc_by_mode[name] = backend.kv.alloc
        rows.append([
            "kv_spill", name, sessions, turns,
            res.prefill_tokens_processed, res.prefill_tokens_skipped,
            f"{res.session_hits}/{res.session_lookups}",
            backend.kv.alloc.host_pages, backend.kv.alloc.spilled_slots(),
            res.spilled_pages, res.restored_pages,
            res.spilled_bytes, res.restored_bytes,
            res.spill_drops, res.spill_hold_events,
            f"{res.restore_time_total:.3f}",
            f"{res.output_tok_s():.1f}", f"{res.makespan:.2f}",
            f"{wall:.1f}"])
    emit(rows, ["table", "mode", "sessions", "turns", "prefill_tokens",
                "tokens_skipped", "session_hits", "host_slots",
                "retained_pages", "spilled_pages", "restored_pages",
                "spilled_bytes", "restored_bytes", "spill_drops",
                "holds", "restore_s", "out_tok_s", "makespan_s",
                "wall_s"])
    # gate 1: token ids identical across all modes (the cost model
    # composes transcripts from deterministic per-rid synthetic
    # generated ids, so any divergence means a run clamped/corrupted a
    # transcript)
    for name in list(by_mode):
        if name == "cold":
            continue
        assert ids_by_mode[name] == ids_by_mode["cold"], \
            f"{name} run changed token ids vs the cold run"
    # gate 2: the host tier must buy real re-prefill work beyond unpin
    unpin = by_mode["unpin"]
    bf16 = by_mode["spill-bf16"]
    int8 = by_mode["spill-int8"]
    int4 = by_mode["spill-int4"]
    assert bf16.spilled_pages > 0 and bf16.restored_pages > 0, \
        "spill run moved no pages — the tier is dead under pressure"
    assert bf16.prefill_tokens_processed < unpin.prefill_tokens_processed, \
        (f"spill run prefilled {bf16.prefill_tokens_processed} >= the "
         f"unpin run's {unpin.prefill_tokens_processed} prompt tokens — "
         "the host tier added nothing over destructive eviction")
    # gate 3: compression actually happened on the wire
    bytes_per_page = {
        n: by_mode[n].spilled_bytes / max(by_mode[n].spilled_pages, 1)
        for n in ("spill-bf16", "spill-int8", "spill-int4")}
    assert bytes_per_page["spill-int8"] < bytes_per_page["spill-bf16"], \
        f"int8 spill moved {bytes_per_page} bytes/page — not compressed"
    assert bytes_per_page["spill-int4"] < bytes_per_page["spill-int8"], \
        f"int4 spill moved {bytes_per_page} bytes/page — not compressed"
    # gate 4: the quantized-tiers acceptance claim — same host budget,
    # >= 2x retained host pages (or strictly fewer drops when the
    # saturation patterns differ) AND strictly less restore time
    ret4 = alloc_by_mode["spill-int4"].spilled_slots()
    retb = alloc_by_mode["spill-bf16"].spilled_slots()
    assert ret4 >= 2 * retb or int4.spill_drops < bf16.spill_drops, \
        (f"int4 tier retained {ret4} host pages vs bf16's {retb} and "
         f"dropped {int4.spill_drops} vs {bf16.spill_drops} — the "
         "compressed tier bought no extra retention")
    assert int4.restore_time_total < bf16.restore_time_total, \
        (f"int4 restore time {int4.restore_time_total:.3f}s >= bf16's "
         f"{bf16.restore_time_total:.3f}s — compressed restores moved "
         "no fewer PCIe bytes")
    red = 1 - bf16.prefill_tokens_processed / max(
        unpin.prefill_tokens_processed, 1)
    print(f"claim,prefill_token_reduction_vs_unpin,{red:.3f}")
    print(f"claim,session_hit_rate_spill,{bf16.session_hit_rate():.3f}")
    print(f"claim,session_hit_rate_unpin,{unpin.session_hit_rate():.3f}")
    print(f"claim,int4_retained_pages_ratio_vs_bf16,"
          f"{ret4 / max(retb, 1):.2f}")
    print(f"claim,int4_restore_time_ratio_vs_bf16,"
          f"{int4.restore_time_total / max(bf16.restore_time_total, 1e-9):.3f}")
    print(f"claim,int8_session_hit_rate,{int8.session_hit_rate():.3f}")
    print()
