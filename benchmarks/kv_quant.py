"""Beyond-paper: int8 KV-cache variant through the Eq.-(6) batcher.

Quantized caches double the Eq.-(6) token budget.  The gain appears in
the BUDGET-LIMITED regime (v5e 16 GiB chips, weights taking most of
HBM): the decode pool doubles and the per-iteration weight read
amortizes across 2x the tokens.  On memory-rich A100-40G at the paper's
scale the pool is not budget-limited and int8 is neutral — both rows are
shown.

Two memory models per (hardware, cache dtype) cell, both on the unified
ServingLoop/CostModelBackend path:

* ``sum``   — Eq. (6) on the HBM-derived token budget (the classic row:
  int8 doubles ``eq6_token_budget``);
* ``paged`` — a FIXED ``kv_pool_tokens`` byte budget pushed through
  ``paging.device_pool_pages``: the ``pool_pages`` column shows the
  int8 pool genuinely holding ~2x the pages of the bf16 pool under the
  SAME bytes (byte-denominated accounting, DESIGN.md §3 "Tier
  precision") — asserted as a CI gate, not just printed.
"""
from __future__ import annotations

from repro.configs import get_config
from repro.core.batcher import MemoryBudget
from repro.core.scheduler import BucketServeScheduler, SchedulerConfig
from repro.core.serving_loop import LoopConfig, ServingLoop
from repro.core.simulator import (A100X4, CostModel, CostModelBackend,
                                  HardwareSpec)
from repro.data.workload import generate

from .common import emit, offline_spec

V5E_4 = HardwareSpec("v5e-4", 197e12, 819e9, 50e9, 16 * 2 ** 30,
                     prefill_chips=2, decode_chips=2)

PAGE = 128
POOL_TOKENS = 512 * PAGE          # fixed bf16-reference byte budget


def _run(cfg, hw, *, paged: bool, n: int):
    budget = MemoryBudget(hbm_bytes_per_device=hw.hbm_bytes,
                          n_devices=hw.decode_chips,
                          weight_bytes=cfg.param_count() * 2)
    sched = BucketServeScheduler(cfg, budget, SchedulerConfig(
        memory_model="paged" if paged else "sum", page_size=PAGE))
    cost = CostModel(cfg, hw)
    backend = CostModelBackend(
        cost, kv_budget=cost.kv_budget_tokens(hw.decode_chips),
        paged=paged, page_size=PAGE,
        kv_pool_tokens=POOL_TOKENS if paged else None)
    loop = ServingLoop(sched, backend, LoopConfig(mode="disagg"))
    res = loop.run(generate(offline_spec("mixed", n)), time_limit=7200)
    return res, sched, backend


def main(quick: bool = False):
    rows = []
    n = 60 if quick else 300
    pool_pages = {}
    for hw_name, hw in (("v5e-4(16GiB)", V5E_4), ("a100x4(40GiB)", A100X4)):
        for variant in ("", "int8"):
            cfg = get_config("llama2-13b", variant=variant)
            for paged in (False, True):
                res, sched, backend = _run(cfg, hw, paged=paged, n=n)
                pages = backend.kv.alloc.n_pages if paged else "-"
                if paged:
                    pool_pages[(hw_name, variant)] = backend.kv.alloc.n_pages
                rows.append(["kv_quant", hw_name, variant or "bf16",
                             "paged" if paged else "sum",
                             int(sched.batcher.token_budget()), pages,
                             round(res.output_tok_s(), 0),
                             round(res.throughput_tok_s(), 0),
                             res.oom_events])
    emit(rows, ["table", "hardware", "cache", "mem_model",
                "eq6_token_budget", "pool_pages", "out_tok_s", "tok_s",
                "oom"])
    # CI gate: the SAME kv_pool_tokens byte budget buys ~2x the pages
    # at int8 cache precision (byte-denominated pool sizing)
    for hw_name in ("v5e-4(16GiB)", "a100x4(40GiB)"):
        bf16 = pool_pages[(hw_name, "")]
        int8 = pool_pages[(hw_name, "int8")]
        assert int8 >= 1.8 * bf16, \
            (f"{hw_name}: int8 pool holds {int8} pages vs bf16's {bf16} "
             "under the same byte budget — pool sizing is not "
             "byte-denominated")


if __name__ == "__main__":
    main()
