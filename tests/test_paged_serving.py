"""Paged KV decode pool, end to end (DESIGN.md §3).

The tentpole claims under test:

* allocation layout is INVISIBLE to results — the paged engine emits
  per-request token ids bit-identical to the contiguous slot pool on the
  same workload;
* under the SAME HBM budget, page-granular admission sustains >= 2x the
  concurrent decode requests of the contiguous pool on the mixed
  (heterogeneous-length) workload;
* block exhaustion mid-decode preempts the youngest request through the
  requeue path and every request still completes, with correct outputs;
* the cost-model backend mirrors the engine's block accounting (backend
  parity holds in paged mode);
* OOM-backoff recovery advances only on successful dispatch (the
  ``_cap_scale`` mutate-on-read regression).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (BucketServeScheduler, MemoryBudget, SchedulerConfig,
                        TaskType)
from repro.core.engine import ServingEngine
from repro.core.request import Request
from repro.core.simulator import A100X4, CostModel, Simulator
from repro.data.workload import WorkloadSpec, generate
from repro.models import transformer as tfm

BUDGET = MemoryBudget(hbm_bytes_per_device=2 ** 30, n_devices=1,
                      weight_bytes=0)


def _mixed_requests(n, max_seq, max_new=6, seed=0):
    """The paper's heterogeneous case, clamped for CPU smoke runs the
    same way launch/serve.py does."""
    spec = WorkloadSpec(dataset="mixed", rps=1e6, n_requests=n, seed=seed,
                        max_model_len=max_seq, task_type=TaskType.OFFLINE)
    reqs = generate(spec)
    for r in reqs:
        r.max_new_tokens = min(r.max_new_tokens, max_new)
        r.prompt_len = min(r.prompt_len, max_seq - 16)
    return reqs


def _engine(cfg, params, *, slots, paged, page_size=128, pool_tokens=None,
            max_batch=None):
    sched = BucketServeScheduler(cfg, BUDGET, SchedulerConfig(
        max_batch=max_batch or slots,
        memory_model="paged" if paged else "sum", page_size=page_size))
    eng = ServingEngine(cfg, params, sched, max_slots=slots,
                        cache_len=cfg.max_seq_len, paged=paged,
                        page_size=page_size, kv_pool_tokens=pool_tokens)
    return eng


class TestPagedEngineParity:
    """Same mixed workload through the paged and contiguous pools ->
    identical emitted token ids per request, AND (the acceptance bar)
    page-granular admission sustains >= 2x the concurrency of the
    contiguous pool under the same HBM budget with page size 128."""

    def test_mixed_workload_tokens_identical_and_2x_concurrency(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        contig_slots = 2
        budget_tokens = contig_slots * cfg.max_seq_len   # 2048 = 16 pages

        outs, peaks = {}, {}
        for paged in (False, True):
            reqs = _mixed_requests(20, cfg.max_seq_len)
            eng = _engine(cfg, params,
                          slots=12 if paged else contig_slots,
                          max_batch=12 if paged else contig_slots,
                          paged=paged, page_size=128,
                          pool_tokens=budget_tokens if paged else None)
            eng.submit(reqs)
            done = eng.run(max_wall_s=600)
            assert len(done) == len(reqs)
            outs[paged] = {r.rid: eng.outputs[r.rid] for r in reqs}
            peaks[paged] = eng.result.peak_pool
            for r in reqs:
                assert len(eng.outputs[r.rid]) == r.max_new_tokens

        assert outs[True] == outs[False]          # bit-identical token ids
        assert peaks[False] <= contig_slots
        assert peaks[True] >= 2 * peaks[False], peaks

    def test_windowed_ring_cache_parity(self):
        """Ring (sliding-window) caches page the same way: virtual slot
        pos % W indirects through the table; parity must survive wraps
        and a window that does not divide the page size."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=128,
                               sliding_window=48)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(9)
        outs = {}
        for paged in (False, True):
            reqs = [Request(rid=i, prompt_len=int(rng.integers(16, 100)),
                            max_new_tokens=int(rng.integers(4, 30)),
                            arrival=0.0, task_type=TaskType.OFFLINE)
                    for i in range(6)]
            rng = np.random.default_rng(9)        # same lengths both runs
            eng = _engine(cfg, params, slots=4, paged=paged, page_size=32)
            eng.submit(reqs)
            done = eng.run(max_wall_s=300)
            assert len(done) == 6
            outs[paged] = {r.rid: eng.outputs[r.rid] for r in reqs}
        assert outs[True] == outs[False]

    def test_paged_composes_with_chunked_prefill(self):
        """Chunked prefill writes a contiguous batch cache; the paged
        insert chops it into pages — the two features must compose
        without changing tokens."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=256)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs = {}
        for paged in (False, True):
            sched = BucketServeScheduler(cfg, BUDGET, SchedulerConfig(
                max_batch=4, memory_model="paged" if paged else "sum",
                page_size=64))
            eng = ServingEngine(cfg, params, sched, max_slots=4,
                                cache_len=256, chunk_tokens=64, paged=paged,
                                page_size=64)
            rng = np.random.default_rng(7)
            reqs = [Request(rid=i, prompt_len=int(rng.integers(40, 200)),
                            max_new_tokens=5, arrival=0.0,
                            task_type=TaskType.OFFLINE) for i in range(5)]
            eng.submit(reqs)
            assert len(eng.run(max_wall_s=300)) == 5
            outs[paged] = {r.rid: eng.outputs[r.rid] for r in reqs}
        assert outs[True] == outs[False]

    def test_int8_kv_paged_parity(self):
        """The quantized-KV serving variant pages its scale pools too:
        int8 paged tokens must match int8 contiguous tokens (scale
        entries scattered to the wrong page would silently corrupt)."""
        cfg = dataclasses.replace(
            get_smoke_config("qwen3-14b", max_seq_len=128),
            kv_cache_dtype="int8")
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs = {}
        for paged in (False, True):
            rng = np.random.default_rng(5)
            reqs = [Request(rid=i, prompt_len=int(rng.integers(8, 90)),
                            max_new_tokens=int(rng.integers(3, 9)),
                            arrival=0.0, task_type=TaskType.OFFLINE)
                    for i in range(6)]
            eng = _engine(cfg, params, slots=4, paged=paged, page_size=32)
            eng.submit(reqs)
            assert len(eng.run(max_wall_s=300)) == 6
            outs[paged] = {r.rid: eng.outputs[r.rid] for r in reqs}
        assert outs[True] == outs[False]

    def test_unpaged_arch_rejected(self):
        """Attention-free archs have no KV to page."""
        cfg = get_smoke_config("rwkv6-3b")
        assert not tfm.supports_paged_decode(cfg)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(AssertionError):
            _engine(cfg, params, slots=4, paged=True)

    def test_too_small_explicit_pool_rejected(self):
        """An explicit kv_pool_tokens below one full request + trash
        page must raise, not silently inflate (honest 'same HBM budget'
        comparisons depend on it)."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=256)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="too small"):
            _engine(cfg, params, slots=4, paged=True, page_size=128,
                    pool_tokens=128)
        with pytest.raises(ValueError, match="too small"):
            Simulator(BucketServeScheduler(cfg, BUDGET, SchedulerConfig()),
                      CostModel(cfg, A100X4), mode="disagg", paged=True,
                      page_size=128, kv_pool_tokens=128, cache_len=256)


class TestPagedPreemption:
    def test_block_exhaustion_preempts_youngest_and_completes(self):
        """A pool too small for the live set forces mid-decode page
        exhaustion: the youngest request is evicted through the requeue
        path, re-prefills later, and every request still finishes with a
        full, correct output stream."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=128)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        reqs = [Request(rid=i, prompt_len=int(rng.integers(20, 40)),
                        max_new_tokens=int(rng.integers(20, 40)),
                        arrival=0.0, task_type=TaskType.OFFLINE)
                for i in range(6)]
        # 5 pages of 32: 4 usable after the trash page — one full request
        eng = _engine(cfg, params, slots=4, paged=True, page_size=32,
                      pool_tokens=5 * 32)
        eng.submit(reqs)
        done = eng.run(max_wall_s=600)
        assert len(done) == 6
        assert eng.result.preempt_events > 0
        # preempted requests restart from scratch: outputs are complete
        # and match an unconstrained reference run
        ref_eng = _engine(cfg, params, slots=4, paged=True, page_size=32)
        ref_reqs = [dataclasses.replace(r, arrival=0.0, generated=0,
                                        first_token=-1.0, prefill_start=-1.0,
                                        finished=-1.0)
                    for r in reqs]
        ref_eng.submit(ref_reqs)
        ref_eng.run(max_wall_s=600)
        for r in reqs:
            assert len(eng.outputs[r.rid]) == r.max_new_tokens
            assert eng.outputs[r.rid] == ref_eng.outputs[r.rid]
        # arrival-rate stats were never double-counted by the requeues
        assert len(eng.sched.monitor.seq_lens) == 6

    def test_pages_all_freed_after_run(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=128)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        eng = _engine(cfg, params, slots=4, paged=True, page_size=32,
                      pool_tokens=5 * 32)
        rng = np.random.default_rng(1)
        reqs = [Request(rid=i, prompt_len=int(rng.integers(8, 60)),
                        max_new_tokens=int(rng.integers(2, 20)),
                        arrival=0.0, task_type=TaskType.OFFLINE)
                for i in range(8)]
        eng.submit(reqs)
        assert len(eng.run(max_wall_s=600)) == 8
        be = eng.backend
        assert be.kv.alloc.free_pages() == be.kv.alloc.n_pages   # no leaks
        assert be.kv.alloc.live_pages() == 0


class _RecordingScheduler(BucketServeScheduler):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.formed = []

    def next_prefill_batch(self, now):
        batch = super().next_prefill_batch(now)
        if batch is not None:
            self.formed.append(tuple(r.rid for r in batch.requests))
        return batch


class TestPagedBackendParity:
    """CostModelBackend mirrors the engine's block accounting: the same
    scheduler driven through both backends in PAGED mode still makes
    identical scheduling decisions."""

    N, SLOTS = 12, 4
    PAGE = 128

    def _workload(self):
        rng = np.random.default_rng(11)
        return [Request(rid=i, prompt_len=int(rng.integers(8, 100)),
                        max_new_tokens=4, arrival=0.0,
                        task_type=TaskType.ONLINE) for i in range(self.N)]

    def _sched(self, cfg):
        return _RecordingScheduler(cfg, BUDGET, SchedulerConfig(
            max_batch=self.SLOTS, memory_model="paged",
            page_size=self.PAGE))

    def test_same_batches_and_buckets_paged(self):
        # cache_len BELOW max_seq_len: both backends must derive the
        # page cap from the same cfg.attn_cache_len(cache_len) rule
        cfg = get_smoke_config("qwen3-14b", max_seq_len=256)
        cache_len = 128
        pool_tokens = 16 * self.PAGE

        sched_sim = self._sched(cfg)
        sim = Simulator(sched_sim, CostModel(cfg, A100X4), mode="disagg",
                        decode_slot_cap=self.SLOTS, paged=True,
                        page_size=self.PAGE, kv_pool_tokens=pool_tokens,
                        cache_len=cache_len)
        res = sim.run(self._workload())
        assert len(res.finished()) == self.N
        assert res.preempt_events == 0

        sched_eng = self._sched(cfg)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, sched_eng, max_slots=self.SLOTS,
                            cache_len=cache_len, paged=True,
                            page_size=self.PAGE,
                            kv_pool_tokens=pool_tokens)
        eng.submit(self._workload())
        done = eng.run(max_wall_s=300)
        assert len(done) == self.N
        assert eng.result.preempt_events == 0
        assert eng.backend.kv.alloc.n_pages == sim.backend.kv.alloc.n_pages

        assert sched_sim.formed == sched_eng.formed
        assert [(b.low, b.up) for b in sched_sim.buckets.buckets] == \
               [(b.low, b.up) for b in sched_eng.buckets.buckets]


class TestOOMBackoffRecovery:
    """Regression: ``_cap_scale`` used to advance the recovery factor on
    EVERY read, so idle scheduler ticks (no batch formed) silently
    restored the cap after an OOM.  Recovery now advances only via
    ``notify_dispatch`` (called by the loop per successful dispatch)."""

    def _sched(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=128)
        return BucketServeScheduler(cfg, BUDGET, SchedulerConfig())

    def test_cap_scale_is_a_pure_read(self):
        s = self._sched()
        assert s._cap_scale() == 1.0
        s.notify_oom()
        shrunk = s._cap_scale()
        assert shrunk == pytest.approx(0.85)
        for _ in range(50):                       # reads never recover
            s._cap_scale()
        assert s._cap_scale() == pytest.approx(shrunk)

    def test_recovery_only_on_dispatch(self):
        s = self._sched()
        s.notify_oom()
        shrunk = s._cap_scale()
        s.notify_dispatch()
        once = s._cap_scale()
        assert once == pytest.approx(shrunk * 1.02)
        for _ in range(200):
            s.notify_dispatch()
        assert s._cap_scale() == 1.0              # capped at full

    def test_idle_ticks_do_not_recover(self):
        """A scheduler polled with an empty queue (the loop's idle tick)
        must not creep its cap back up."""
        from repro.core.baselines import DistServeLikeScheduler
        cfg = get_smoke_config("qwen3-14b", max_seq_len=128)
        s = DistServeLikeScheduler(cfg, BUDGET)
        s.notify_oom()
        shrunk = s._cap_scale()
        for t in range(100):
            assert s.next_prefill_batch(float(t)) is None
        assert s._cap_scale() == pytest.approx(shrunk)
