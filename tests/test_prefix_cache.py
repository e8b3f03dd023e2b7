"""Cross-request prefix cache, end to end (DESIGN.md §3 "Prefix
sharing").

The tentpole claims under test:

* the radix index maps a prompt to its longest cached FULL-page run,
  capped so at least one suffix token always prefills (first-token
  logits need a forward pass); the final partial page is never shared
  (the COW rule by construction);
* on the shared-prefix workload (page 128, same HBM budget) the
  prefix-cache run emits per-request token ids BIT-IDENTICAL to the
  cold run while prefilling >= 40% fewer prompt tokens (acceptance);
* eviction is refcount-aware: LRU zero-ref cached prefixes are
  reclaimed before any live request is preempted, and a preemption
  victim whose pages are all shared (release frees nothing) is never
  picked (the starvation case);
* the O(n^2) victim list scan in extend_for_decode is gone — a large-
  pool run picks the SAME victims as a quadratic reference (timing-free
  regression);
* engine and cost-model backends make identical admission decisions
  AND identical hit counts (backend parity extends to the cache);
* hit metrics flow: PrefixCache.stats -> ServeResult / GlobalMonitor.
"""
import numpy as np
import pytest

from repro.core.paging import (BlockAllocator, admit_blocks,
                               extend_for_decode)
from repro.core.prefix_cache import PrefixCache
from repro.core.request import Request, TaskType
from repro.data.workload import WorkloadSpec, generate

PAGE = 8


def _req(rid, plen=10, mnt=4, arrival=0.0):
    return Request(rid=rid, prompt_len=plen, max_new_tokens=mnt,
                   arrival=arrival)


def _toks(seed, n):
    return np.random.default_rng(seed).integers(0, 1000, n).astype(np.int32)


# ------------------------------------------------------------ radix unit --
class TestRadixIndex:
    def test_lookup_matches_longest_cached_run(self):
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(0, 3 * PAGE + 3)
        t = a.alloc(0, len(toks) + 1)
        cache.register(a, toks, t)               # 3 full pages indexed
        assert len(cache) == 3

        pages, hit = cache.lookup(toks)
        assert hit == 3 * PAGE and pages == t[:3]
        # diverging third page -> only the first two match
        other = toks.copy()
        other[2 * PAGE] += 1
        pages, hit = cache.lookup(other)
        assert hit == 2 * PAGE and pages == t[:2]
        # diverging FIRST token -> cold
        other = toks.copy()
        other[0] += 1
        assert cache.lookup(other) == ([], 0)

    def test_lookup_never_matches_entire_prompt(self):
        """At least one suffix token must prefill: a prompt of exactly
        k full pages matches at most k-1."""
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(1, 2 * PAGE)
        t = a.alloc(0, len(toks) + 1)
        cache.register(a, toks, t)
        pages, hit = cache.lookup(toks)
        assert hit == PAGE and pages == t[:1]    # capped at (2P-1)//P = 1

    def test_partial_final_page_never_indexed(self):
        """The COW rule by construction: a prompt's trailing partial
        page stays private — only full pages enter the radix."""
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(2, PAGE + 3)                # 1 full + partial
        t = a.alloc(0, len(toks) + 1)
        cache.register(a, toks, t)
        assert len(cache) == 1
        assert cache.pinned_pages() == t[:1]

    def test_register_first_wins_on_duplicates(self):
        """Two concurrent cold requests with the same prefix: the
        second's identical chunk keeps the FIRST's canonical page; the
        duplicate page stays private (refcount untouched)."""
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(3, PAGE + 1)
        t0 = a.alloc(0, len(toks) + 1)
        t1 = a.alloc(1, len(toks) + 1)
        cache.register(a, toks, t0)
        cache.register(a, toks, t1)
        assert len(cache) == 1
        assert cache.pinned_pages() == t0[:1]
        assert a.refs(t0[0]) == 2                # table + pin
        assert a.refs(t1[0]) == 1                # private duplicate

    def test_pinned_prefix_survives_writer_release(self):
        a = BlockAllocator(n_pages=4, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(4, 2 * PAGE + 1)
        t = a.alloc(0, len(toks) + 1)
        cache.register(a, toks, t)
        a.release(0)
        assert a.free_pages() == 2               # 2 pinned, 3rd page freed
        pages, hit = cache.lookup(np.concatenate([toks, _toks(9, 4)]))
        assert hit == 2 * PAGE and pages == t[:2]

    def test_lru_eviction_leaf_first_skips_referenced(self):
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        cache = PrefixCache(PAGE)
        old = _toks(5, 2 * PAGE + 1)             # chain of 2 nodes
        t_old = a.alloc(0, len(old) + 1)
        cache.register(a, old, t_old)
        young = _toks(6, PAGE + 1)
        t_y = a.alloc(1, len(young) + 1)
        cache.register(a, young, t_y)
        a.release(0)                             # old chain zero-ref
        # rid 1 still references its page: only the old chain is
        # evictable, and leaf-first means depth-2 before depth-1
        assert cache.evict_one(a) is True
        assert cache.evict_one(a) is True
        assert cache.evict_one(a) is False       # young page refs==2
        assert len(cache) == 1
        assert cache.pinned_pages() == t_y[:1]
        assert cache.stats.evictions == 2

    def test_admit_blocks_shares_and_evicts_under_pressure(self):
        """admit_blocks with a cache: a hit request allocs only its
        suffix pages; when the free list starves, zero-ref cached
        prefixes are evicted before admission fails."""
        a = BlockAllocator(n_pages=6, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(7, 4 * PAGE)
        r0 = _req(0, plen=len(toks));  r0.tokens = toks
        assert admit_blocks(a, [r0], lambda r: r.prompt_len + 1,
                            cache=cache, tokens_of=lambda r: r.tokens) == 1
        cache.register(a, toks, a.table(0))      # 4 pages indexed
        a.release(0)
        # same prompt again: shares 3 pages (cap), allocs 2 private
        r1 = _req(1, plen=len(toks));  r1.tokens = toks
        assert admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                            cache=cache, tokens_of=lambda r: r.tokens) == 1
        assert r1.prefix_hit_tokens == 3 * PAGE
        assert a.table(1)[:3] == cache.pinned_pages()[:3]
        assert a.shared_pages() == 3
        # a cold 1-page request now starves (0 free): LRU eviction of
        # the zero-ref 4th cached page (the only one no table holds)
        # makes room
        r2 = _req(2, plen=len(toks));  r2.tokens = _toks(8, 4 * PAGE)
        assert admit_blocks(a, [r2], lambda r: PAGE,
                            cache=cache, tokens_of=lambda r: r.tokens) == 1
        assert cache.stats.evictions >= 1
        assert r2.prefix_hit_tokens == 0

    def test_stats_and_monitor_accounting(self):
        from repro.core.monitor import GlobalMonitor
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(10, 2 * PAGE + 1)
        r0 = _req(0, plen=len(toks));  r0.tokens = toks
        r1 = _req(1, plen=len(toks));  r1.tokens = toks
        admit_blocks(a, [r0], lambda r: r.prompt_len + 1,
                     cache=cache, tokens_of=lambda r: r.tokens)
        cache.register(a, toks, a.table(0))
        admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                     cache=cache, tokens_of=lambda r: r.tokens)
        assert cache.stats.lookups == 2 and cache.stats.hits == 1
        assert cache.stats.hit_tokens == 2 * PAGE
        assert cache.pages_saved() == 2
        assert cache.stats.peak_shared == 2
        mon = GlobalMonitor()
        for r in (r0, r1):
            mon.on_prefix_lookup(r.prefix_hit_tokens, PAGE)
        assert mon.prefix_lookups == 2 and mon.prefix_hits == 1
        assert mon.prefix_hit_rate() == 0.5
        assert mon.prefix_pages_saved == 2
        snap = mon.snapshot(0.0)
        assert snap.prefix_hit_rate == 0.5


# ------------------------------------------- refcount-aware preemption ----
class TestRefcountAwareEviction:
    def test_victim_with_zero_reclaimable_never_picked(self):
        """Starvation case (satellite): the YOUNGEST candidate's pages
        are all shared — releasing it frees nothing.  The old policy
        (pure youngest-first) would evict it and starve forever; the
        refcount-aware policy picks the younger request that actually
        frees pages."""
        a = BlockAllocator(n_pages=5, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(0, PAGE)
        donor = _req(0, plen=PAGE - 1, arrival=0.0)
        a.alloc(0, PAGE)
        cache.register(a, toks, a.table(0))      # page pinned
        mid = _req(1, plen=2 * PAGE - 1, arrival=1.0)
        a.alloc(1, 2 * PAGE)                     # 2 private pages
        yng = _req(2, plen=PAGE - 1, arrival=2.0)
        a.alloc(2, PAGE, shared=a.table(0))      # ALL pages shared
        assert a.free_pages() == 2

        # the oldest needs 3 more pages: cache eviction is impossible
        # (the cached page is still referenced by rid 0 and rid 2), so
        # preemption must pick MID (reclaimable 2) over YNG (0)
        donor.generated = 3 * PAGE
        victims = extend_for_decode(
            a, [donor, mid, yng],
            lambda r: r.prompt_len + 1 + r.generated, cache=cache)
        assert victims == [mid]
        assert a.holds(yng.rid) and not a.holds(mid.rid)
        assert len(a.table(donor.rid)) == 4

    def test_cache_evicted_before_any_preemption(self):
        """Zero-ref cached pages are the cheapest reclaim: with enough
        of them, NO live request is preempted."""
        a = BlockAllocator(n_pages=5, page_size=PAGE)
        cache = PrefixCache(PAGE)
        toks = _toks(1, 2 * PAGE)
        a.alloc(0, 2 * PAGE)
        cache.register(a, toks, a.table(0))
        a.release(0)                             # both pages zero-ref
        old = _req(1, plen=2 * PAGE - 1, arrival=0.0)
        yng = _req(2, plen=PAGE - 1, arrival=1.0)
        a.alloc(1, 2 * PAGE)
        a.alloc(2, PAGE)                         # free list empty now
        old.generated = PAGE
        yng.generated = PAGE
        victims = extend_for_decode(
            a, [old, yng], lambda r: r.prompt_len + 1 + r.generated,
            cache=cache)
        assert victims == []                     # nobody preempted
        assert cache.stats.evictions == 2
        assert len(cache) == 0

    def test_self_preempt_when_nothing_reclaimable(self):
        """Degenerate endgame: no cache, no younger victim frees
        anything — the starving request preempts itself (termination)."""
        a = BlockAllocator(n_pages=1, page_size=PAGE)
        cache = PrefixCache(PAGE)
        r0 = _req(0, plen=PAGE - 1, arrival=0.0)
        t0 = a.alloc(0, PAGE)
        cache.register(a, _toks(2, PAGE), t0)    # r0's page pinned
        yng = _req(1, plen=PAGE - 1, arrival=1.0)
        a.alloc(1, PAGE, shared=t0)              # fully shared
        r0.generated = PAGE
        victims = extend_for_decode(
            a, [r0, yng], lambda r: r.prompt_len + 1 + r.generated,
            cache=cache)
        assert victims and victims[0] is r0


# ------------------------------------------------- O(n^2) victim scan -----
def _reference_extend_for_decode(alloc, pool, decode_tokens, cache=None):
    """The pre-PR-3 quadratic formulation (victims tracked in a LIST,
    membership via linear scans) with the refcount-aware policy —
    semantics the set-keyed implementation must reproduce exactly."""
    victims = []
    order = sorted(pool, key=lambda r: (r.arrival, r.rid))
    for r in order:
        if r in victims:                         # O(n) scan (the bug)
            continue
        while alloc.extend(r.rid, decode_tokens(r)) is None:
            if cache is not None and cache.evict_one(alloc):
                continue
            younger = [c for c in order if c not in victims and c is not r
                       and alloc.holds(c.rid)
                       and (c.arrival, c.rid) > (r.arrival, r.rid)
                       and alloc.reclaimable(c.rid) > 0]
            if not younger:
                alloc.release(r.rid)
                victims.append(r)
                break
            v = max(younger, key=lambda c: (alloc.reclaimable(c.rid),
                                            c.arrival, c.rid))
            alloc.release(v.rid)
            victims.append(v)
    return victims


class TestVictimSetRegression:
    def test_large_pool_victims_unchanged(self):
        """Timing-free regression for the set-keyed victim tracking: on
        a 300-request pool under heavy page pressure, the victim
        SEQUENCE matches the quadratic reference exactly."""
        rng = np.random.default_rng(0)

        def build():
            a = BlockAllocator(n_pages=700, page_size=PAGE)
            pool = []
            rng2 = np.random.default_rng(42)
            for rid in range(300):
                plen = int(rng2.integers(1, 3 * PAGE))
                r = _req(rid, plen=plen,
                         arrival=float(rng2.integers(0, 50)))
                if a.alloc(rid, plen + 1) is None:
                    break
                r.generated = int(rng2.integers(1, 2 * PAGE))
                pool.append(r)
            return a, pool

        a1, pool1 = build()
        a2, pool2 = build()
        need = lambda r: r.prompt_len + 1 + r.generated
        got = extend_for_decode(a1, pool1, need)
        ref = _reference_extend_for_decode(a2, pool2, need)
        assert [v.rid for v in got] == [v.rid for v in ref]
        assert len(got) > 10                     # pressure actually bit
        # allocator end states agree too
        assert a1.free_pages() == a2.free_pages()
        for r in pool1:
            assert a1.table(r.rid) == a2.table(r.rid)


# ------------------------------------------------ workload scenarios ------
class TestSharedPrefixWorkload:
    def test_prefix_scenarios_share_token_prefixes(self):
        spec = WorkloadSpec(dataset="alpaca", rps=4.0, n_requests=40,
                            max_model_len=2048, prefix_groups=3,
                            prefix_tokens=256, seed=5, vocab_size=1000)
        reqs = generate(spec)
        heads = {}
        for r in reqs:
            assert r.tokens is not None
            assert len(r.tokens) == r.prompt_len
            assert r.prompt_len > 256             # prefix + >=1 suffix
            heads.setdefault(bytes(r.tokens[:256].tobytes()),
                             []).append(r.rid)
        assert 1 < len(heads) <= 3                # N distinct prefixes
        assert max(len(v) for v in heads.values()) >= 2   # Zipf reuse
        # deterministic
        again = generate(spec)
        for a, b in zip(reqs, again):
            assert np.array_equal(a.tokens, b.tokens)

    def test_classic_spec_unchanged(self):
        spec = WorkloadSpec(dataset="alpaca", n_requests=8, seed=1)
        assert all(r.tokens is None for r in generate(spec))


# --------------------------------------------------- engine end to end ----
import jax                                                    # noqa: E402

from repro.configs import get_smoke_config                    # noqa: E402
from repro.core import (BucketServeScheduler, MemoryBudget,   # noqa: E402
                        SchedulerConfig)
from repro.core.engine import ServingEngine                   # noqa: E402
from repro.core.simulator import (A100X4, CostModel,          # noqa: E402
                                  Simulator)
from repro.models import transformer as tfm                   # noqa: E402

BUDGET = MemoryBudget(hbm_bytes_per_device=2 ** 30, n_devices=1,
                      weight_bytes=0)


def _prefix_workload(cfg, n, pre, groups=2, seed=3, max_new=4):
    spec = WorkloadSpec(dataset="alpaca", rps=1e6, n_requests=n, seed=seed,
                        max_model_len=cfg.max_seq_len,
                        task_type=TaskType.OFFLINE, prefix_groups=groups,
                        prefix_tokens=pre, vocab_size=cfg.vocab_size)
    reqs = generate(spec)
    for r in reqs:
        r.max_new_tokens = min(r.max_new_tokens, max_new)
    return reqs


def _engine(cfg, params, *, slots, prefix_cache, page_size=128,
            pool_tokens=None, chunk_tokens=None):
    sched = BucketServeScheduler(cfg, BUDGET, SchedulerConfig(
        max_batch=slots, memory_model="paged", page_size=page_size))
    return ServingEngine(cfg, params, sched, max_slots=slots,
                         cache_len=cfg.max_seq_len, paged=True,
                         page_size=page_size, kv_pool_tokens=pool_tokens,
                         chunk_tokens=chunk_tokens,
                         prefix_cache=prefix_cache)


class TestPrefixCacheEngine:
    """Acceptance (ISSUE 3): on the shared-prefix workload, page 128,
    same HBM budget, the prefix-cache run produces per-request token ids
    BIT-IDENTICAL to the cold run while prefilling >= 40% fewer total
    prompt tokens."""

    def test_shared_prefix_tokens_identical_and_40pct_fewer_prefill(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs, res = {}, {}
        for cached in (False, True):
            reqs = _prefix_workload(cfg, 16, 512)
            eng = _engine(cfg, params, slots=4, prefix_cache=cached,
                          pool_tokens=8 * 1024)
            eng.submit(reqs)
            done = eng.run(max_wall_s=600)
            assert len(done) == len(reqs)
            outs[cached] = {r.rid: eng.outputs[r.rid] for r in reqs}
            res[cached] = eng.result
            for r in reqs:
                assert len(eng.outputs[r.rid]) == r.max_new_tokens
            # allocator invariant: free + unique-live == total; at run
            # end only the cache's pins remain live
            be = eng.backend
            assert be.kv.alloc.free_pages() + be.kv.alloc.live_pages() \
                == be.kv.alloc.n_pages
            if cached:
                assert be.kv.alloc.live_pages() == len(be.kv.retention.prefix)
                assert be.kv.retention.prefix.clear(be.kv.alloc) > 0
                assert be.kv.alloc.free_pages() == be.kv.alloc.n_pages
            else:
                assert be.kv.alloc.live_pages() == 0

        assert outs[True] == outs[False]          # bit-identical token ids
        cold = res[False].prefill_tokens_processed
        cached_toks = res[True].prefill_tokens_processed
        assert cached_toks <= 0.6 * cold, (cached_toks, cold)
        # skipped + processed adds back up to the cold run's work
        assert cached_toks + res[True].prefill_tokens_skipped == cold
        assert res[False].prefix_lookups == 0     # cold run has no cache
        assert res[True].prefix_hits > 0
        assert res[True].prefix_hit_rate() > 0.5
        assert res[True].prefix_pages_saved * 128 \
            == res[True].prefix_hit_tokens
        assert res[True].shared_pages_peak > 0

    def test_monitor_sees_hits(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=256)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        reqs = _prefix_workload(cfg, 8, 128, max_new=2)
        eng = _engine(cfg, params, slots=4, prefix_cache=True)
        eng.submit(reqs)
        assert len(eng.run(max_wall_s=300)) == 8
        mon = eng.sched.monitor
        assert mon.prefix_lookups == 8
        assert mon.prefix_hits == eng.result.prefix_hits
        assert mon.prefix_hit_tokens == eng.result.prefix_hit_tokens

    def test_composes_with_chunked_prefill(self):
        """Chunk plans that START past a cached prefix must slice spans
        at absolute offsets: tokens identical to the cold chunked run."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=256)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs = {}
        for cached in (False, True):
            reqs = _prefix_workload(cfg, 8, 128, seed=9, max_new=3)
            eng = _engine(cfg, params, slots=4, prefix_cache=cached,
                          page_size=64, chunk_tokens=96)
            eng.submit(reqs)
            assert len(eng.run(max_wall_s=300)) == 8
            outs[cached] = {r.rid: eng.outputs[r.rid] for r in reqs}
        assert outs[True] == outs[False]

    def test_preemption_with_cache_still_correct(self):
        """A pool tight enough to force mid-decode preemption AND cache
        eviction: every request completes with outputs identical to an
        unconstrained cached run (restarts re-match the prefix)."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=256)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs = {}
        for pool in (None, 8 * 64):              # ample vs 8-page squeeze
            reqs = _prefix_workload(cfg, 7, 128, seed=11, max_new=24)
            eng = _engine(cfg, params, slots=4, prefix_cache=True,
                          page_size=64, pool_tokens=pool)
            eng.submit(reqs)
            done = eng.run(max_wall_s=600)
            assert len(done) == len(reqs)
            outs[pool] = {r.rid: eng.outputs[r.rid] for r in reqs}
            for r in reqs:
                assert len(eng.outputs[r.rid]) == r.max_new_tokens
        assert outs[None] == outs[8 * 64]

    def test_uncacheable_arch_rejected(self):
        cfg = get_smoke_config("rwkv6-3b")
        assert not cfg.prefix_cacheable
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(AssertionError):
            _engine(cfg, params, slots=4, prefix_cache=True)
        cfg2 = get_smoke_config("qwen3-14b", max_seq_len=256,
                                sliding_window=64)
        assert not cfg2.prefix_cacheable          # ring cache: no resume

    def test_fused_modes_rejected(self):
        """coupled/static bypass backend.chunk_plan — a prefix cache
        there would count hits without ever skipping prefill."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=256)
        with pytest.raises(AssertionError, match="disagg"):
            Simulator(BucketServeScheduler(cfg, BUDGET, SchedulerConfig()),
                      CostModel(cfg, A100X4), mode="coupled", paged=True,
                      prefix_cache=True)


class _RecordingScheduler(BucketServeScheduler):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.formed = []

    def next_prefill_batch(self, now):
        batch = super().next_prefill_batch(now)
        if batch is not None:
            self.formed.append(tuple(r.rid for r in batch.requests))
        return batch


class TestPrefixBackendParity:
    """CostModelBackend mirrors the engine's prefix-cache accounting:
    identical batches AND identical hit counts on the same workload."""

    N, SLOTS, PAGE_ = 12, 4, 128

    def _sched(self, cfg):
        return _RecordingScheduler(cfg, BUDGET, SchedulerConfig(
            max_batch=self.SLOTS, memory_model="paged",
            page_size=self.PAGE_))

    def _workload(self, cfg):
        reqs = _prefix_workload(cfg, self.N, 128, max_new=3)
        for r in reqs:      # all queued up-front: identical first ticks
            r.arrival = 0.0 # on the wall and the virtual clock
        return reqs

    def test_same_batches_and_hit_counts(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=256)
        # ample pool: parity is asserted in the no-starvation regime —
        # under page pressure the two substrates requeue at different
        # (wall vs virtual) times by design, as in PR 2's parity test
        pool_tokens = 64 * self.PAGE_

        sched_sim = self._sched(cfg)
        sim = Simulator(sched_sim, CostModel(cfg, A100X4), mode="disagg",
                        decode_slot_cap=self.SLOTS, paged=True,
                        page_size=self.PAGE_, kv_pool_tokens=pool_tokens,
                        cache_len=cfg.max_seq_len, prefix_cache=True)
        res_sim = sim.run(self._workload(cfg))
        assert len(res_sim.finished()) == self.N

        sched_eng = self._sched(cfg)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, sched_eng, max_slots=self.SLOTS,
                            cache_len=cfg.max_seq_len, paged=True,
                            page_size=self.PAGE_,
                            kv_pool_tokens=pool_tokens, prefix_cache=True)
        eng.submit(self._workload(cfg))
        assert len(eng.run(max_wall_s=300)) == self.N
        res_eng = eng.result

        assert sched_sim.formed == sched_eng.formed
        assert res_sim.prefix_lookups == res_eng.prefix_lookups > 0
        assert res_sim.prefix_hits == res_eng.prefix_hits > 0
        assert res_sim.prefix_hit_tokens == res_eng.prefix_hit_tokens
        assert res_sim.prefill_tokens_skipped \
            == res_eng.prefill_tokens_skipped > 0
        assert sim.backend.kv.alloc.n_pages == eng.backend.kv.alloc.n_pages
