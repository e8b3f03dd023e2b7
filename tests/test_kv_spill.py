"""Host-RAM KV spill tier (PR 5, DESIGN.md §3 "Host spill tier").

The tentpole claims under test:

* eviction is no longer (only) destructive: each retention rung —
  expired session tails, LRU cold radix prefixes, live session tails —
  SPILLS its victim to a host pool before it would drop it, and drops
  only when the host budget is also exhausted (host-side LRU);
* a lookup whose hit continues into spilled pages initiates a
  host->device RESTORE and the request is HELD (``Request.spill_wait``)
  instead of being admitted to re-prefill restorable KV; the restore
  latency lands on that request's TTFT;
* restored pages are BIT-IDENTICAL to what was spilled: on the
  sessions x turns workload with a pool tight enough that PR 4 unpins
  live sessions, the --kv-spill run produces token ids equal to the
  no-spill run while turns >= 2 prefill >= 40% fewer prompt tokens
  than the unpin baseline under the same HBM budget;
* engine and cost-model backends agree on formed batches, spill and
  restore counts, and session hit counts (backend parity extends to
  spill decisions);
* satellites: one shared ``maintain`` path (``PagedKv.tick``) drives
  TTL expiry and copy completion identically in both backends;
  ``decode_preempt`` uploads block tables incrementally (O(new pages),
  regression-tested against the full-rescan reference).
"""
import math

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.paging import (BlockAllocator, PagedKv, admit_blocks,
                               extend_for_decode)
from repro.core.request import Request, TaskType
from repro.core.retention import KvRetention

PAGE = 8


def _req(rid, plen=10, mnt=4, arrival=0.0, sid=None, turn=0):
    return Request(rid=rid, prompt_len=plen, max_new_tokens=mnt,
                   arrival=arrival, session_id=sid, turn=turn)


def _toks(seed, n):
    return np.random.default_rng(seed).integers(0, 1000, n).astype(np.int32)


def _release(rt, a, req, path, now=0.0):
    req.generated = max(req.generated, 1)
    rt.on_release(a, req, path, now)


def _rt(a, *, ttl=1000.0, host=8, sec=0.5):
    return KvRetention(PAGE, session_ttl=ttl, host_pool_pages=host,
                       spill_seconds_per_page=sec)


class _RecordingCopier:
    """Protocol double: records the byte-movement calls the backend
    copier would receive, so unit tests can assert dispatch order."""

    def __init__(self):
        self.events = []

    def spill(self, page, hslot):
        self.events.append(("spill", page, hslot))

    def restore(self, hslot, page):
        self.events.append(("restore", hslot, page))

    def drop(self, hslot):
        self.events.append(("drop", hslot))

    def poll(self):
        pass


# --------------------------------------------------------- allocator unit --
class TestAllocatorSpill:
    def test_spill_frees_device_and_occupies_host(self):
        a = BlockAllocator(n_pages=4, page_size=PAGE, host_pages=2)
        t = a.alloc(0, PAGE)
        a.pin(t[0])
        a.release(0)                        # pin is now the last ref
        h = a.spill(t[0])
        assert h is not None
        assert a.free_pages() == 4 and a.live_pages() == 0
        assert a.spilled_slots() == 1 and a.free_host_slots() == 1
        # combined accounting: free + unique-live + spilled == accounted
        assert (a.free_pages() + a.live_pages() == a.n_pages
                and a.free_host_slots() + a.spilled_slots() == a.host_pages)

    def test_spill_refused_while_referenced(self):
        """A page in any live block table must never spill — the sharer
        would read freed HBM."""
        a = BlockAllocator(n_pages=4, page_size=PAGE, host_pages=2)
        t = a.alloc(0, PAGE)
        a.pin(t[0])                          # cache pin + table ref
        assert a.spill(t[0]) is None
        assert a.spilled_slots() == 0 and a.refs(t[0]) == 2

    def test_spill_refused_when_host_full(self):
        a = BlockAllocator(n_pages=4, page_size=PAGE, host_pages=1)
        t0 = a.alloc(0, PAGE)
        t1 = a.alloc(1, PAGE)
        a.pin(t0[0])
        a.pin(t1[0])
        a.release(0)
        a.release(1)
        assert a.spill(t0[0]) is not None
        assert a.spill(t1[0]) is None        # host pool exhausted
        assert a.refs(t1[0]) == 1            # untouched

    def test_restore_roundtrip_and_idempotence(self):
        a = BlockAllocator(n_pages=2, page_size=PAGE, host_pages=1)
        t = a.alloc(0, PAGE)
        a.pin(t[0])
        a.release(0)
        h = a.spill(t[0])
        p1 = a.restore_begin(h)
        assert p1 is not None and a.refs(p1) == 1
        assert a.restore_begin(h) == p1      # idempotent begin
        assert a.spilled_slots() == 1        # slot held until commit
        assert a.restore_commit(h) is True
        assert a.restore_commit(h) is False  # idempotent commit
        assert a.free_host_slots() == 1
        assert a.unpin(p1) is True

    def test_drop_spilled_refused_mid_restore(self):
        a = BlockAllocator(n_pages=2, page_size=PAGE, host_pages=1)
        t = a.alloc(0, PAGE)
        a.pin(t[0])
        a.release(0)
        h = a.spill(t[0])
        a.restore_begin(h)
        assert a.drop_spilled(h) is False    # copy in flight
        a.restore_commit(h)


# --------------------------------------------------------- retention unit --
class TestSpillRungs:
    def test_pressure_spills_before_dropping(self):
        """Admission pressure on retained pages spills them (content
        survives on host) instead of destroying them."""
        a = BlockAllocator(n_pages=4, page_size=PAGE, host_pages=8)
        rt = _rt(a)
        r0 = _req(0, sid=1)
        p0 = _toks(0, 3 * PAGE + 2)
        a.alloc(0, 4 * PAGE)
        _release(rt, a, r0, p0, now=0.0)     # 3 full + tail retained
        cold = _req(1, plen=2 * PAGE - 1)
        cold.tokens = _toks(1, cold.prompt_len)
        assert admit_blocks(a, [cold], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 1
        assert rt.stats.pages_spilled >= 2
        assert rt.stats.spill_drops == 0
        assert rt.prefix.stats.evictions == 0        # nothing destroyed
        assert a.spilled_slots() == rt.stats.pages_spilled

    def test_decode_pressure_spills_before_preempting(self):
        """extend_for_decode: the spill rung frees pages so neither the
        retained session nor any live request is destroyed."""
        a = BlockAllocator(n_pages=4, page_size=PAGE, host_pages=8)
        rt = _rt(a)
        r0 = _req(0, sid=1)
        p0 = _toks(2, PAGE + 2)
        a.alloc(0, len(p0) + 1)
        _release(rt, a, r0, p0, now=0.0)     # 2 pages retained
        old = _req(1, plen=PAGE - 1, arrival=0.0)
        yng = _req(2, plen=PAGE - 1, arrival=1.0)
        a.alloc(1, PAGE)
        a.alloc(2, PAGE)
        assert a.free_pages() == 0
        old.generated = PAGE
        yng.generated = PAGE
        victims = extend_for_decode(
            a, [old, yng], lambda r: r.prompt_len + 1 + r.generated,
            cache=rt)
        assert victims == []
        assert rt.stats.pages_spilled == 2
        assert 1 in rt.sessions              # session still resumable
        assert rt.sessions[1].tail_hslot is not None

    def test_ttl_expiry_demotes_instead_of_dropping(self):
        a = BlockAllocator(n_pages=8, page_size=PAGE, host_pages=8)
        rt = KvRetention(PAGE, session_ttl=5.0, host_pool_pages=8,
                         spill_seconds_per_page=0.5)
        r0 = _req(0, sid=1)
        p0 = _toks(3, PAGE + 3)
        a.alloc(0, len(p0) + 1)
        _release(rt, a, r0, p0, now=0.0)
        freed = rt.tick(a, 6.0)              # past the TTL
        assert freed == 1                    # the tail's HBM came back
        e = rt.sessions[1]
        assert e.tail_hslot is not None and e.tail_page is None
        assert e.expires_at == math.inf      # host LRU owns it now
        assert rt.stats.sessions_expired == 0
        assert rt.stats.pages_spilled == 1

    def test_host_exhaustion_falls_back_to_drop(self):
        """With a 0-page host pool the ladder degenerates to PR 4
        destructive eviction."""
        a = BlockAllocator(n_pages=4, page_size=PAGE, host_pages=0)
        rt = KvRetention(PAGE, session_ttl=1000.0)
        r0 = _req(0, sid=1)
        p0 = _toks(4, 3 * PAGE + 2)
        a.alloc(0, 4 * PAGE)
        _release(rt, a, r0, p0, now=0.0)
        cold = _req(1, plen=2 * PAGE - 1)
        cold.tokens = _toks(5, cold.prompt_len)
        assert admit_blocks(a, [cold], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 1
        assert rt.stats.pages_spilled == 0
        assert rt.prefix.stats.evictions >= 1

    def test_host_lru_drops_colder_for_warmer(self):
        """A full host pool makes room for a WARMER incoming spill by
        dropping its LRU entry — and refuses a colder incoming one."""
        a = BlockAllocator(n_pages=8, page_size=PAGE, host_pages=1)
        rt = _rt(a, host=1)
        # two single-page radix entries, distinct paths, no sessions
        for seed in (10, 11):
            r = _req(seed, sid=None)
            path = _toks(seed, PAGE)
            a.alloc(seed, PAGE + 1)
            _release(rt, a, r, path, now=0.0)
        # warm up the second path (later stamp)
        rt.lookup(np.concatenate([_toks(11, PAGE), _toks(99, 2)]), req=None,
                  alloc=a)
        spilled = rt.evict(a, 2)
        assert spilled == 2
        # one page spilled at rest, one destroyed along the way
        assert a.spilled_slots() == 1
        assert rt.stats.pages_spilled >= 1
        assert rt.stats.spill_drops + rt.prefix.stats.evictions >= 1


class TestRestoreHold:
    def _spilled_session(self, a, rt, seed=20, sid=7, now=0.0):
        r0 = _req(0, sid=sid)
        path = _toks(seed, 2 * PAGE + 5)
        a.alloc(0, len(path) + 1)
        _release(rt, a, r0, path, now=now)
        # pressure: spill everything retained
        need = a.n_pages - a.free_pages()
        rt.evict(a, need)
        assert a.free_pages() == a.n_pages
        assert rt.stats.pages_spilled == 3   # 2 full + tail
        return path

    def test_lookup_initiates_restore_and_holds(self):
        a = BlockAllocator(n_pages=4, page_size=PAGE, host_pages=8)
        rt = _rt(a, sec=0.5)
        cop = _RecordingCopier()
        rt.copier = cop
        path = self._spilled_session(a, rt)
        rt.tick(a, 1.0)
        r1 = _req(1, plen=len(path) + 6, sid=7, turn=1)
        r1.tokens = np.concatenate([path, _toks(21, 6)])
        n = admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                         cache=rt, tokens_of=lambda r: r.tokens)
        assert n == 0                            # HELD, not admitted
        assert r1.spill_wait == pytest.approx(1.0 + 3 * 0.5)
        assert rt.stats.restore_holds == 1
        assert r1.session_hit_tokens == 0        # no claim while held
        assert [e[0] for e in cop.events].count("restore") == 3
        # restores reserved device pages (pinned by the cache)
        assert a.free_pages() == 1
        # completion at the modeled time: pages flip LIVE
        rt.tick(a, r1.spill_wait)
        assert rt.stats.pages_restored == 3
        assert rt.stats.restored_tokens == len(path)
        assert rt.restores_in_flight() == 0
        e = rt.sessions[7]
        assert e.tail_hslot is None and e.tail_page is not None
        # the re-queued admission now takes the full session hit
        r1.spill_wait = -1.0
        n = admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                         cache=rt, tokens_of=lambda r: r.tokens)
        assert n == 1
        assert r1.session_hit_tokens == len(path)
        assert r1.prefix_hit_tokens == len(path)

    def test_second_holder_joins_inflight_restore(self):
        """A second request hitting a restoring path waits for the SAME
        transfer — no duplicate copies, no double restore."""
        a = BlockAllocator(n_pages=8, page_size=PAGE, host_pages=8)
        rt = _rt(a, sec=0.5)
        r0 = _req(0, sid=None)
        path = _toks(30, 2 * PAGE)
        a.alloc(0, len(path) + 1)
        _release(rt, a, r0, path, now=0.0)
        rt.evict(a, 2)                           # both pages spilled
        suffix = np.concatenate([path, _toks(31, 4)])
        r1, r2 = _req(1, plen=len(suffix)), _req(2, plen=len(suffix))
        r1.tokens = r2.tokens = suffix
        assert admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 0
        assert admit_blocks(a, [r2], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 0
        assert r2.spill_wait == r1.spill_wait
        assert rt.stats.pages_restored == 0
        rt.tick(a, r1.spill_wait)
        assert rt.stats.pages_restored == 2      # one transfer, not two

    def test_register_revives_spilled_nodes(self):
        """A re-prefill over a spilled path re-materializes the same
        KV (pure function of the token path) — register adopts the
        fresh pages and the host copies are discarded for free."""
        a = BlockAllocator(n_pages=8, page_size=PAGE, host_pages=8)
        rt = _rt(a)
        r0 = _req(0, sid=None)
        path = _toks(40, 2 * PAGE)
        a.alloc(0, len(path) + 1)
        _release(rt, a, r0, path, now=0.0)
        rt.evict(a, 2)
        assert rt.prefix.spilled_nodes() == 2
        # a cold duplicate re-prefilled the same path
        t = a.alloc(1, 2 * PAGE)
        rt.prefix.register(a, path, t)
        assert rt.prefix.spilled_nodes() == 0
        assert a.spilled_slots() == 0            # host slots returned
        assert rt.stats.spill_drops == 0         # revive, not destruction
        pages, hit = rt.prefix.lookup(np.concatenate([path, _toks(41, 2)]))
        assert hit == 2 * PAGE and pages == t[:2]


class TestMaintainParity:
    """Satellite: ONE shared maintain path — TTL expiry and restore
    completion fire at the same clock times through either backend's
    ``maintain`` because both tick the one ``PagedKv`` they hold."""

    @staticmethod
    def _kv(**kw):
        # 4 device pages + the trash page, 8 host slots, bf16 widths
        return PagedKv(get_smoke_config("qwen3-14b", max_seq_len=4 * PAGE),
                       PAGE, 4 * PAGE, pool_tokens=5 * PAGE, **kw)

    def _drive(self, times):
        kv = self._kv(session_ttl=5.0, host_pool_tokens=8 * PAGE)
        a, rt = kv.alloc, kv.retention
        assert (a.n_pages, a.host_pages) == (4, 8)
        r0 = _req(0, sid=1)
        path = _toks(50, PAGE + 3)
        a.alloc(0, len(path) + 1)
        _release(rt, a, r0, path, now=0.0)
        events = []
        for t in times:
            kv.tick(t)
            e = rt.sessions.get(1)
            events.append((t, rt.stats.pages_spilled,
                           rt.stats.pages_restored,
                           None if e is None else e.tail_hslot is not None))
        return events, rt, a

    def test_same_times_same_transitions(self):
        times = [1.0, 4.9, 5.0, 7.5]
        ev1, rt1, _ = self._drive(times)
        ev2, rt2, _ = self._drive(times)
        assert ev1 == ev2
        # demotion happened exactly at the 5.0 tick
        assert ev1[1][3] is False and ev1[2][3] is True

    def test_maintain_noops_without_paged_pool(self):
        from repro.core.simulator import A100X4, CostModel, CostModelBackend
        cfg = get_smoke_config("qwen3-14b", max_seq_len=4 * PAGE)
        be = CostModelBackend(CostModel(cfg, A100X4), kv_budget=1e6)
        assert be.kv is None
        be.maintain(1.0)                     # must not raise
        kv = self._kv()                      # paged, nothing retained
        assert kv.retention is None
        kv.tick(1.0)
        assert kv.alloc.free_pages() == kv.alloc.n_pages


# ------------------------------------------- block-table mirror satellite --
class TestBlockTableMirrorIncremental:
    """Satellite: decode_preempt's block-table upload is O(new pages)
    per grown request.  Timing-free regression: drive the incremental
    mirror and the old full-rescan reference through the same
    alloc/extend/preempt churn — identical host tensors, with the
    incremental path writing only appended cells."""

    def _reference_sync(self, host, pool, slot_of, alloc, trash):
        """The pre-PR-5 formulation (engine.py decode_preempt): rescan
        every pooled request's full table per dispatch."""
        compares = 0
        for r in pool:
            slot = slot_of.get(r.rid)
            if slot is None:
                continue
            t = np.asarray(alloc.table(r.rid), np.int32)
            compares += len(t)
            if not np.array_equal(host[slot, :len(t)], t):
                host[slot, :len(t)] = t
        return compares

    def test_incremental_matches_reference_through_churn(self):
        from repro.core.engine import _BlockTableMirror
        rng = np.random.default_rng(0)
        n_slots, pages_per_seq, trash = 8, 32, 999
        alloc = BlockAllocator(n_pages=256, page_size=PAGE)
        mirror = _BlockTableMirror(n_slots, pages_per_seq, trash)
        ref = np.full((n_slots, pages_per_seq), trash, np.int32)
        pool, slot_of, free = [], {}, list(range(n_slots))
        rid, tokens = 0, {}
        ref_compares = 0
        for step in range(400):
            op = rng.random()
            if op < 0.3 and free:                     # admit
                r = _req(rid, plen=int(rng.integers(1, 10 * PAGE)))
                if alloc.alloc(r.rid, r.prompt_len + 1) is not None:
                    slot = free.pop()
                    slot_of[r.rid] = slot
                    t = alloc.table(r.rid)
                    ref[slot] = trash
                    ref[slot, :len(t)] = t
                    mirror.insert(slot, r.rid, t)
                    tokens[r.rid] = r.prompt_len + 1
                    pool.append(r)
                    rid += 1
            elif op < 0.8 and pool:                   # decode growth
                for r in pool:
                    tokens[r.rid] = min(tokens[r.rid]
                                        + int(rng.integers(0, 2 * PAGE)),
                                        pages_per_seq * PAGE)
                    alloc.extend(r.rid, tokens[r.rid])
            elif pool:                                # release
                r = pool.pop(int(rng.integers(len(pool))))
                alloc.release(r.rid)
                slot = slot_of.pop(r.rid)
                free.append(slot)
                ref[slot] = trash
                mirror.clear(slot, r.rid)
                tokens.pop(r.rid)
            # the per-dispatch sync both paths run
            ref_compares += self._reference_sync(ref, pool, slot_of,
                                                 alloc, trash)
            for r in pool:
                mirror.sync(slot_of[r.rid], r.rid, alloc)
            assert np.array_equal(mirror.host, ref)
        # O(new pages): the incremental path touched far fewer cells
        # than the reference rescanned (timing-free bound)
        assert mirror.writes < ref_compares / 4, \
            (mirror.writes, ref_compares)


# --------------------------------------------------- engine end to end ----
import jax                                                    # noqa: E402

from repro.configs import get_smoke_config                    # noqa: E402
from repro.core import (BucketServeScheduler, MemoryBudget,   # noqa: E402
                        SchedulerConfig)
from repro.core.engine import ServingEngine                   # noqa: E402
from repro.core.simulator import (A100X4, CostModel,          # noqa: E402
                                  Simulator)
from repro.data.workload import WorkloadSpec, generate        # noqa: E402
from repro.models import transformer as tfm                   # noqa: E402

BUDGET = MemoryBudget(hbm_bytes_per_device=2 ** 30, n_devices=1,
                      weight_bytes=0)
PAGE_E = 128
TIGHT_POOL = 12 * PAGE_E      # forces PR 4 to unpin live sessions


def _session_workload(cfg, *, sessions=3, turns=4, utter=200, out=8,
                      seed=7):
    spec = WorkloadSpec(dataset="alpaca", rps=1e6, sessions=sessions,
                        turns=turns, utterance_tokens=utter,
                        max_new_tokens=out, seed=seed,
                        task_type=TaskType.OFFLINE,
                        max_model_len=cfg.max_seq_len,
                        vocab_size=cfg.vocab_size)
    return generate(spec)


def _engine(cfg, params, *, host_pool_tokens=None, slots=4,
            pool_tokens=TIGHT_POOL, session_ttl=1000.0, spill_dtype=""):
    sched = BucketServeScheduler(cfg, BUDGET, SchedulerConfig(
        max_batch=slots, memory_model="paged", page_size=PAGE_E))
    return ServingEngine(cfg, params, sched, max_slots=slots,
                         cache_len=cfg.max_seq_len, paged=True,
                         page_size=PAGE_E, kv_pool_tokens=pool_tokens,
                         session_ttl=session_ttl,
                         host_pool_tokens=host_pool_tokens,
                         spill_dtype=spill_dtype)


class TestSpillEngineAcceptance:
    """Acceptance (ISSUE 5): sessions x turns workload, page 128, pool
    tight enough that PR 4 unpins live sessions — with the spill tier
    every request's token ids are bit-identical to the no-spill run,
    and turns >= 2 prefill >= 40% fewer prompt tokens than the unpin
    baseline under the SAME HBM budget."""

    def _run(self, cfg, params, host_pool_tokens, **kw):
        reqs = _session_workload(cfg)
        eng = _engine(cfg, params, host_pool_tokens=host_pool_tokens, **kw)
        eng.submit(reqs)
        done = eng.run(max_wall_s=600)
        assert len(done) == len(reqs)
        return eng, reqs

    def test_bit_identical_and_40pct_fewer_prefill_than_unpin(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs, pre, res = {}, {}, {}
        for host in (None, 64 * PAGE_E):
            eng, reqs = self._run(cfg, params, host)
            outs[host] = {r.rid: eng.outputs[r.rid] for r in reqs}
            outs[host].update({(r.rid, "p"): r.tokens.tolist()
                               for r in reqs})
            pre[host] = {r.rid: (r.turn, r.prefilled_tokens) for r in reqs}
            res[host] = eng.result
            for r in reqs:
                assert len(eng.outputs[r.rid]) == r.max_new_tokens
            be = eng.backend
            assert be.kv.alloc.free_pages() + be.kv.alloc.live_pages() \
                == be.kv.alloc.n_pages
            assert (be.kv.alloc.free_host_slots() + be.kv.alloc.spilled_slots()
                    == be.kv.alloc.host_pages)
            be.kv.retention.clear(be.kv.alloc)
            assert be.kv.alloc.free_pages() == be.kv.alloc.n_pages
            assert be.kv.alloc.spilled_slots() == 0

        # the tight pool really did force PR 4's destructive eviction
        unpin = res[None]
        assert (unpin.sessions_evicted + unpin.sessions_expired
                + unpin.prefix_evictions) > 0
        assert unpin.spilled_pages == 0
        # the spill tier replaced destruction with copies ...
        spill = res[64 * PAGE_E]
        assert spill.spilled_pages > 0
        assert spill.restored_pages > 0
        assert spill.restored_tokens > 0
        # ... bit-identically ...
        assert outs[64 * PAGE_E] == outs[None]
        # ... and turns >= 2 re-prefill >= 40% fewer prompt tokens
        unpin_t2 = sum(p for t, p in pre[None].values() if t >= 1)
        spill_t2 = sum(p for t, p in pre[64 * PAGE_E].values() if t >= 1)
        assert spill_t2 <= 0.6 * unpin_t2, (spill_t2, unpin_t2)

    def test_restore_latency_lands_on_ttft(self):
        """Held turns pay the restore wait in their TTFT (arrival is
        not reset when the parked request re-enters the queue)."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        eng, reqs = self._run(cfg, params, 64 * PAGE_E)
        r = eng.result
        assert r.spill_hold_events > 0
        for q in reqs:
            assert q.first_token >= q.arrival
            assert q.ttft() < math.inf


def _record_dispatched(backend, log):
    """Record the composition of every batch that actually DISPATCHES
    (reaches prefill_chunk 0, i.e. survived the slot and KV-page
    admission clamps).  Formation ATTEMPTS are not comparable across
    backends — a batch that fails admission is re-formed every
    scheduler tick until pages free, and tick cadence is a clock
    property (wall vs virtual), not a policy one."""
    orig = backend.prefill_chunk

    def rec(job, idx, _orig=orig, _log=log):
        if idx == 0:
            _log.append(tuple(r.rid for r in job.batch.requests))
        return _orig(job, idx)

    backend.prefill_chunk = rec


class TestSpillBackendParity:
    """Engine vs cost model under the spill tier: identical dispatched
    batches, spill/restore counts and session hit counts — the spill
    DECISIONS live in the shared retention layer, the backends only
    move/price bytes."""

    SLOTS = 4
    POOL = 10 * PAGE_E

    def _sched(self, cfg):
        return BucketServeScheduler(cfg, BUDGET, SchedulerConfig(
            max_batch=self.SLOTS, memory_model="paged",
            page_size=PAGE_E))

    def _workload(self, cfg):
        reqs = _session_workload(cfg, sessions=2, turns=4, utter=220,
                                 out=4)
        for r in reqs:
            r.arrival = 0.0
        return reqs

    @pytest.mark.parametrize("spill_dtype", ["bf16", "int4"])
    def test_same_batches_and_spill_counts(self, spill_dtype):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        host = 64 * PAGE_E
        n = 8

        sim = Simulator(self._sched(cfg), CostModel(cfg, A100X4),
                        mode="disagg",
                        decode_slot_cap=self.SLOTS, paged=True,
                        page_size=PAGE_E, kv_pool_tokens=self.POOL,
                        cache_len=cfg.max_seq_len, session_ttl=1000.0,
                        host_pool_tokens=host, spill_dtype=spill_dtype)
        disp_sim = []
        _record_dispatched(sim.backend, disp_sim)
        res_sim = sim.run(self._workload(cfg))
        assert len(res_sim.finished()) == n

        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, self._sched(cfg),
                            max_slots=self.SLOTS,
                            cache_len=cfg.max_seq_len, paged=True,
                            page_size=PAGE_E, kv_pool_tokens=self.POOL,
                            session_ttl=1000.0, host_pool_tokens=host,
                            spill_dtype=spill_dtype)
        disp_eng = []
        _record_dispatched(eng.backend, disp_eng)
        eng.submit(self._workload(cfg))
        assert len(eng.run(max_wall_s=300)) == n
        res_eng = eng.result

        assert disp_sim == disp_eng
        assert res_sim.spilled_pages == res_eng.spilled_pages > 0
        assert res_sim.restored_pages == res_eng.restored_pages > 0
        assert res_sim.restored_tokens == res_eng.restored_tokens > 0
        assert res_sim.spill_drops == res_eng.spill_drops
        assert res_sim.spill_hold_events == res_eng.spill_hold_events > 0
        assert res_sim.session_lookups == res_eng.session_lookups > 0
        assert res_sim.session_hits == res_eng.session_hits > 0
        assert res_sim.session_hit_tokens == res_eng.session_hit_tokens
        assert res_sim.prefill_tokens_skipped \
            == res_eng.prefill_tokens_skipped > 0
        # quantized-tier parity: both backends price the SAME
        # compressed bytes and the SAME modeled restore time
        assert res_sim.spilled_bytes == res_eng.spilled_bytes > 0
        assert res_sim.restored_bytes == res_eng.restored_bytes > 0
        assert res_sim.restore_time_total \
            == pytest.approx(res_eng.restore_time_total)
        if spill_dtype == "int4":
            # compressed slots: strictly fewer bytes than the bf16
            # hot-tier footprint of the same pages
            hot = res_eng.spilled_pages * PAGE_E \
                * cfg.cache_bytes_per_token()
            assert res_eng.spilled_bytes < hot / 2


class TestQuantizedSpillEngine:
    """Tentpole bit-accuracy story, engine end to end.

    * int8 POOL + int8 SPILL: spilled pages hold the pool's own int8
      codes (pass-through, no requantization), so restore is LOSSLESS
      and outputs are bit-identical to the same pool without a spill
      tier.
    * int4 SPILL of a bf16 pool: lossy, but scheduling must be
      UNCHANGED vs the bf16-spill run under the same budget — the
      compressed tier only moves fewer bytes, it does not change which
      batches dispatch."""

    HOST = 64 * PAGE_E

    def _run(self, cfg, params, host, disp=None, **kw):
        reqs = _session_workload(cfg)
        eng = _engine(cfg, params, host_pool_tokens=host, **kw)
        if disp is not None:
            _record_dispatched(eng.backend, disp)
        eng.submit(reqs)
        assert len(eng.run(max_wall_s=600)) == len(reqs)
        outs = {r.rid: eng.outputs[r.rid] for r in reqs}
        outs.update({(r.rid, "p"): r.tokens.tolist() for r in reqs})
        return eng, outs

    def test_int8_pool_spill_restore_lossless(self):
        # int8 halves the page cost, so a ~equally tight pool needs a
        # ~halved byte budget (12 int8 pages here vs TIGHT_POOL's 11)
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024,
                               kv_cache_dtype="int8")
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        pool = 7 * PAGE_E
        _, base = self._run(cfg, params, None, pool_tokens=pool)
        eng, spill = self._run(cfg, params, self.HOST, pool_tokens=pool,
                               spill_dtype="int8")
        assert eng.result.spilled_pages > 0
        assert eng.result.restored_pages > 0
        assert spill == base                 # token ids bit-identical

    def test_int4_spill_leaves_dispatch_unchanged(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        disp, res = {}, {}
        for dt in ("bf16", "int4"):
            disp[dt] = []
            eng, _ = self._run(cfg, params, self.HOST, disp=disp[dt],
                               spill_dtype=dt)
            res[dt] = eng.result
        assert res["bf16"].spilled_pages > 0
        assert disp["int4"] == disp["bf16"]  # same batches dispatched
        assert res["int4"].spilled_pages == res["bf16"].spilled_pages
        assert res["int4"].restored_pages == res["bf16"].restored_pages
        assert res["int4"].spilled_bytes < res["bf16"].spilled_bytes / 2
        assert res["int4"].restore_time_total \
            < res["bf16"].restore_time_total


class TestInt4LogitDrift:
    """Documented int4 accuracy bound (DESIGN.md §3 "Tier precision"):
    round-tripping a prefilled KV cache through the spill tier's int4
    quantizer perturbs next-token logits by < 1.5 on the smoke config
    (observed ~0.7 with random weights, logit scale ~3)."""

    def test_roundtrip_logit_delta_bounded(self):
        import numpy as _np

        from repro.models.attention import (dequantize_kv_int4,
                                            quantize_kv_int4)

        cfg = get_smoke_config("qwen3-14b")
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0,
                                 cfg.vocab_size)
        l1, c1 = tfm.prefill(cfg, params, tokens=tok, cache_len=40)

        def roundtrip(lay):
            out = dict(lay)
            for k in ("k", "v"):
                x = _np.asarray(lay[k], _np.float32)
                packed, sc = quantize_kv_int4(x)
                import jax.numpy as jnp
                out[k] = jnp.asarray(
                    dequantize_kv_int4(packed, sc, x.shape[-1])
                ).astype(lay[k].dtype)
            return out

        c2 = dict(c1)
        c2["groups"] = [[roundtrip(lay) for lay in g]
                        for g in c1["groups"]]
        nt = l1.argmax(-1)
        l1b, _ = tfm.decode_step(cfg, params, nt, c1)
        l2b, _ = tfm.decode_step(cfg, params, nt, c2)
        import jax.numpy as jnp
        delta = float(jnp.max(jnp.abs(l1b - l2b)))
        assert delta < 1.5, delta
        assert bool(jnp.isfinite(l2b).all())


class TestRestoreAwareAdmission:
    """Satellite: Eq.-(6) admission prices in-flight restore traffic —
    reserved device pages plus the COMPRESSED byte backlog on the PCIe
    channel, converted through Eq. (6)'s own kv-bytes denominator."""

    def _sched(self, model="paged"):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        return BucketServeScheduler(cfg, BUDGET, SchedulerConfig(
            max_batch=4, memory_model=model, page_size=PAGE_E))

    def test_pressure_terms(self):
        b = self._sched().batcher
        # device term: pages reserved by restore_begin
        assert b.admission_pressure_tokens(2, 0) == 2 * PAGE_E
        # channel term: compressed bytes through the Eq.-(6) denominator
        assert b.admission_pressure_tokens(0, 5 * b.kv_per_tok) == 5
        assert b.admission_pressure_tokens(2, 5 * b.kv_per_tok) \
            == 2 * PAGE_E + 5

    def test_sum_model_prices_backlog_only(self):
        b = self._sched("sum").batcher
        # no paged pool: reservations aren't device pages, only the
        # channel backlog is real occupancy-to-be
        assert b.admission_pressure_tokens(2, 0) == 0
        assert b.admission_pressure_tokens(2, 3 * b.kv_per_tok) == 3

    def test_monitor_levels_throttle_n_max(self):
        s = self._sched()
        base = s._n_max()
        assert base > 1
        # a huge compressed backlog throttles admission ...
        s.monitor.on_restore_state(4, 10 ** 9 * s.batcher.kv_per_tok)
        assert s._pressure_tokens() > 10 ** 9
        assert s._n_max() < base
        # ... and the monitor holds LEVELS, not counters: the next
        # maintain tick with a drained channel clears the pressure
        s.monitor.on_restore_state(0, 0)
        assert s._pressure_tokens() == 0
        assert s._n_max() == base
