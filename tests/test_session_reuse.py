"""Unified KV retention + multi-turn session resume (DESIGN.md §3
"Session retention").

The tentpole claims under test:

* release is a RETENTION policy, not a free: a finished request's full
  transcript (prompt + generated[:-1] — the last token's KV is never
  written) extends the radix path, and the partial tail page stays
  pinned privately under the session key with a TTL;
* the next turn of a session re-sends the transcript as its prompt
  prefix and resumes past ALL of it — full pages by radix reference,
  the unaligned tail by pin hand-over — with token ids BIT-IDENTICAL
  to a cold re-prefill (acceptance: multi-turn workload, page 128,
  same HBM budget, >= 60% fewer prefilled prompt tokens on turns >= 2);
* eviction walks ONE ordered policy: expired sessions -> LRU cold
  prefixes -> live sessions -> (only then) preemption, so a pinned
  session is always unpinned before any live request loses work;
* engine and cost-model backends form identical batches AND identical
  session hit counts (backend parity extends to the session table);
* satellites: the scheduler's earliest-online bucket pick no longer
  rescans every queued request per tick (timing-free regression vs the
  quadratic reference); `_live_tokens` window capping lives in
  SchedulerBase (baselines included); workload generation is
  seed-stable across calls for every family.
"""
import numpy as np
import pytest

from repro.core.bucket import BucketManager
from repro.core.paging import BlockAllocator, admit_blocks, extend_for_decode
from repro.core.request import Request, TaskType
from repro.core.retention import KvRetention
from repro.data.workload import WorkloadSpec, generate

PAGE = 8


def _req(rid, plen=10, mnt=4, arrival=0.0, sid=None, turn=0):
    return Request(rid=rid, prompt_len=plen, max_new_tokens=mnt,
                   arrival=arrival, session_id=sid, turn=turn)


def _toks(seed, n):
    return np.random.default_rng(seed).integers(0, 1000, n).astype(np.int32)


def _release(rt, a, req, path, now=0.0):
    """Finish ``req`` whose pool KV covers ``path`` tokens."""
    req.generated = max(req.generated, 1)
    rt.on_release(a, req, path, now)


# ------------------------------------------------------- retention unit ---
class TestRetentionRelease:
    def test_release_registers_full_transcript_and_pins_tail(self):
        """Release with sessions on: full pages (prompt AND generated)
        join the radix, the partial tail stays pinned, everything else
        frees."""
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=10.0)
        r = _req(0, plen=2 * PAGE - 2, sid=7)
        path = _toks(0, 3 * PAGE + 3)       # prompt + generated KV path
        a.alloc(0, len(path) + 1)           # table spans the transcript
        t = a.table(0)
        _release(rt, a, r, path, now=1.0)
        assert not a.holds(0)
        assert len(rt.prefix) == 3          # 3 full transcript pages
        assert rt.prefix.pinned_pages() == t[:3]
        e = rt.sessions[7]
        assert e.tail_page == t[3] and a.refs(t[3]) == 1   # session pin
        assert e.expires_at == pytest.approx(11.0)
        assert rt.stats.sessions_retained == 1
        # free + unique-live == total with exactly the 4 retained pages
        assert a.live_pages() == 4
        assert a.free_pages() + a.live_pages() == a.n_pages

    def test_sessions_disabled_keeps_free_on_release(self):
        """session_ttl=None: the retention layer degenerates to the PR 3
        behaviour — release frees, nothing new enters the radix."""
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=None)
        r = _req(0, plen=PAGE, sid=7)
        a.alloc(0, 3 * PAGE)
        _release(rt, a, r, _toks(0, 3 * PAGE), now=1.0)
        assert len(rt.prefix) == 0 and not rt.sessions
        assert a.free_pages() == a.n_pages

    def test_next_turn_resumes_full_transcript_with_tail(self):
        """The resumed turn's hit covers the UNALIGNED transcript: radix
        pages by reference, the pinned tail transferred into its table
        at the right index."""
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=10.0)
        r0 = _req(0, sid=3, turn=0)
        path = _toks(1, 2 * PAGE + 5)
        a.alloc(0, len(path) + 1)
        t0 = a.table(0)
        _release(rt, a, r0, path)

        r1 = _req(1, plen=len(path) + 6, sid=3, turn=1)
        r1.tokens = np.concatenate([path, _toks(2, 6)])
        n = admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                         cache=rt, tokens_of=lambda r: r.tokens)
        assert n == 1
        assert r1.prefix_hit_tokens == len(path)        # NOT page-aligned
        assert r1.session_hit_tokens == len(path)
        assert a.table(1)[:3] == t0[:3]                 # radix + tail pages
        assert a.refs(t0[2]) == 1                       # tail now private
        assert 3 not in rt.sessions                     # entry consumed
        assert rt.stats.session_hits == 1
        assert rt.stats.tail_reuses == 1
        assert rt.stats.session_hit_tokens == len(path)

    def test_diverging_prompt_gets_radix_only(self):
        """A next 'turn' whose ids diverge inside the tail must NOT get
        the tail page (its KV is only valid for the exact path) — the
        radix full-page run still serves."""
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=10.0)
        r0 = _req(0, sid=3)
        path = _toks(3, 2 * PAGE + 5)
        a.alloc(0, len(path) + 1)
        _release(rt, a, r0, path)
        diverged = np.concatenate([path, _toks(4, 6)])
        diverged[2 * PAGE + 2] += 1                     # inside the tail
        r1 = _req(1, plen=len(diverged), sid=3, turn=1)
        r1.tokens = diverged
        assert admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 1
        assert r1.prefix_hit_tokens == 2 * PAGE         # page-aligned only
        assert r1.session_hit_tokens == 0
        assert 3 in rt.sessions                         # entry survives
        assert rt.stats.session_hits == 0

    def test_wrong_session_never_gets_anothers_tail(self):
        """Same token path, different session id: radix sharing yes,
        tail hand-over no."""
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=10.0)
        r0 = _req(0, sid=3)
        path = _toks(5, 2 * PAGE + 5)
        a.alloc(0, len(path) + 1)
        _release(rt, a, r0, path)
        r1 = _req(1, plen=len(path) + 4, sid=99, turn=1)
        r1.tokens = np.concatenate([path, _toks(6, 4)])
        assert admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 1
        assert r1.prefix_hit_tokens == 2 * PAGE
        assert r1.session_hit_tokens == 0
        assert 3 in rt.sessions


class TestRetentionTtlAndPressure:
    def test_ttl_tick_unpins_expired_sessions(self):
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=5.0)
        r0 = _req(0, sid=1)
        path = _toks(7, PAGE + 3)
        a.alloc(0, len(path) + 1)
        _release(rt, a, r0, path, now=0.0)
        assert rt.live_sessions() == 1
        assert rt.tick(a, 4.9) == 0                     # not yet
        assert rt.live_sessions() == 1
        freed = rt.tick(a, 5.0)                         # expired
        assert freed == 1 and rt.live_sessions() == 0
        assert rt.stats.sessions_expired == 1
        # the radix full page stays (it is independent LRU state)
        assert len(rt.prefix) == 1

    def test_expired_session_not_resumable(self):
        a = BlockAllocator(n_pages=8, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=5.0)
        r0 = _req(0, sid=1)
        path = _toks(8, PAGE + 3)
        a.alloc(0, len(path) + 1)
        _release(rt, a, r0, path, now=0.0)
        rt.tick(a, 100.0)
        r1 = _req(1, plen=len(path) + 2, sid=1, turn=1)
        r1.tokens = np.concatenate([path, _toks(9, 2)])
        assert admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 1
        assert r1.session_hit_tokens == 0
        assert r1.prefix_hit_tokens == PAGE             # radix survives TTL

    def test_eviction_order_expired_then_prefix_then_live_sessions(self):
        """The ONE ordered policy: expired session tails first, then
        LRU cold radix prefixes, then live session tails."""
        a = BlockAllocator(n_pages=6, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=5.0)
        # session 1 (will expire): 1 full page + tail
        r0 = _req(0, sid=1)
        p0 = _toks(10, PAGE + 2)
        a.alloc(0, len(p0) + 1)
        _release(rt, a, r0, p0, now=0.0)
        # session 2 (stays live): 1 full page + tail
        r1 = _req(1, sid=2)
        p1 = _toks(11, PAGE + 2)
        a.alloc(1, len(p1) + 1)
        _release(rt, a, r1, p1, now=4.0)
        rt.tick(a, 6.0)                     # sid 1 expired but NOT ticked
        assert rt.live_sessions() == 1      # ... tick already dropped it
        assert rt.stats.sessions_expired == 1
        # 2 radix pages + live tail pinned; evict 1: the LRU radix page
        # goes before the live session tail
        live_tail = rt.sessions[2].tail_page
        assert rt.evict(a, 1) == 1
        assert rt.live_sessions() == 1
        assert a.refs(live_tail) == 1
        assert rt.prefix.stats.evictions == 1
        # keep evicting: second radix page, THEN the live session tail
        assert rt.evict(a, 2) == 2
        assert rt.live_sessions() == 0
        assert rt.stats.sessions_evicted == 1
        assert a.free_pages() == a.n_pages

    def test_pressure_unpins_session_before_preempting_live_request(self):
        """Acceptance: under page pressure the retained session is
        sacrificed before ANY live request is preempted."""
        a = BlockAllocator(n_pages=4, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=1000.0)   # far from expiry
        r0 = _req(0, sid=1)
        p0 = _toks(12, PAGE + 2)
        a.alloc(0, len(p0) + 1)
        _release(rt, a, r0, p0, now=0.0)             # 2 pages retained
        # two live requests fill the rest
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
        assert victims == []                         # NOBODY preempted
        assert rt.live_sessions() == 0               # session paid instead
        assert rt.stats.sessions_evicted + rt.stats.sessions_expired >= 1
        assert len(rt.prefix) <= 1

    def test_admission_pressure_also_unpins_sessions(self):
        a = BlockAllocator(n_pages=4, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=1000.0)
        r0 = _req(0, sid=1)
        p0 = _toks(13, 3 * PAGE + 2)
        a.alloc(0, 4 * PAGE)
        _release(rt, a, r0, p0, now=0.0)             # all 4 pages retained
        cold = _req(1, plen=2 * PAGE - 1)
        cold.tokens = _toks(14, cold.prompt_len)
        assert admit_blocks(a, [cold], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 1
        assert a.holds(1)
        assert rt.live_sessions() == 0 or len(rt.prefix) < 3

    def test_failed_admission_aborts_claim(self):
        """If allocation fails after the session was claimed, the entry
        must stay resumable (claim rolled back, nothing unpinned)."""
        a = BlockAllocator(n_pages=4, page_size=PAGE)
        rt = KvRetention(PAGE, session_ttl=1000.0)
        r0 = _req(0, sid=1)
        p0 = _toks(15, PAGE + 2)
        a.alloc(0, 2 * PAGE)
        _release(rt, a, r0, p0, now=0.0)
        # a fat live request leaves too little room for the next turn
        a.alloc(5, 2 * PAGE)
        r1 = _req(1, plen=6 * PAGE, sid=1, turn=1)
        r1.tokens = np.concatenate([p0, _toks(16, 6 * PAGE - len(p0))])
        assert admit_blocks(a, [r1], lambda r: r.prompt_len + 1,
                            cache=rt, tokens_of=lambda r: r.tokens) == 0
        e = rt.sessions[1]
        assert e.claimed_by is None                  # rolled back
        assert r1.session_hit_tokens == 0
        assert a.refs(e.tail_page) >= 1              # still pinned


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
PAGE_E = 128


def _session_workload(cfg, *, sessions=2, turns=4, utter=250, out=6,
                      seed=7):
    spec = WorkloadSpec(dataset="alpaca", rps=1e6, sessions=sessions,
                        turns=turns, utterance_tokens=utter,
                        max_new_tokens=out, seed=seed,
                        task_type=TaskType.OFFLINE,
                        max_model_len=cfg.max_seq_len,
                        vocab_size=cfg.vocab_size)
    return generate(spec)


def _engine(cfg, params, *, session_ttl, prefix_cache=False, slots=4,
            pool_tokens=64 * PAGE_E, chunk_tokens=None):
    sched = BucketServeScheduler(cfg, BUDGET, SchedulerConfig(
        max_batch=slots, memory_model="paged", page_size=PAGE_E))
    return ServingEngine(cfg, params, sched, max_slots=slots,
                         cache_len=cfg.max_seq_len, paged=True,
                         page_size=PAGE_E, kv_pool_tokens=pool_tokens,
                         chunk_tokens=chunk_tokens,
                         prefix_cache=prefix_cache, session_ttl=session_ttl)


class TestSessionResumeEngine:
    """Acceptance (ISSUE 4): multi-turn workload, page 128, same HBM
    budget — every turn's token ids bit-identical to a cold run with
    >= 60% fewer prefilled prompt tokens across turns >= 2."""

    def _run(self, cfg, params, session_ttl, **kw):
        reqs = _session_workload(cfg, **{k: v for k, v in kw.items()
                                         if k in ("sessions", "turns",
                                                  "utter", "out", "seed")})
        eng = _engine(cfg, params, session_ttl=session_ttl,
                      **{k: v for k, v in kw.items()
                         if k in ("prefix_cache", "slots", "pool_tokens",
                                  "chunk_tokens")})
        eng.submit(reqs)
        done = eng.run(max_wall_s=600)
        assert len(done) == len(reqs)
        return eng, reqs

    def test_resumed_turns_bit_identical_and_60pct_fewer_prefill(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs, pre, res = {}, {}, {}
        for ttl in (None, 1000.0):
            eng, reqs = self._run(cfg, params, ttl)
            outs[ttl] = {r.rid: eng.outputs[r.rid] for r in reqs}
            # prompts are composed at runtime from actual outputs —
            # record them too, the cold/resumed transcripts must agree
            outs[ttl].update({(r.rid, "p"): r.tokens.tolist()
                              for r in reqs})
            pre[ttl] = {r.rid: (r.turn, r.prefilled_tokens) for r in reqs}
            res[ttl] = eng.result
            for r in reqs:
                assert len(eng.outputs[r.rid]) == r.max_new_tokens
            be = eng.backend
            # allocator invariant: free + unique-live == total; at run
            # end only the retention layer's pins remain live
            assert be.kv.alloc.free_pages() + be.kv.alloc.live_pages() \
                == be.kv.alloc.n_pages
            if ttl is not None:
                assert be.kv.alloc.live_pages() > 0
                assert be.kv.retention.clear(be.kv.alloc) > 0
                assert be.kv.alloc.free_pages() == be.kv.alloc.n_pages
            else:
                assert be.kv.alloc.live_pages() == 0

        assert outs[1000.0] == outs[None]     # bit-identical token ids
        cold_t2 = sum(p for t, p in pre[None].values() if t >= 2)
        warm_t2 = sum(p for t, p in pre[1000.0].values() if t >= 2)
        assert warm_t2 <= 0.4 * cold_t2, (warm_t2, cold_t2)
        r = res[1000.0]
        # 3 resumable turns per session, all resumed (incl. the tail)
        assert r.session_hits == 6 and r.session_lookups == 8
        assert r.tail_pages_reused == 6
        assert r.sessions_retained == 8
        assert r.session_hit_tokens > 0
        assert res[None].session_lookups == 0

    def test_composes_with_chunked_prefill(self):
        """Resumed spans at non-page-aligned offsets must stay
        positionally exact under chunking too."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs = {}
        for ttl in (None, 1000.0):
            eng, reqs = self._run(cfg, params, ttl, chunk_tokens=96,
                                  sessions=1, turns=3, utter=200, out=5)
            outs[ttl] = {r.rid: eng.outputs[r.rid] for r in reqs}
        assert outs[1000.0] == outs[None]

    def test_eviction_under_pressure_stays_correct(self):
        """A pool tight enough to force session/prefix eviction and
        preemption: outputs still match the ample-pool resumed run."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs = {}
        for pool in (64 * PAGE_E, 18 * PAGE_E):
            eng, reqs = self._run(cfg, params, 1000.0, pool_tokens=pool,
                                  sessions=2, turns=3, utter=220, out=8)
            outs[pool] = {r.rid: eng.outputs[r.rid] for r in reqs}
            for r in reqs:
                assert len(eng.outputs[r.rid]) == r.max_new_tokens
        assert outs[64 * PAGE_E] == outs[18 * PAGE_E]

    def test_first_token_only_turns_never_retained(self):
        """Regression: a max_new_tokens=1 row is never scattered into
        the pool — retaining it would index pages holding NO transcript
        KV into the radix, and the next turn would resume onto garbage.
        Such turns must stay cold (and bit-identical) in both runs."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        outs = {}
        for ttl in (None, 1000.0):
            eng, reqs = self._run(cfg, params, ttl, sessions=2, turns=2,
                                  utter=250, out=1)
            outs[ttl] = {r.rid: eng.outputs[r.rid] for r in reqs}
            if ttl is not None:
                assert eng.result.sessions_retained == 0
                assert eng.result.session_hits == 0
                assert eng.backend.kv.alloc.live_pages() == 0
        assert outs[1000.0] == outs[None]

    def test_ttl_zero_disables_resume_but_not_radix(self):
        """session_ttl=0: every entry expires before the next turn —
        no session hits, but transcript full pages still serve via the
        plain radix (page-aligned only)."""
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        eng, reqs = self._run(cfg, params, 0.0, sessions=1, turns=3)
        r = eng.result
        assert r.session_hits == 0 and r.tail_pages_reused == 0
        assert r.sessions_expired > 0
        assert r.prefix_hit_tokens > 0        # radix reuse survives TTL
        for q in reqs:
            assert q.prefix_hit_tokens % PAGE_E == 0


class _RecordingScheduler(BucketServeScheduler):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.formed = []

    def next_prefill_batch(self, now):
        batch = super().next_prefill_batch(now)
        if batch is not None:
            self.formed.append(tuple(r.rid for r in batch.requests))
        return batch


class TestSessionBackendParity:
    """CostModelBackend mirrors the engine's session retention:
    identical formed batches AND identical session hit counts on the
    same multi-turn workload (each backend composes transcripts from
    its OWN generated ids — the structure, lengths and therefore every
    admission decision must still agree)."""

    SLOTS = 4

    def _sched(self, cfg):
        return _RecordingScheduler(cfg, BUDGET, SchedulerConfig(
            max_batch=self.SLOTS, memory_model="paged",
            page_size=PAGE_E))

    def _workload(self, cfg):
        reqs = _session_workload(cfg, sessions=2, turns=3, utter=220,
                                 out=4)
        for r in reqs:      # session starts queued up-front: identical
            r.arrival = 0.0  # first ticks on wall and virtual clocks
        return reqs

    def test_same_batches_and_session_hit_counts(self):
        cfg = get_smoke_config("qwen3-14b", max_seq_len=1024)
        pool_tokens = 64 * PAGE_E
        n = 6                                 # 2 sessions x 3 turns

        sched_sim = self._sched(cfg)
        sim = Simulator(sched_sim, CostModel(cfg, A100X4), mode="disagg",
                        decode_slot_cap=self.SLOTS, paged=True,
                        page_size=PAGE_E, kv_pool_tokens=pool_tokens,
                        cache_len=cfg.max_seq_len, session_ttl=1000.0)
        res_sim = sim.run(self._workload(cfg))
        assert len(res_sim.finished()) == n

        sched_eng = self._sched(cfg)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, sched_eng, max_slots=self.SLOTS,
                            cache_len=cfg.max_seq_len, paged=True,
                            page_size=PAGE_E, kv_pool_tokens=pool_tokens,
                            session_ttl=1000.0)
        eng.submit(self._workload(cfg))
        assert len(eng.run(max_wall_s=300)) == n
        res_eng = eng.result

        assert sched_sim.formed == sched_eng.formed
        assert res_sim.session_lookups == res_eng.session_lookups > 0
        assert res_sim.session_hits == res_eng.session_hits > 0
        assert res_sim.session_hit_tokens == res_eng.session_hit_tokens
        assert res_sim.tail_pages_reused == res_eng.tail_pages_reused > 0
        assert res_sim.sessions_retained == res_eng.sessions_retained
        assert res_sim.prefix_hit_tokens == res_eng.prefix_hit_tokens
        assert res_sim.prefill_tokens_skipped \
            == res_eng.prefill_tokens_skipped > 0


# ------------------------------------------------ earliest-online pick ----
def _quadratic_pick(manager, offline_policy="sjf"):
    """The pre-PR-4 formulation: rescan every request in every bucket."""
    nonempty = manager.nonempty()
    if not nonempty:
        return None
    online = [b for b in nonempty
              if any(r.task_type == TaskType.ONLINE for r in b.requests)]
    if online:
        return min(online, key=lambda b: min(
            r.arrival for r in b.requests
            if r.task_type == TaskType.ONLINE))
    if offline_policy == "sjf":
        return min(nonempty, key=lambda b: b.low)
    return max(nonempty, key=lambda b: b.up)


def _incremental_pick(manager, offline_policy="sjf"):
    """What BucketServeScheduler._pick_bucket now does (cached mins)."""
    nonempty = manager.nonempty()
    if not nonempty:
        return None
    online = [b for b in nonempty if b.earliest_online() is not None]
    if online:
        return min(online, key=lambda b: b.earliest_online())
    if offline_policy == "sjf":
        return min(nonempty, key=lambda b: b.low)
    return max(nonempty, key=lambda b: b.up)


class TestEarliestOnlineIncremental:
    def test_pick_matches_quadratic_reference_through_churn(self):
        """Timing-free regression: over a random add/adjust/pop churn
        the cached earliest-online pick equals the full-rescan pick at
        EVERY tick (including after splits and merges)."""
        rng = np.random.default_rng(0)
        bm = BucketManager(l_max=4096)
        live = []
        rid = 0
        for step in range(300):
            for _ in range(int(rng.integers(1, 5))):      # arrivals
                r = Request(rid=rid, prompt_len=int(rng.integers(1, 4095)),
                            max_new_tokens=4,
                            arrival=float(rng.integers(0, 1000)),
                            task_type=TaskType.ONLINE if rng.random() < 0.5
                            else TaskType.OFFLINE)
                bm.add(r)
                live.append(r)
                rid += 1
            bm.adjust(n_max=int(rng.integers(1, 12)))     # split/merge
            got = _incremental_pick(bm)
            ref = _quadratic_pick(bm)
            assert (got is None) == (ref is None)
            if got is not None:
                assert (got.low, got.up) == (ref.low, ref.up)
                assert got.earliest_online() == (
                    min((r.arrival for r in got.requests
                         if r.task_type == TaskType.ONLINE), default=None))
            if live and rng.random() < 0.7:               # dispatch (pop)
                k = int(rng.integers(1, min(len(live), 8) + 1))
                idx = rng.choice(len(live), size=k, replace=False)
                batch = [live[i] for i in idx]
                bm.pop(batch)
                live = [r for i, r in enumerate(live) if i not in set(idx)]
        assert bm.total() == len(live)

    def test_requeue_with_new_arrival_reflected(self):
        """A popped request re-added with a mutated (penalised) arrival
        must update the cached min."""
        bm = BucketManager(l_max=1024)
        r = Request(rid=0, prompt_len=10, max_new_tokens=4, arrival=1.0,
                    task_type=TaskType.ONLINE)
        bm.add(r)
        assert bm.buckets[0].earliest_online() == 1.0
        bm.pop([r])
        assert bm.buckets[0].earliest_online() is None
        r.arrival = 9.0
        bm.add(r)
        assert bm.buckets[0].earliest_online() == 9.0


# --------------------------------------------------- _live_tokens dedupe --
class TestLiveTokensWindowCap:
    def test_baseline_scheduler_window_caps_in_flight_charge(self):
        """Satellite: the sliding-window cap moved into SchedulerBase —
        a windowed config through a BASELINE scheduler must charge
        min(window, prompt+output), not the uncapped sum."""
        from repro.configs import get_smoke_config
        from repro.core.baselines import StaticBatchScheduler
        from repro.core.batcher import MemoryBudget
        from repro.core.scheduler import BucketServeScheduler, \
            SchedulerConfig
        cfg = get_smoke_config("qwen3-14b", max_seq_len=4096,
                               sliding_window=64)
        budget = MemoryBudget(hbm_bytes_per_device=2 ** 30, n_devices=1,
                              weight_bytes=0)
        r = Request(rid=0, prompt_len=1000, max_new_tokens=200, arrival=0.0)
        base = StaticBatchScheduler(cfg, budget)
        base.admit_decode(r)
        assert base.monitor.in_flight_tokens == 64
        base.release_decode(r)
        assert base.monitor.in_flight_tokens == 0
        # and it matches BucketServe's charge exactly (one rule, hoisted)
        bs = BucketServeScheduler(cfg, budget, SchedulerConfig())
        assert bs._live_tokens(r) == base._live_tokens(r) == 64

    def test_unwindowed_charge_unchanged(self):
        from repro.configs import get_smoke_config
        from repro.core.baselines import StaticBatchScheduler
        from repro.core.batcher import MemoryBudget
        cfg = get_smoke_config("qwen3-14b", max_seq_len=4096)
        budget = MemoryBudget(hbm_bytes_per_device=2 ** 30, n_devices=1,
                              weight_bytes=0)
        r = Request(rid=0, prompt_len=1000, max_new_tokens=200, arrival=0.0)
        s = StaticBatchScheduler(cfg, budget)
        s.admit_decode(r)
        assert s.monitor.in_flight_tokens == 1200


# ------------------------------------------------ workload determinism ----
class TestWorkloadDeterminism:
    """Satellite: the SAME spec must regenerate identical requests and
    token ids across calls — parity tests regenerate workloads per
    backend and rely on it."""

    def _assert_identical(self, a, b):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert (x.rid, x.prompt_len, x.max_new_tokens, x.arrival,
                    x.session_id, x.turn, x.history_tokens,
                    x.think_gap) == \
                   (y.rid, y.prompt_len, y.max_new_tokens, y.arrival,
                    y.session_id, y.turn, y.history_tokens, y.think_gap)
            for f in ("tokens", "utterance"):
                xa, ya = getattr(x, f), getattr(y, f)
                assert (xa is None) == (ya is None)
                if xa is not None:
                    assert np.array_equal(xa, ya)

    def test_classic_family_seed_stable(self):
        spec = WorkloadSpec(dataset="mixed", n_requests=64, seed=11,
                            max_model_len=4096)
        self._assert_identical(generate(spec), generate(spec))

    def test_prefix_family_seed_stable(self):
        spec = WorkloadSpec(dataset="alpaca", n_requests=48, seed=12,
                            max_model_len=2048, prefix_groups=3,
                            prefix_tokens=128, vocab_size=1000)
        self._assert_identical(generate(spec), generate(spec))

    def test_session_family_seed_stable(self):
        spec = WorkloadSpec(dataset="alpaca", sessions=5, turns=4,
                            seed=13, max_model_len=4096, rps=2.0,
                            think_time_s=3.0, vocab_size=1000)
        self._assert_identical(generate(spec), generate(spec))

    def test_window_exhausted_session_truncates(self):
        """Regression: a transcript that exactly fills the window must
        END the session, not emit a turn with prompt_len >
        max_model_len (the engine would silently clamp its KV)."""
        spec = WorkloadSpec(dataset="alpaca", sessions=1, turns=3,
                            seed=0, max_model_len=64,
                            utterance_tokens=40, max_new_tokens=24,
                            vocab_size=1000)
        reqs = generate(spec)
        assert 1 <= len(reqs) < 3                # truncated, not oversized
        for r in reqs:
            assert r.prompt_len + r.max_new_tokens <= 64

    def test_session_family_shape(self):
        spec = WorkloadSpec(dataset="alpaca", sessions=3, turns=4,
                            seed=14, max_model_len=8192,
                            utterance_tokens=100, max_new_tokens=20,
                            vocab_size=1000)
        reqs = generate(spec)
        assert len(reqs) == 12
        by_sid = {}
        for r in reqs:
            by_sid.setdefault(r.session_id, []).append(r)
        for sid, turns in by_sid.items():
            turns.sort(key=lambda r: r.turn)
            transcript = 0
            for t, r in enumerate(turns):
                assert r.turn == t
                assert r.history_tokens == transcript
                assert r.prompt_len == transcript + len(r.utterance)
                if t == 0:
                    assert np.array_equal(r.tokens, r.utterance)
                    assert r.think_gap == 0.0
                else:
                    assert r.tokens is None      # composed by the loop
                assert r.prompt_len + r.max_new_tokens <= 8192
                transcript = r.prompt_len + r.max_new_tokens
