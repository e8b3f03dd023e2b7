"""Fault-injection plane + work-preserving recovery (DESIGN.md §9).

Four acceptance surfaces:
  * the injector is a pure function of (seed, site, draw counter) —
    bit-identical replay, no hidden global RNG state;
  * a seeded fault storm through the full serving stack loses and
    duplicates NOTHING: every request terminal, every ledger conserved
    (including the new ``fault_retry`` phase), allocator accounting
    exact;
  * checkpointed drain/resume: a loop drained mid-run and resumed on a
    COLD loop produces bit-identical final transcripts (sim synthetic
    ids AND real engine argmax ids);
  * allocator spill/restore chaos with fault-plane interleavings
    (cancel mid-restore, double restore, release-under-restore) holds
    free + unique-live == n_pages and free-host + spilled == host_pages.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.batcher import MemoryBudget
from repro.core.faults import SITES, FaultInjector, FaultPlan
from repro.core.paging import BlockAllocator
from repro.core.recovery import (CHECKPOINT_VERSION, DEFAULT_RECOVERY,
                                 LoopCheckpoint, RecoveryPolicy)
from repro.core.request import Request, TaskType
from repro.core.scheduler import BucketServeScheduler, SchedulerConfig
from repro.core.simulator import A100X4, CostModel, Simulator
from repro.core.telemetry import PHASES, WAIT_PHASES
from repro.data.workload import DEFAULT_CLASS_MIX, WorkloadSpec, generate

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # the 500-trial fallback below still runs
    HAVE_HYPOTHESIS = False

CFG = get_config("llama2-13b")
PAGE = 128

# every site armed at rates that actually fire on a 40-request burst
STORM = dict(decode_step=0.03, prefill_chunk=0.08, restore_stall=0.3,
             restore_error=0.3, host_corrupt=0.15, maintain_tick=0.05)


def _chaos_sim(plan=None, n=40, recovery=None, restore_timeout=30.0,
               **sim_kw):
    """test_telemetry's burst recipe (spills AND restores fire) with the
    fault plane armed on top."""
    budget = MemoryBudget(hbm_bytes_per_device=40 * 2 ** 30, n_devices=3,
                          weight_bytes=CFG.param_count() * 2)
    sched = BucketServeScheduler(CFG, budget, SchedulerConfig(
        max_batch=8, memory_model="paged", page_size=PAGE))
    sim = Simulator(sched, CostModel(CFG, A100X4), mode="disagg",
                    decode_slot_cap=64, paged=True, page_size=PAGE,
                    kv_pool_tokens=16 * 1024, prefix_cache=True,
                    session_ttl=600.0, host_pool_tokens=64 * 1024,
                    fault_plan=plan, recovery=recovery,
                    restore_timeout=restore_timeout, **sim_kw)
    spec = WorkloadSpec(rps=6.0, n_requests=n,
                        max_model_len=CFG.max_seq_len,
                        vocab_size=CFG.vocab_size,
                        class_mix=DEFAULT_CLASS_MIX, burst_factor=4.0,
                        diurnal_period_s=40.0, burst_every_s=15.0,
                        burst_duration_s=4.0, prefix_groups=4,
                        prefix_tokens=2 * PAGE, sessions=8, turns=3,
                        think_time_s=2.0, seed=7)
    return sim, generate(spec)


def _final_states(res):
    return sorted((r.rid, r.finished, r.first_token, r.generated,
                   r.dropped, r.quarantined) for r in res.requests)


def _assert_terminal_conserved(res, reqs):
    """Zero lost / zero duplicated / every ledger closed + conserved."""
    rids = [r.rid for r in res.requests]
    assert len(rids) == len(set(rids)) == len(reqs)
    assert sorted(rids) == sorted(r.rid for r in reqs)
    for r in res.requests:
        assert r.finished >= 0 or r.dropped, r.rid      # terminal
        if r.finished >= 0 and not r.dropped:
            assert r.generated == r.max_new_tokens, r.rid
        led = r.ledger
        assert led is not None and led.closed, r.rid
        assert led.conserved(), (r.rid, led.residual(), led.seq)


def _assert_alloc_exact(sim):
    a = sim.loop.backend.kv.alloc
    assert a.free_pages() + a.live_pages() == a.n_pages
    assert a.free_host_slots() + a.spilled_slots() == a.host_pages


def _transcript(backend, r):
    """Full token path: prompt (slice promotion included) + synthetic
    generated continuation past the promoted boundary."""
    toks = [] if r.tokens is None else \
        [int(t) for t in r.tokens[:r.prompt_len]]
    gen = backend.generated_tokens(r)[r.sliced_tokens:]
    return toks + [int(t) for t in gen]


# ------------------------------------------------------- injector unit ---
class TestInjectorUnit:
    def test_pure_function_of_seed_site_counter(self):
        # same plan, DIFFERENT interleaving of draws across sites: each
        # site's fired-counter list is identical — no cross-site or
        # hidden-global state
        plan = FaultPlan(seed=42, rates={s: 0.2 for s in SITES})
        a, b = FaultInjector(plan), FaultInjector(plan)
        for _ in range(300):
            for s in SITES:
                a.fire(s)
        for s in SITES:                       # site-major, not draw-major
            for _ in range(300):
                b.fire(s)
        for s in SITES:
            assert a.fired(s) == b.fired(s)
        assert a.log != [] and sorted(a.log) == sorted(b.log)

    def test_seed_changes_decisions(self):
        p1 = FaultPlan(seed=1, rates={"decode_step": 0.3})
        p2 = FaultPlan(seed=2, rates={"decode_step": 0.3})
        f1, f2 = FaultInjector(p1), FaultInjector(p2)
        for _ in range(200):
            f1.fire("decode_step")
            f2.fire("decode_step")
        assert f1.fired("decode_step") != f2.fired("decode_step")

    def test_unarmed_site_counts_draws_never_fires(self):
        fi = FaultInjector(FaultPlan(seed=3, rates={"decode_step": 1.0}))
        for _ in range(50):
            assert not fi.fire("prefill_chunk")
            assert fi.fire("decode_step")
        assert fi.draws("prefill_chunk") == 50
        assert fi.fired("prefill_chunk") == []
        assert fi.fired("decode_step") == list(range(50))
        assert fi.fired_count() == 50

    def test_rate_is_respected_statistically(self):
        fi = FaultInjector(FaultPlan(seed=9, rates={"decode_step": 0.1}))
        n = sum(fi.fire("decode_step") for _ in range(4000))
        assert 300 < n < 500, n

    def test_parse_spec_roundtrip(self):
        spec = "seed=7,decode_step=0.02,restore_stall=0.3,stall_s=5"
        plan = FaultPlan.parse(spec)
        assert plan.seed == 7 and plan.stall_s == 5.0
        assert plan.rate("decode_step") == 0.02
        assert plan.rate("restore_stall") == 0.3
        assert plan.rate("prefill_chunk") == 0.0
        assert FaultPlan.parse(plan.spec()) == plan

    def test_parse_rejects_unknown_site(self):
        with pytest.raises((AssertionError, ValueError, KeyError)):
            FaultPlan.parse("seed=1,flux_capacitor=0.5")

    def test_rate_bounds_validated(self):
        with pytest.raises(AssertionError):
            FaultPlan(seed=0, rates={"decode_step": 1.5})

    def test_fault_retry_is_a_ledger_wait_phase(self):
        assert "fault_retry" in PHASES
        assert "fault_retry" in WAIT_PHASES


# ------------------------------------------------------- seeded storm ----
class TestFaultStorm:
    def test_storm_loses_and_duplicates_nothing(self):
        plan = FaultPlan(seed=11, rates=STORM, stall_s=0.4)
        sim, reqs = _chaos_sim(plan)
        res = sim.run(reqs)
        _assert_terminal_conserved(res, reqs)
        _assert_alloc_exact(sim)
        # the storm actually stormed, and the loop actually recovered
        assert res.fault_events > 0
        assert res.fault_retries > 0
        phases = set()
        for r in res.requests:
            phases |= set(r.ledger.phases)
        assert "fault_retry" in phases
        # restore-channel fault surface exercised too
        assert (res.restore_stalls + res.restore_failures
                + res.restore_sheds + res.corruptions) > 0

    def test_storm_is_bit_identical_on_replay(self):
        plan = FaultPlan(seed=11, rates=STORM, stall_s=0.4)
        outs, logs = [], []
        for _ in range(2):
            sim, reqs = _chaos_sim(plan)
            res = sim.run(reqs)
            outs.append(_final_states(res))
            logs.append(list(sim.faults.log))
        assert outs[0] == outs[1]
        assert logs[0] == logs[1] and logs[0]

    def test_decode_kill_preserves_sliced_work(self):
        # decode faults hot enough to exhaust retries: pool kills fire,
        # yet every transcript stays exact (slice-boundary promotion)
        plan = FaultPlan(seed=4, rates={"decode_step": 0.25})
        sim, reqs = _chaos_sim(plan, slice_tokens=32)
        res = sim.run(reqs, time_limit=40000.0)
        _assert_terminal_conserved(res, reqs)
        assert res.fault_kills > 0
        ref_sim, ref_reqs = _chaos_sim(None, slice_tokens=32)
        ref = ref_sim.run(ref_reqs)
        want = {r.rid: _transcript(ref_sim.loop.backend, r)
                for r in ref.requests if not r.dropped}
        for r in res.requests:
            if not r.dropped:
                assert _transcript(sim.loop.backend, r) == want[r.rid], r.rid


# -------------------------------------------- restore-channel recovery ---
class TestRestoreRecovery:
    def test_hard_faults_and_corruption_degrade_to_recompute(self):
        plan = FaultPlan(seed=21, rates={"restore_error": 0.6,
                                         "host_corrupt": 0.5})
        sim, reqs = _chaos_sim(plan)
        res = sim.run(reqs)
        _assert_terminal_conserved(res, reqs)
        _assert_alloc_exact(sim)
        assert (res.restore_failures + res.corruptions) > 0

    def test_stalled_restore_times_out_to_cold_prefill(self):
        # satellite 1 regression: a parked request whose restore stalls
        # past the hold timeout unparks as a cold prefill — the loop
        # NEVER hangs on a dead channel
        plan = FaultPlan(seed=13, rates={"restore_stall": 1.0},
                         stall_s=1.0)
        sim, reqs = _chaos_sim(plan, restore_timeout=0.1)
        res = sim.run(reqs)
        _assert_terminal_conserved(res, reqs)
        _assert_alloc_exact(sim)
        assert res.restore_stalls > 0
        assert res.restore_timeouts > 0

    def test_unwinnable_restore_sheds_instead_of_burning_channel(self):
        # a stall far past every SLO budget: the slack rule sheds the
        # restore up front — nothing ever parks behind the dead channel
        plan = FaultPlan(seed=13, rates={"restore_stall": 1.0},
                         stall_s=1e6)
        sim, reqs = _chaos_sim(plan)
        res = sim.run(reqs)
        _assert_terminal_conserved(res, reqs)
        assert res.restore_sheds > 0
        assert res.makespan < 1e5          # the stall never entered time


# ---------------------------------------------------------- quarantine ---
class TestQuarantine:
    def test_poisoned_requests_never_kill_the_loop(self):
        # EVERY prefill chunk faults: no request can ever complete, yet
        # the loop terminates — retries exhaust, streaks cross the
        # quarantine bar, ledgers close, session cascades drop cleanly
        plan = FaultPlan(seed=5, rates={"prefill_chunk": 1.0})
        sim, reqs = _chaos_sim(plan, n=16)
        res = sim.run(reqs)
        _assert_terminal_conserved(res, reqs)
        assert res.quarantined > 0
        assert all(r.dropped for r in res.requests)
        assert any(r.quarantined for r in res.requests)
        # cascade drops (later session turns) are NOT quarantine drops
        assert res.quarantined <= sum(r.dropped for r in res.requests)

    def test_quarantine_threshold_honored(self):
        plan = FaultPlan(seed=5, rates={"prefill_chunk": 1.0})
        pol = RecoveryPolicy(max_retries=1, quarantine_after=2)
        sim, reqs = _chaos_sim(plan, n=8, recovery=pol)
        res = sim.run(reqs)
        _assert_terminal_conserved(res, reqs)
        assert res.quarantined > 0
        for r in res.requests:
            if r.quarantined:
                assert r.fault_streak >= pol.quarantine_after, r.rid


# ------------------------------------------------------ drain / resume ---
class TestDrainResume:
    def test_checkpoint_json_roundtrip(self):
        sim, reqs = _chaos_sim(None)
        sim.run(reqs, drain_at=4.0)
        ck = sim.loop.drain()
        assert ck.requests or ck.held_turns      # drained mid-run
        ck2 = LoopCheckpoint.from_json(ck.to_json())
        assert ck2.version == CHECKPOINT_VERSION
        assert ck2.now == ck.now
        assert ck2.requests == ck.requests
        assert ck2.held_turns == ck.held_turns
        assert ck2.sessions == ck.sessions
        bad = ck.to_json().replace(f'"version": {CHECKPOINT_VERSION}',
                                   '"version": 999')
        with pytest.raises(AssertionError):
            LoopCheckpoint.from_json(bad)

    def test_drain_resume_transcripts_bit_identical(self):
        # reference: one uninterrupted run
        ref_sim, ref_reqs = _chaos_sim(None, slice_tokens=32)
        ref = ref_sim.run(ref_reqs)
        assert not any(r.dropped for r in ref.requests)
        want = {r.rid: _transcript(ref_sim.loop.backend, r)
                for r in ref.requests}

        # drained run: stop mid-flight, checkpoint through JSON, resume
        # on a COLD loop
        sim1, reqs1 = _chaos_sim(None, slice_tokens=32)
        res1 = sim1.run(reqs1, drain_at=4.0)
        ck = LoopCheckpoint.from_json(sim1.loop.drain().to_json())
        assert ck.requests or ck.held_turns
        _assert_alloc_exact(sim1)                # drain left no leaks
        sim2, _ = _chaos_sim(None, slice_tokens=32)
        res2 = sim2.run(ck.restore_requests(), resume_clock=ck.now)

        done1 = {r.rid: r for r in res1.requests
                 if r.finished >= 0 and not r.dropped}
        done2 = {r.rid: r for r in res2.requests}
        assert not any(r.dropped for r in done2.values())
        assert set(done1) | set(done2) == set(want)
        assert not (set(done1) & set(done2))     # nothing ran twice
        for rid, r in done1.items():
            assert _transcript(sim1.loop.backend, r) == want[rid], rid
        for rid, r in done2.items():
            assert _transcript(sim2.loop.backend, r) == want[rid], rid
        # resumed deadlines kept their pre-drain anchor
        for r in res2.requests:
            if r.t0_anchor >= 0.0:
                assert r.ledger.t0 == pytest.approx(r.t0_anchor)

    def test_resume_clock_continues_at_drain_time(self):
        sim1, reqs1 = _chaos_sim(None)
        sim1.run(reqs1, drain_at=4.0)
        ck = sim1.loop.drain()
        sim2, _ = _chaos_sim(None)
        res2 = sim2.run(ck.restore_requests(), resume_clock=ck.now)
        assert ck.now >= 4.0
        for r in res2.requests:
            if r.finished >= 0:
                assert r.finished >= ck.now


# ------------------------------------------- allocator fault chaos (§3) --
def _chaos_step(a, rng, live, spilled, restoring, committed, rid_ctr):
    """One random op against the allocator, including the fault-plane
    interleavings: cancel mid-restore, restore_begin idempotence under
    a second begin, drop-at-rest, release while other slots restore."""
    op = rng.integers(0, 7)
    if op == 0:                                       # admit
        rid = rid_ctr[0]
        rid_ctr[0] += 1
        if a.alloc(rid, int(rng.integers(1, 5 * PAGE))) is not None:
            live.add(rid)
    elif op == 1 and live:                            # grow
        rid = int(rng.choice(sorted(live)))
        a.extend(rid, a.table_len(rid) * PAGE + int(rng.integers(1, PAGE)))
    elif op == 2 and live:                            # release
        rid = int(rng.choice(sorted(live)))
        live.discard(rid)
        a.release(rid)
    elif op == 3 and live:                            # retire tail to host
        rid = int(rng.choice(sorted(live)))
        page = a.table(rid)[-1]
        if a.refs(page) == 1:                         # sole owner
            a.pin(page)                               # pin outlives table
            live.discard(rid)
            a.release(rid)
            h = a.spill(page)
            if h is not None:
                spilled.add(h)
            else:                                     # host full: drop
                a.unpin(page)
    elif op == 4 and spilled:                         # restore_begin
        h = int(rng.choice(sorted(spilled)))
        page = a.restore_begin(h)
        if page is not None:
            assert a.restore_begin(h) == page         # idempotent
            spilled.discard(h)
            restoring[h] = page
    elif op == 5 and restoring:                       # commit OR fault
        h = int(rng.choice(sorted(restoring)))
        page = restoring.pop(h)
        if rng.random() < 0.5:                        # fault: unwind
            assert a.restore_cancel(h)
            assert not a.restore_cancel(h)            # second is a no-op
            spilled.add(h)
        else:
            assert a.restore_commit(h)
            assert not a.restore_commit(h)            # second is a no-op
            committed.add(page)                       # pinned, restored
    elif op == 6 and spilled:                         # bit-rot drop
        h = int(rng.choice(sorted(spilled)))
        if a.drop_spilled(h):
            spilled.discard(h)


def _chaos_invariants(a):
    assert a.free_pages() + a.live_pages() == a.n_pages
    assert a.free_host_slots() + a.spilled_slots() == a.host_pages


def _run_chaos_trial(seed, steps=60):
    rng = np.random.default_rng(seed)
    a = BlockAllocator(n_pages=int(rng.integers(2, 10)), page_size=PAGE,
                       host_pages=int(rng.integers(1, 8)))
    live, spilled, committed, rid_ctr = set(), set(), set(), [0]
    restoring = {}
    for _ in range(steps):
        _chaos_step(a, rng, live, spilled, restoring, committed, rid_ctr)
        _chaos_invariants(a)
    # teardown: every path back to empty still balances
    for h in sorted(restoring):
        assert a.restore_cancel(h)
        spilled.add(h)
    for rid in sorted(live):
        a.release(rid)
    for page in sorted(committed):
        assert a.unpin(page)                          # frees: sole owner
    for h in sorted(spilled):
        assert a.drop_spilled(h)
    assert a.live_pages() == 0
    _chaos_invariants(a)


class TestAllocatorFaultChaos:
    def test_500_random_fault_interleavings(self):
        for seed in range(500):
            _run_chaos_trial(seed)


if HAVE_HYPOTHESIS:
    class TestAllocatorFaultChaosProperty:
        @settings(deadline=None, max_examples=200)
        @given(seed=st.integers(0, 2 ** 31 - 1),
               steps=st.integers(1, 120))
        def test_any_interleaving_holds_invariants(self, seed, steps):
            _run_chaos_trial(seed, steps=steps)


# ------------------------------------------- real-engine fault surface ---
import math                                                   # noqa: E402

import jax                                                    # noqa: E402

from repro.configs import get_smoke_config                    # noqa: E402
from repro.core.engine import ServingEngine                   # noqa: E402
from repro.models import transformer as tfm                   # noqa: E402


def _smoke_engine(fault_plan=None, slots=4, **kw):
    cfg = get_smoke_config("qwen3-14b", max_seq_len=128)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    budget = MemoryBudget(hbm_bytes_per_device=2 ** 30, n_devices=1,
                          weight_bytes=0)
    sched = BucketServeScheduler(cfg, budget,
                                 SchedulerConfig(max_batch=slots))
    return ServingEngine(cfg, params, sched, max_slots=slots,
                         cache_len=128, fault_plan=fault_plan, **kw)


def _eng_reqs(n=8, seed=3, mnt=6):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt_len=int(rng.integers(8, 48)),
                    max_new_tokens=mnt, arrival=0.0,
                    task_type=TaskType.OFFLINE) for i in range(n)]


class TestEngineFaults:
    def test_fired_sequences_bit_identical_across_backends(self):
        # the SAME plan drives the real engine and the simulator; per
        # site, decisions at shared draw counters must agree exactly —
        # the injector seam is backend-agnostic (counter streams differ
        # in LENGTH across substrates, never in content)
        plan = FaultPlan(seed=5, rates={"prefill_chunk": 0.15,
                                        "decode_step": 0.05,
                                        "maintain_tick": 0.1})
        eng = _smoke_engine(fault_plan=plan)
        reqs = _eng_reqs()
        eng.submit(reqs)
        done = eng.run(max_wall_s=300)
        assert len(done) + sum(r.dropped for r in reqs) == len(reqs)
        for r in done:
            assert len(eng.outputs[r.rid]) == r.max_new_tokens
        assert eng.result.fault_events > 0

        sim, sreqs = _chaos_sim(plan)
        sim.run(sreqs, time_limit=40000.0)
        for site in SITES:
            k = min(eng.faults.draws(site), sim.faults.draws(site))
            ef = [c for c in eng.faults.fired(site) if c < k]
            sf = [c for c in sim.faults.fired(site) if c < k]
            assert ef == sf, site

    def test_engine_drain_resume_token_ids_identical(self):
        # reference: uninterrupted argmax transcripts
        ref = _smoke_engine(slice_tokens=2)
        reqs = _eng_reqs()
        ref.submit(reqs)
        ref_done = ref.run(max_wall_s=300)
        assert len(ref_done) == len(reqs)
        want = {r.rid: list(ref.outputs[r.rid]) for r in reqs}

        # drain a second engine mid-run (wall clock), resume the JSON
        # checkpoint on a COLD engine: the gate line of serve.py's
        # --drain-after smoke
        eng2 = _smoke_engine(slice_tokens=2)
        reqs2 = _eng_reqs()
        eng2.submit(reqs2)
        eng2.loop.run(reqs2, time_limit=math.inf, max_wall_s=300,
                      drain_at=1.0)
        ck = LoopCheckpoint.from_json(eng2.loop.drain().to_json())
        eng3 = _smoke_engine(slice_tokens=2)
        cold = ck.restore_requests()
        eng3.loop.run(cold, time_limit=math.inf, max_wall_s=300,
                      resume_clock=ck.now)

        done2 = {r.rid for r in reqs2 if r.finished >= 0 and not r.dropped}
        done3 = {r.rid for r in cold if r.finished >= 0 and not r.dropped}
        assert done2 | done3 == set(want)        # nothing lost
        assert not (done2 & done3)               # nothing duplicated
        for rid in done2:
            assert list(eng2.outputs[rid]) == want[rid], rid
        for rid in done3:
            assert list(eng3.outputs[rid]) == want[rid], rid
