"""Host spans and counters of the wall-clock serving path (DESIGN.md §7):
off while no profiler captures, on the profiler's timeline while one
does, and the compile events JAX reports as spans inside them."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core import BucketServeScheduler, MemoryBudget, SchedulerConfig
from repro.core.engine import ServingEngine
from repro.core.request import Request, TaskType
from repro.core.serving_loop import WallClock
from repro.core.simulator import A100X4, CostModel, Simulator
from repro.core.telemetry import (NULL_TRACER, ProfilerTracer, Tracer,
                                  validate_perfetto)
from repro.kernels import chunk_attn
from repro.models import transformer as tfm

CFG = get_smoke_config("yi-6b", max_seq_len=256)
TRACER_METHODS = ("track", "complete", "instant", "counter", "async_begin",
                  "async_end", "span", "on_compile")


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, tracer=None):
    """A paged, chunked smoke engine: every span of the serving path."""
    sched = BucketServeScheduler(
        CFG, MemoryBudget(hbm_bytes_per_device=2 ** 34, n_devices=1,
                          weight_bytes=0),
        SchedulerConfig(max_batch=4, memory_model="paged", page_size=16))
    return ServingEngine(CFG, params, sched, max_slots=4, cache_len=256,
                         chunk_tokens=64, paged=True, page_size=16,
                         kv_pool_tokens=1024, tracer=tracer)


def _requests():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt_len=int(rng.integers(8, 150)),
                    max_new_tokens=6, arrival=0.05 * i,
                    task_type=TaskType.ONLINE) for i in range(6)]


def _serve(eng):
    eng.submit(_requests())
    done = eng.run(max_wall_s=300)
    assert len(done) == 6
    return {rid: list(t) for rid, t in eng.outputs.items()}


def _boom(*a, **kw):
    raise RuntimeError("tracing while no profiler captures")


def test_off_path_makes_no_tracer_call(params, monkeypatch):
    want = _serve(_engine(params, tracer=NULL_TRACER))
    eng = _engine(params)
    tr = eng.loop.tracer
    assert isinstance(tr, ProfilerTracer) and not tr.enabled
    assert eng.backend.tracer is tr
    # every method, and the TraceAnnotation its spans would enter (JAX
    # enters its own internally, so the class itself stays as it is)
    for name in TRACER_METHODS + ("_annotation",):
        monkeypatch.setattr(tr, name, _boom)
    assert _serve(eng) == want
    assert tr.events == []


def test_the_cost_model_keeps_the_null_tracer():
    sched = BucketServeScheduler(
        CFG, MemoryBudget(2 ** 34, 1, 0), SchedulerConfig(max_batch=4))
    sim = Simulator(sched, CostModel(CFG, A100X4), mode="disagg")
    assert sim.loop.tracer is NULL_TRACER


def _host_spans(trace_dir):
    """The ``serve.*`` events of the profiler's host plane, as
    (name, start ns, end ns) sorted by start, with each one's parent:
    the innermost ``serve.*`` event around it."""
    pd = ProfileData.from_file(
        glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0])
    evs = sorted((e.start_ns, -e.duration_ns, e.name)
                 for pl in pd.planes if pl.name.startswith("/host:")
                 for ln in pl.lines for e in ln.events
                 if e.name.startswith("serve."))
    out, stack = [], []
    for s, neg, name in evs:
        e = s - neg
        while stack and stack[-1][2] <= s:
            stack.pop()
        out.append((name, stack[-1][0] if stack else None))
        stack.append((name, s, e))
    return out


def test_on_path_spans_twin_the_profiler_and_nest(params, tmp_path):
    eng = _engine(params)
    calls = []
    be = eng.backend
    for name in ("prefill_chunk", "decode_iter"):
        inner = getattr(be, name)

        def wrapped(*a, _inner=inner):
            t0 = be.clock.now()
            out = _inner(*a)
            calls.append((t0, be.clock.now()))
            return out
        setattr(be, name, wrapped)
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng)
    tr = eng.loop.tracer
    assert not tr.enabled
    spans = sorted((e["ts"], e["name"], e["args"]["parent"])
                   for e in tr.events
                   if e["ph"] == "X" and e["name"].startswith("serve.")
                   and e["name"] != "serve.compile")
    names = {n for _, n, _ in spans}
    assert {"serve.loop.iter", "serve.loop.admit", "serve.sched.form_batch",
            "serve.loop.retire", "serve.prefill.batch_cache",
            "serve.prefill.await_prev", "serve.prefill.dispatch",
            "serve.prefill.first_tokens", "serve.prefill.insert_pages",
            "serve.kv.upload_tables", "serve.decode.dispatch",
            "serve.decode.readback", "serve.decode.outputs"} <= names
    # each span has its twin on the profiler's host timeline, in the
    # same order and nested in the same parent
    assert [(n, p) for _, n, p in spans] == _host_spans(tmp_path)
    iters = [(e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6)
             for e in tr.events if e["name"] == "serve.loop.iter"]
    assert calls and all(any(s <= a and b <= e for s, e in iters)
                         for a, b in calls)
    compiles = [e for e in tr.events if e["name"] == "serve.compile"]
    assert compiles and all(e["args"]["fun_name"] and e["args"]["parent"]
                            for e in compiles)
    # counters at the same boundaries
    ctr = {e["name"] for e in tr.events if e["ph"] == "C"}
    assert {"sched.batch", "decode.live", "kv.pages_in_use"} <= ctr


def test_jitted_programs_carry_their_names(params):
    eng = _engine(params, tracer=Tracer())
    _serve(eng)
    compiled = set(eng.loop.tracer.compiles)
    assert {"jit(decode_step)", "jit(zero_batch_cache)"} <= compiled
    # the prefill and chunk steps stay ``fn``: the benchmark's
    # attribution keys on that name
    assert "jit(fn)" in compiled
    assert not any("unknown" in n for n in compiled)


def test_explicit_tracer_records_compiles_and_exports_valid(params):
    tr = Tracer()
    _serve(_engine(params, tracer=tr))
    doc = tr.export()
    assert validate_perfetto(doc) == []
    assert any(e["name"] == "serve.compile" for e in doc["traceEvents"])
    # a tracer on its own clock records compiles from anywhere
    own = Tracer(WallClock())
    jax.jit(lambda x: x * 3 + 1)(np.ones(5)).block_until_ready()
    got = [e for e in own.events if e["name"] == "serve.compile"]
    assert got and {e["args"]["event"] for e in got} >= {
        "jaxpr_trace_duration", "backend_compile_duration"}
    assert all(e["args"]["parent"] is None for e in got)


def test_chunk_dispatch_counts_the_kv_blocks_it_reads(params, monkeypatch):
    """Counter ``attn.chunk_kv_blocks`` once per chunk-step dispatch, in
    order: the blocks of keys [0, start + clen) the chunk attention reads,
    of the cache's S / blk_k (blocks of 64 here, four over 256 slots),
    and ``kernel`` 0: on the CPU ``full_attention`` runs."""
    monkeypatch.setattr(chunk_attn, "BLOCK", 64)
    eng = _engine(params, tracer=Tracer())
    be = eng.backend
    ends = []
    make = be._chunk_fn

    def chunk_fn(clen, bsz):
        fn = make(clen, bsz)

        def call(p, tokens, cache, start, lengths):
            ends.append(start + tokens.shape[1])
            return fn(p, tokens, cache, start, lengths)
        return call
    monkeypatch.setattr(be, "_chunk_fn", chunk_fn)
    _serve(eng)
    got = [e["args"] for e in eng.loop.tracer.events
           if e["name"] == "attn.chunk_kv_blocks"]
    assert len(ends) > 1 and max(ends) > 64
    assert got == [{"read": -(-end // 64), "total": 4, "kernel": 0}
                   for end in ends]
