"""The qwen3-14b.longdoc cell's mesh path on a four-device CPU mesh, at a
small Qwen3 shape (RMSNorm over each query and key head, 5 query heads
per KV head, 4 KV heads, 512 positions): ``harness.run_cell`` against
the plain reference, the tensor-parallel counters, and the readers of
the cell's tensor-parallel metrics.

The device count is fixed when JAX starts, so the runs are made in one
child process with ``--xla_force_host_platform_device_count=4``: a run
on one device first (no mesh is set yet), then a sound traced run on
the (1, 4) mesh with the float8 control, then one in which every
decode step's tokens are altered.
"""
import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import harness
import reduce as R

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "qwen3-14b.longdoc"
# the widest gap a sound run reads at this shape on the CPU is ~0.01;
# the float8 control reads ~0.2
SMOKE_LIMIT = 0.05

CHILD = """
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{bench!r}, {src!r}]
import harness, run, traffic
from repro.configs import get_smoke_config

bench = harness.load_json({root!r}, "BENCHMARK.json")
smoke = get_smoke_config("qwen3-14b", n_heads=20, n_kv_heads=4, d_head=16,
                         d_model=128, max_seq_len=512)
base = harness.load_json({bench!r}, "configs", "qwen3-14b.json")
base = dict(base, pool_tokens=2 * 512 + 128,
            limits={{"logit_gap_max": {limit!r}}},
            model={{k: getattr(smoke, k, v) for k, v in base["model"].items()}})
mix = dict(traffic.load_mix("longdoc"), preroll_s=1.0, drain_cap_s=5.0,
           output_clip=[4, 200])
mix["classes"] = [dict(c, output=dict(c["output"], median=80))
                  for c in mix["classes"]]
clients = []


class Kept(harness.Client):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        clients.append(self)


harness.Client = Kept


def alter_tokens(engine):
    be = engine.backend
    inner = be.decode_iter

    def decode_iter(pool, context_tokens):
        out = inner(pool, context_tokens)
        for r in be.slot_req:
            if r is not None:
                got = be.outputs[r.rid]
                got[-1] = (got[-1] + 1) % smoke.vocab_size
        return out
    be.decode_iter = decode_iter


def one(cfgfile, trace, **kw):
    out = harness.run_cell(cfgfile, mix, 2 ** 40 + 17, 2.0, trace,
                           run.cell_metrics(bench, {cell!r}, trace),
                           t_proc=time.perf_counter(), model=smoke, **kw)
    c = clients[-1]
    events = c.engine.loop.tracer.events
    return {{"correct": out["result"]["correct"],
            "attempted": out["result"]["attempted"],
            "failed": out["result"]["failed"],
            "metrics": out["result"]["metrics"],
            "gap": out["check"]["logit_gap_max"]["value"],
            "control": out.get("control"),
            "counters": [[e["name"], e["ts"] * 1e-6, e["args"]]
                         for e in events if e["ph"] == "C"],
            "dispatches": [e["ts"] * 1e-6 for e in events
                           if e["name"] == "serve.decode.dispatch"],
            "decodes": [[d.t0, list(d.contexts)] for d in c.calls
                        if d.kind == "decode"]}}


res = {{"one": one(dict(base, chips=1, mesh={{"data": 1, "model": 1}}), True),
       "sound": one(base, True, control=True),
       "fault": one(base, False, tamper=alter_tokens)}}
print("RESULT " + json.dumps(res))
"""


@pytest.fixture(scope="module")
def runs():
    prog = CHILD.format(bench=BENCH, src=os.path.join(ROOT, "src"),
                        root=ROOT, cell=CELL, limit=SMOKE_LIMIT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(prog)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(x for x in p.stdout.splitlines() if x.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def _counter(run, name):
    return [(ts, args) for n, ts, args in run["counters"] if n == name]


def test_mesh_run_agrees_with_the_reference(runs):
    sound = runs["sound"]
    assert sound["correct"] is True and sound["gap"] <= SMOKE_LIMIT
    assert sound["attempted"] > 0 and sound["failed"] == 0
    # the float8 control fails the same comparison
    assert sound["control"]["correct"] is False
    assert sound["control"]["logit_gap_max"]["value"] > SMOKE_LIMIT


def test_wrong_tokens_under_the_mesh_are_not_correct(runs):
    fault = runs["fault"]
    assert fault["correct"] is False and fault["gap"] > SMOKE_LIMIT


def test_collectives_counted_at_every_traced_decode_dispatch(runs):
    sound = runs["sound"]
    decode = [a for _, a in _counter(sound, "tp.collectives")
              if "decode.count" in a]
    chunk = [a for _, a in _counter(sound, "tp.collectives")
             if "chunk.count" in a]
    assert sound["dispatches"] and len(decode) == len(sound["dispatches"])
    assert all(a["decode.count"] > 0 and a["decode.bytes"] > 0
               for a in decode)
    # one compiled step: the same reading at every dispatch
    assert len({(a["decode.count"], a["decode.bytes"]) for a in decode}) == 1
    assert chunk and all(a["chunk.count"] > 0 and a["chunk.bytes"] > 0
                         for a in chunk)
    kib = sound["metrics"]["tp.decode_collective_kib"]["value"]
    assert kib == pytest.approx(decode[0]["decode.bytes"] / 1024)


def test_decode_context_matches_the_wrappers_call_for_call(runs):
    sound = runs["sound"]
    got = _counter(sound, "kv.decode_context")
    assert got and len(got) == len(sound["dispatches"])
    starts = [t0 for t0, _ in sound["decodes"]]
    for ts, args in got:
        # the wrapped call that was running when the counter was stamped
        _, ctx = sound["decodes"][int(np.searchsorted(starts, ts)) - 1]
        assert (args["sum"], args["max"]) == (sum(ctx), max(ctx))


def test_one_device_run_records_neither_counter(runs):
    one = runs["one"]
    assert one["correct"] is True and one["dispatches"]
    names = {n for n, _, _ in one["counters"]}
    assert not names & {"tp.collectives", "kv.decode_context"}
    assert "tp.decode_collective_kib" not in one["metrics"]


def test_device_trace_readers_need_a_device_trace(runs):
    # the CPU trace has no device plane: no device-trace metric
    for run in (runs["one"], runs["sound"]):
        assert not set(run["metrics"]) & {
            "tp.decode_collective_ms", "tp.step_mfu",
            "device.collective_frac"}


# ------------------------------------------------- readers, by hand --
QWEN = {"n_layers": 40, "d_model": 5120, "n_heads": 40, "n_kv_heads": 8,
        "d_head": 128, "d_ff": 17408, "vocab_size": 151936, "qk_norm": True}


def _read(name, run):
    return harness.load_reader(name)(run)


def _trace():
    """Two devices, two decode calls in a window of [1, 2) s: device 0
    runs an async all-reduce (start 0.1 ms, done 0.2 ms, 1 ms apart) and
    a 0.5 ms all-gather that overlaps it; device 1 a plain 2 ms
    all-reduce.  A chunk step (``fn``) in the first call holds a
    collective that is not the decode step's."""
    ms = 1e6
    mods = [R.Events.of([(1.5e9, 1.5e9 + 10 * ms, "jit_decode_step(1)"),
                         (1.5e9 + 11 * ms, 1.5e9 + 15 * ms, "jit_fn(2)"),
                         (1.7e9, 1.7e9 + 10 * ms, "jit_decode_step(1)")])] * 2
    ops0 = R.Events.of([
        (1.5e9 + 1 * ms, 1.5e9 + 1.1 * ms, "all-reduce-start.1"),
        (1.5e9 + 1.5 * ms, 1.5e9 + 2.0 * ms, "all-gather.2"),
        (1.5e9 + 2.0 * ms, 1.5e9 + 2.2 * ms, "all-reduce-done.1"),
        (1.5e9 + 3 * ms, 1.5e9 + 4 * ms, "fusion.3"),
        (1.5e9 + 12 * ms, 1.5e9 + 13 * ms, "all-reduce.9"),
        (1.7e9 + 1 * ms, 1.7e9 + 1.1 * ms, "all-reduce-start.1"),
        (1.7e9 + 2.0 * ms, 1.7e9 + 2.2 * ms, "all-reduce-done.1")])
    ops1 = R.Events.of([(1.5e9 + 1 * ms, 1.5e9 + 3 * ms, "all-reduce.4"),
                        (1.7e9 + 1 * ms, 1.7e9 + 3 * ms, "all-reduce.4")])
    spans = [("bench.decode_iter", 1.5e9, 1.5e9 + 20 * ms),
             ("bench.decode_iter", 1.7e9, 1.7e9 + 20 * ms)]
    return R.Trace(1.0e9, 2.0e9, spans, [ops0, ops1], mods)


def _run(trace=None, chips=4, events=()):
    C = harness.Call
    client = types.SimpleNamespace(
        calls=[C("prefill", 1.2, rows=1, pad_to=256, real_tokens=128,
                 positions=((0, 128),), chunked=True, first_chunk=True,
                 prompt_tokens=200, rids=(1,)),
               C("decode", 1.5, live=2, contexts=(1000, 3000)),
               C("decode", 1.7, live=2, contexts=(1001, 3001))],
        stamps={}, due={}, max_new={}, requests=[],
        engine=types.SimpleNamespace(loop=types.SimpleNamespace(
            tracer=types.SimpleNamespace(events=list(events)))))
    return R.Run(cfg=QWEN, cfgfile={"slots": 2},
                 mix={"mode": "offline"}, chips=chips, client=client,
                 lo=1.0, hi=2.0, setup_s=60.0, compiles=(0, 0),
                 device={"kind": "TPU v5 lite"}, trace=trace)


def test_decode_collective_ms_joins_async_pairs_once():
    # device 0: [1.0, 2.2] joined, with the all-gather inside it: 1.2 ms,
    # then 1.2 ms; the chunk step's all-reduce is left out; device 1: 2 ms
    # each step
    got = _read("tp.decode_collective_ms", _run(_trace()))
    assert got == pytest.approx((1.2 + 2.0) / 2)
    assert _read("tp.decode_collective_ms", _run(None)) is None
    assert _read("tp.decode_collective_ms", _run(_trace(), chips=1)) is None


def test_step_mfu_of_a_tensor_parallel_cell_is_step_mfus_reading():
    run = _run(_trace())
    assert _read("tp.step_mfu", run) == _read("step_mfu", run) > 0
    assert _read("tp.step_mfu", _run(_trace(), chips=1)) is None
    assert _read("tp.step_mfu", _run(None)) is None


def test_decode_collective_kib_reads_the_program_counter():
    ev = [{"name": "tp.collectives", "ph": "C", "ts": 1.5e6,
           "args": {"decode.count": 81, "decode.bytes": 81 * 20480}},
          {"name": "tp.collectives", "ph": "C", "ts": 1.6e6,
           "args": {"chunk.count": 81, "chunk.bytes": 10 ** 7}},
          {"name": "tp.collectives", "ph": "C", "ts": 2.5e6,   # after
           "args": {"decode.count": 1, "decode.bytes": 1}}]
    assert _read("tp.decode_collective_kib", _run(events=ev)) == 81 * 20
    assert _read("tp.decode_collective_kib", _run()) is None
