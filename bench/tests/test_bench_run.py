"""bench/run.py's contract on the CPU: it refuses to run without a TPU,
and its inner run, rehearsed at smoke width with the device check
stepped around, prints a well-formed line that the check can turn
false."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

import harness  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# the widest gap a sound smoke-width run reads on the CPU is ~0.01; the
# float8 control reads ~0.3 there
SMOKE_LIMIT = 0.05


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi-6b.mixed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_benchmark_file_names_readers_and_files():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    assert bench["command"] == ["python3", "bench/run.py"]
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(ROOT, c["file"]))
    cells = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
        assert len(w["why"]) <= 200
        cells.add(w["name"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(harness.load_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for cell in cells:
        assert {m["name"] for m in run.cell_metrics(bench, cell, False)} >= {
            "setup_s", "itl_p95_ms"}
        assert run.cell_metrics(bench, cell, True)


def test_window_opens_on_the_loop_clock_while_idle():
    """No request is served while the window opens: it opens at its
    mark all the same, and the profiler starts ahead of it."""
    import types

    from repro.core.serving_loop import WallClock
    clock = WallClock()
    seen = []

    def loop_run(requests, time_limit):
        be.begin(requests)
        while True:                     # an idle server: ticks only
            clock.advance(clock.now() + 0.001)

    be = types.SimpleNamespace(begin=lambda reqs: clock.start(),
                               prefill_chunk=None, decode_iter=None,
                               clock=clock)
    engine = types.SimpleNamespace(
        backend=be, sched=types.SimpleNamespace(on_arrival=None),
        loop=types.SimpleNamespace(run=loop_run))
    client = harness.Client(engine, 0.3, 0.2, 1.0,
                            on_start=lambda: seen.append(client.now()),
                            lead=0.1,
                            on_close=lambda: seen.append(client.now()))
    client.serve([], measuring=True)
    assert (client.lo, client.hi) == (0.3, pytest.approx(0.5))
    assert client.closed and 0.5 <= client.now() < 0.55
    # the profiler starts ahead of the window and stops as it closes
    assert len(seen) == 2 and 0.2 <= seen[0] < 0.25
    assert 0.5 <= seen[1] <= client.drain_from < 0.55


def _smoke_cell(slots=2):
    from repro.configs import get_smoke_config
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    w = next(x for x in bench["workloads"] if x["name"] == "yi-6b.mixed")
    cfgfile = harness.load_json(BENCH, "configs", "yi-6b.json")
    smoke = get_smoke_config("yi-6b", max_seq_len=512)
    cfgfile = dict(cfgfile, slots=slots, pool_tokens=1024,
                   limits={"logit_gap_max": SMOKE_LIMIT},
                   model={k: getattr(smoke, k, v)
                          for k, v in cfgfile["model"].items()})
    mix = dict(traffic.load_mix("mixed"), rate_rps=4.0, preroll_s=0.5,
               drain_cap_s=5.0)
    mix["classes"] = [dict(c, output=dict(c["output"], median=10))
                      for c in mix["classes"]]
    return bench, w, cfgfile, mix, smoke


def _rehearse(trace=False, **kw):
    bench, w, cfgfile, mix, smoke = _smoke_cell()
    return harness.run_cell(
        cfgfile, mix, 2 ** 40 + 17, 2.0, trace,
        run.cell_metrics(bench, w["name"], trace),
        t_proc=time.perf_counter(), model=smoke, **kw)


@pytest.fixture(scope="module")
def sound():
    return _rehearse(control=True)


def test_rehearsal_prints_a_well_formed_line(sound, capsys):
    run.report(sound)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert err.strip().splitlines()[-1].startswith("check logit_gap_max ")
    info = sound["info"]
    assert info["arrived_in_window"] > 0 and info["tokens_in_window"] > 0
    assert info["ttft_s"]["n"] > 0 and info["ttft_s"]["p90"] > 0
    assert info["checked_tokens"] > 0
    # the control, the reference in float8, fails the same comparison
    assert sound["control"]["correct"] is False
    assert sound["control"]["logit_gap_max"]["value"] > SMOKE_LIMIT
    assert info["control_gap_max"] > SMOKE_LIMIT
    # the window opened at its mark on the loop clock
    assert info["phases_s"]["preroll_s"] == pytest.approx(0.5, abs=0.25)


def _alter_a_token(engine):
    be = engine.backend
    inner = be.decode_iter

    def decode_iter(pool, context_tokens):
        out = inner(pool, context_tokens)
        r = next(r for r in be.slot_req if r is not None)
        be.outputs[r.rid][-1] = (be.outputs[r.rid][-1] + 1) % \
            engine.cfg.vocab_size
        return out
    be.decode_iter = decode_iter


def _keep_decode_state(engine):
    be = engine.backend
    inner = be._decode_fn
    be._decode_fn = lambda p, tok, cache: (inner(p, tok, cache)[0], cache)


@pytest.mark.parametrize("fault", [_alter_a_token, _keep_decode_state],
                         ids=["token_altered", "state_unchanged"])
def test_a_fault_in_the_timed_path_is_not_correct(fault):
    out = _rehearse(tamper=fault)
    assert out["result"]["correct"] is False
    assert out["check"]["logit_gap_max"]["value"] > SMOKE_LIMIT


def test_traced_rehearsal_reads_counters_but_no_device_metric():
    out = _rehearse(trace=True)
    got = out["result"]["metrics"]
    # the CPU has no device plane: no device-trace metric is reported
    assert "step.decode_ms" not in got and "device.idle_frac" not in got
    assert 0 < got["sched.decode_occupancy"]["value"] <= 1
    assert 0 < got["kv.pool_util"]["value"] <= 1
    assert 0 <= got["sched.padding_frac"]["value"] < 1
