"""The program's spans on the trace's timeline, the device's idle share
split by what the host was doing, and the program's counters against
the harness's wrappers in the CPU traced rehearsal."""
import types

import numpy as np
import pytest

import harness
import reduce as R
import spans as S
from test_bench_run import _rehearse


def read(name, run):
    return harness.load_reader(name)(run)


def _events(*spans):
    """Chrome complete events of program spans given in loop seconds."""
    return [{"name": n, "ph": "X", "ts": s * 1e6, "dur": (e - s) * 1e6}
            for n, s, e in spans]


def _run(trace, events, lo=10.0):
    tracer = types.SimpleNamespace(events=events)
    client = types.SimpleNamespace(
        engine=types.SimpleNamespace(loop=types.SimpleNamespace(
            tracer=tracer)))
    return R.Run(cfg={}, cfgfile={}, mix={"mode": "open"}, chips=1,
                 client=client, lo=lo, hi=lo + 1.0, setup_s=0.0,
                 compiles=(0, 0), device={}, trace=trace)


@pytest.fixture
def trace():
    # window [1 s, 2 s] in trace ns; device 0 busy [1.0, 1.2) and
    # [1.5, 1.6); device 1 busy [1.0, 1.9)
    ops0 = R.Events.of([(1.0e9, 1.2e9, "a"), (1.5e9, 1.6e9, "b")])
    ops1 = R.Events.of([(0.5e9, 1.9e9, "c")])
    return R.Trace(1.0e9, 2.0e9, [], [ops0, ops1], [ops0, ops1])


def test_spans_are_placed_by_the_window_ends(trace):
    run = _run(trace, _events(("serve.loop.iter", 10.25, 10.5),
                              ("bench.x", 10.0, 11.0), ("other", 10, 11)))
    got = S.program_spans(run)
    assert [n for n, _, _ in got] == ["serve.loop.iter"]
    assert got[0][1:] == pytest.approx((1.25e9, 1.5e9))


def test_idle_shares_sum_to_the_idle_share(trace):
    # loop seconds 10 + x map to trace x + 1
    run = _run(trace, _events(
        ("serve.loop.iter", 10.1, 10.45),
        ("serve.prefill.dispatch", 10.2, 10.4),
        ("serve.compile", 10.25, 10.35),       # inside the dispatch
        ("serve.loop.iter", 10.6, 10.95),
        ("serve.loop.wait", 10.7, 10.8),
        ("serve.compile", 10.75, 10.85)))     # past the wait's end
    # device 0 idle [1.2, 1.5) and [1.6, 2.0): compile 0.10 + 0.10,
    # wait 0.05 (1.7-1.75), host 0.15 + 0.20, none 0.05 + 0.05; device 1
    # idle [1.9, 2.0): host 0.05 (to 1.95), none 0.05
    compile_, wait, host = (read(f"device.idle_frac.{k}", run)
                            for k in ("compile", "wait", "host"))
    assert compile_ == pytest.approx(0.2 / 2)
    assert wait == pytest.approx(0.05 / 2)
    assert host == pytest.approx((0.35 + 0.05) / 2)
    none = S.idle_split(trace, S.program_spans(run))["none"]
    assert none == pytest.approx((0.1 + 0.05) / 2)
    assert compile_ + wait + host + none == pytest.approx(
        read("device.idle_frac", run), abs=1e-12)


def test_covered_length_below_points():
    iv = np.array([[1.0, 2.0], [3.0, 5.0]])
    got = S.covered(iv, [0.0, 1.5, 2.5, 4.0, 9.0])
    assert got == pytest.approx([0.0, 0.5, 1.0, 2.0, 3.0])
    assert S.overlap(np.array([[0.0, 1.5], [4.5, 6.0]]), iv) == \
        pytest.approx(1.0)


@pytest.mark.parametrize("tracer", [
    types.SimpleNamespace(enabled=False),          # the parent's NULL_TRACER
    types.SimpleNamespace(events=[])])             # no span recorded
def test_no_program_spans_nothing_to_read(trace, tracer):
    run = _run(trace, [])
    run.client.engine.loop.tracer = tracer
    for k in ("compile", "wait", "host"):
        assert read(f"device.idle_frac.{k}", run) is None
    run = _run(None, _events(("serve.loop.iter", 10.1, 10.2)))
    assert read("device.idle_frac.host", run) is None


def _paired(calls, counters, name):
    """For each call, the counter ``name`` stamped since the call before
    it began and no later than its own start: the program's reading of
    that call."""
    out, prev = [], -np.inf
    for c in calls:
        got = [a for t, n, a in counters if n == name and prev < t <= c.t0]
        out.append(got)
        prev = c.t0
    return out


def test_traced_rehearsal_counters_match_the_wrappers():
    held = []
    out = _rehearse(trace=True, tamper=held.append)
    got = out["result"]["metrics"]
    # the CPU trace has no device plane: no idle share to split
    assert not {"device.idle_frac.compile", "device.idle_frac.wait",
                "device.idle_frac.host"} & set(got)
    engine = held[0]
    client = engine.sched.on_arrival.__self__          # the harness's
    counters = sorted((e["ts"] * 1e-6, e["name"], e["args"])
                      for e in engine.loop.tracer.events if e["ph"] == "C")
    calls = list(client.calls)
    win = [i for i, c in enumerate(calls) if client.lo <= c.t0 < client.hi]
    live = _paired(calls, counters, "decode.live")
    pages = _paired(calls, counters, "kv.pages_in_use")
    batch = _paired(calls, counters, "sched.batch")
    occ, util, real, padded = [], [], 0, 0
    for i in win:
        c = calls[i]
        if c.kind == "decode":
            assert [a["live"] for a in live[i]] == [c.live]
            assert [(a["in_use"], a["pages"]) for a in pages[i]] == [
                (c.pages_used, c.pages_total)]
            occ.append(live[i][0]["live"] / engine.backend.max_slots)
            util.append(pages[i][0]["in_use"] / pages[i][0]["pages"])
        elif c.first_chunk:
            assert [(a["rows"], a["pad_to"], a["prompt_tokens"])
                    for a in batch[i]] == [(c.rows, c.pad_to,
                                            c.prompt_tokens)]
            real += batch[i][0]["prompt_tokens"]
            padded += batch[i][0]["rows"] * batch[i][0]["pad_to"]
    assert occ and padded
    assert got["sched.decode_occupancy"]["value"] == pytest.approx(
        np.mean(occ), abs=1e-9)
    assert got["kv.pool_util"]["value"] == pytest.approx(
        np.mean(util), abs=1e-9)
    assert got["sched.padding_frac"]["value"] == pytest.approx(
        1 - real / padded, abs=1e-9)
