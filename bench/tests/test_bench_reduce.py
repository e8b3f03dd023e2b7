"""The device-trace reduction: busy intervals, attribution, idle gaps."""
import numpy as np
import pytest

import reduce as R


def events(*evs):
    return R.Events.of(evs)


def test_union_merges_overlaps_and_touching():
    iv = np.array([[5, 7], [0, 2], [1, 3], [3, 4], [10, 11]], float)
    assert R.union(iv).tolist() == [[0, 4], [5, 7], [10, 11]]


def test_gaps_between_busy_inside_window():
    busy = np.array([[2, 4], [6, 7]], float)
    assert R.gaps(busy, 0, 10).tolist() == [[0, 2], [4, 6], [7, 10]]
    assert R.gaps(np.zeros((0, 2)), 0, 10).tolist() == [[0, 10]]


def test_module_names_reduce_to_the_function():
    assert R.module_name("jit_decode_step(1234)") == "decode_step"
    assert R.module_name("jit_fn.3") == "fn"
    assert R.module_name("jit_fn") == "fn"


def test_label_is_the_innermost_span():
    spans = [("bench.decode_iter", 0, 10), ("bench.prefill_chunk", 2, 4)]
    assert R.label_at(spans, 3) == "bench.prefill_chunk"
    assert R.label_at(spans, 5) == "bench.decode_iter"
    assert R.label_at(spans, 11) == "host"


@pytest.fixture
def trace():
    # window [100, 200) ns on two devices; device 1 is busy half as long
    ops0 = events((90, 110, "fusion.1"), (105, 120, "fusion.2"),
                  (150, 160, "all-reduce.3"), (190, 230, "fusion.1"))
    ops1 = events((100, 125, "fusion.1"), (150, 160, "all-reduce.3"))
    mods0 = events((100, 120, "jit_decode_step(1)"), (130, 140, "jit_fn(2)"),
                   (150, 170, "jit_scatter(3)"), (180, 190, "jit_fn(2)"),
                   (300, 310, "jit_fn(2)"))
    spans = [("bench.decode_iter", 95, 125), ("bench.prefill_chunk", 128, 170)]
    return R.Trace(100.0, 200.0, spans, [ops0, ops1], [mods0, events()])


def test_busy_is_the_union_clipped_to_the_window(trace):
    # device 0: [100, 120) + [150, 160) + [190, 200) = 40 ns
    assert trace.busy_s(0) == pytest.approx(40e-9)
    assert trace.busy_s(1) == pytest.approx(35e-9)
    assert trace.mean_busy_s() == pytest.approx(37.5e-9)
    assert trace.window_s == pytest.approx(100e-9)


def test_attribution_by_name_and_by_span(trace):
    dec = trace.module_time(lambda n, s: n == "decode_step")
    assert dec == (pytest.approx(20e-9), 1)
    pre = trace.module_time(
        lambda n, s: n == "fn" or (n != "decode_step"
                                   and trace.in_span("bench.prefill_chunk", s)))
    # two fn programs in the window, the scatter inside a prefill span;
    # the fn at 300 ns starts after the window
    assert pre == (pytest.approx(40e-9), 3)


def test_step_is_the_longest_program_each_call_launched():
    # two decode calls in the window; each launched its step and an
    # argmax; the second call's step is named otherwise
    mods = events((101, 111, "jit__unknown(1)"), (111, 112, "jit__argmax(2)"),
                  (131, 139, "jit_decode_step(3)"), (139, 140, "jit__argmax(2)"),
                  (150, 170, "jit_fn(4)"))
    spans = [("bench.decode_iter", 100, 115), ("bench.decode_iter", 130, 141),
             ("bench.prefill_chunk", 149, 160), ("bench.decode_iter", 90, 99)]
    tr = R.Trace(100.0, 200.0, spans, [events()], [mods])
    progs = tr.span_programs("bench.decode_iter")
    assert [[n for n, _ in p] for p in progs] == [
        ["jit__unknown(1)", "jit__argmax(2)"],
        ["jit_decode_step(3)", "jit__argmax(2)"]]
    # each call's whole device work: the step and its argmax
    assert tr.step_times("bench.decode_iter") == [pytest.approx(11e-9),
                                                  pytest.approx(9e-9)]
    assert tr.step_times("bench.prefill_chunk") == [pytest.approx(20e-9)]
    # a chunk step the prefill call dispatched can start on the device
    # once the next decode call has begun: it is not the decode's work
    late = events((101, 111, "jit__unknown(1)"), (111, 112, "jit__argmax(2)"),
                  (113, 125, "jit_fn(4)"))
    tr = R.Trace(100.0, 200.0, [("bench.decode_iter", 100, 130)],
                 [events()], [late])
    assert tr.step_times("bench.decode_iter") == [pytest.approx(23e-9)]
    assert tr.step_times("bench.decode_iter",
                         exclude=R.PREFILL_PROGRAMS) == [pytest.approx(11e-9)]


def test_collectives_and_top_ops(trace):
    assert trace.op_time(R.COLLECTIVE, 0) == pytest.approx(10e-9)
    top = trace.top_ops()
    assert top[0][0] == "fusion.1"
    # fusion.1 started in the window: device 0 10 ns (clipped at the
    # window's end), device 1 25 ns; mean over the two devices
    assert top[0][1] == pytest.approx(17.5e-9)


def test_idle_gaps_longest_first_with_host_labels(trace):
    g = trace.idle_gaps()
    # [120, 150) and [160, 190) on device 0; the host was in a prefill
    # call at 135 and in no wrapped call at 175
    assert [round(x[1] * 1e9) for x in g] == [30, 30]
    assert [x[0] for x in g] == ["bench.prefill_chunk", "host"]


def test_read_trace_finds_the_harness_spans(tmp_path):
    import time

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(R.ANCHOR):
        anchor = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.decode_iter"):
        f(x).block_until_ready()
    end = time.perf_counter()
    jax.profiler.stop_trace()
    # a window from before the profiler started is cut to the anchor
    tr = R.read_trace(str(tmp_path), -1.0, end - anchor + 0.01)
    assert tr.hi > tr.lo
    assert [s[0] for s in tr.spans] == ["bench.decode_iter"]
    name, s, e = tr.spans[0]
    assert tr.lo <= s < e <= tr.hi
    assert tr.window_s == pytest.approx(end - anchor + 0.01)
    assert tr.ops == []                 # the CPU has no device plane


def test_read_trace_without_window_is_an_error(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    with pytest.raises((ValueError, FileNotFoundError)):
        R.read_trace(str(tmp_path), 0.0, 1.0)
