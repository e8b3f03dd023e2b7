"""Each metric reader on a hand-built run record."""
import types

import pytest

import counts
import harness
import reduce as R

YI = {"n_layers": 32, "d_model": 4096, "n_heads": 32, "n_kv_heads": 4,
      "d_head": 128, "d_ff": 11008, "vocab_size": 64000, "qk_norm": False}


def read(name, run):
    return harness.load_reader(name)(run)


@pytest.fixture
def run():
    C = harness.Call
    calls = [
        C("prefill", 0.5, rows=2, pad_to=256, real_tokens=200,
          positions=((0, 128), (0, 72)), first_chunk=True, chunked=True,
          prompt_tokens=300, rids=(1, 2)),
        C("prefill", 1.2, rows=2, pad_to=256, real_tokens=100,
          positions=((128, 100), (128, 0)), chunked=True, rids=(1, 2)),
        C("decode", 1.5, live=2, contexts=(229, 73), pages_used=4,
          pages_total=8),
        C("decode", 1.6, live=4, contexts=(230, 74, 10, 10),
          pages_used=6, pages_total=8),
        C("decode", 5.0, live=1, contexts=(9,), pages_used=1,
          pages_total=8),                     # after the window
    ]
    client = types.SimpleNamespace(
        calls=calls, stamps={1: [1.3, 1.52, 1.62], 2: [2.5], 3: [0.2, 0.4]},
        due={1: 1.1, 2: 1.2, 3: 0.1, 4: 1.9}, admitted={1: 1.1, 2: 1.25},
        max_new={1: 3, 2: 5, 3: 2}, requests=[])
    mods = R.Events.of([(1.5e9, 1.51e9, "jit__unknown(1)"),
                        (1.6e9, 1.612e9, "jit__unknown(1)"),
                        (0.55e9, 0.59e9, "jit_fn(2)"),
                        (1.21e9, 1.25e9, "jit_fn(2)")])
    ops = R.Events.of([(1.0e9, 1.5e9, "fusion"), (1.5e9, 1.51e9, "fusion"),
                       (1.6e9, 1.61e9, "all-reduce.1")])
    spans = [("bench.decode_iter", 1.5e9, 1.52e9),
             ("bench.decode_iter", 1.6e9, 1.62e9),
             ("bench.prefill_chunk", 0.5e9, 0.6e9)]
    tr = R.Trace(1.0e9, 2.0e9, spans, [ops, ops], [mods, mods])
    return R.Run(cfg=YI, cfgfile={"slots": 4},
                 mix={"mode": "open", "drain_cap_s": 3}, chips=2,
                 client=client, lo=1.0, hi=2.0, setup_s=30.0,
                 compiles=(2, 1), device={"kind": "TPU v5 lite"}, trace=tr)


def test_client_side_metrics(run):
    # tokens stamped in [1, 2): 1.3, 1.52, 1.62 -> 3 per second
    assert read("output_tok_s", run) == pytest.approx(3.0)
    # gaps with both ends in the window: 0.22, 0.10; p95 = 0.22 s
    assert read("itl_p95_ms", run) == pytest.approx(220.0)
    assert read("setup_s", run) == 30.0
    # due in the window: 1 (TTFT 0.2), 2 (1.3), 4 (no first token)
    info = run.summary()["info"]
    assert info["ttft_s"] == {"n": 2, "no_first_token": 1,
                              "p50": pytest.approx(0.2),
                              "p90": pytest.approx(1.3)}
    assert info["arrived_in_window"] == 3
    assert run.summary()["attempted"] == 3 and run.summary()["failed"] == 1


def test_counter_metrics(run):
    # one batch whose first chunk the window ran... the one at 0.5 s is
    # before it: no batch began in the window
    assert read("sched.padding_frac", run) is None
    # decode steps in the window: 2 and 4 live of 4 slots
    assert read("sched.decode_occupancy", run) == pytest.approx(0.75)
    assert read("kv.pool_util", run) == pytest.approx((4 / 8 + 6 / 8) / 2)
    assert read("device.window_compiles", run) == 3


def test_device_metrics(run):
    assert read("step.decode_ms", run) == pytest.approx(11.0)
    # fn programs in the window: the one at 1.21 s, 40 ms, for the 100
    # real prompt tokens of the prefill call at 1.2 s (the one at 0.55 s
    # is before the window)
    assert read("step.prefill_ms_per_ktok.online", run) == pytest.approx(400.0)
    assert read("step.prefill_ms_per_ktok.offline", run) is None
    # busy [1.0, 1.51) + [1.6, 1.61) of a 1 s window, on both devices
    assert read("device.idle_frac", run) == pytest.approx(1 - 0.52)
    assert read("device.collective_frac", run) == pytest.approx(0.01)
    peaks = R.peaks_for("TPU v5 lite")
    b = [counts.bound_s(*counts.decode_step(YI, c, 2), peaks)[0]
         for c in [(229, 73), (230, 74, 10, 10)]]
    assert read("decode_step_roofline", run) == pytest.approx(
        100 * (sum(b) / 2) * 2 / 0.022)
    flops = counts.prefill_flops(YI, [(128, 100), (128, 0)]) + \
        counts.decode_step(YI, (229, 73))[0] + \
        counts.decode_step(YI, (230, 74, 10, 10))[0]
    assert read("step_mfu", run) == pytest.approx(
        100 * flops / (1.0 * 2 * 197e12))


def test_no_trace_no_device_metric(run):
    run.trace = None
    for name in ("step.decode_ms", "decode_step_roofline", "step_mfu",
                 "device.idle_frac", "device.collective_frac",
                 "step.prefill_ms_per_ktok.online"):
        assert read(name, run) is None
