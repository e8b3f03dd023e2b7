"""Operation and byte counts of the decode step and of prefill, against
hand counts at the published widths."""
import json
import os

import pytest

import counts
import reduce

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def model(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)["model"]


YI = {"n_layers": 32, "d_model": 4096, "n_heads": 32, "n_kv_heads": 4,
      "d_head": 128, "d_ff": 11008, "vocab_size": 64000, "qk_norm": False}
QWEN3_14B = {"n_layers": 40, "d_model": 5120, "n_heads": 40, "n_kv_heads": 8,
             "d_head": 128, "d_ff": 17408, "vocab_size": 151936,
             "qk_norm": True}


def test_yi_matmul_weights_by_hand():
    # per layer: wq 4096x4096 + wk, wv 4096x512 + wo 4096x4096
    # + gate, up, down 3 x 4096x11008 = 173,015,040; x 32 layers
    # + head 64000 x 4096
    assert counts.matmul_params(YI) == 173_015_040 * 32 + 262_144_000
    # with the embedding and the norms, the Yi-6B card's 6.06e9
    total = counts.matmul_params(YI) + 262_144_000 + 32 * 2 * 4096 + 4096
    assert total == 6_061_035_520


def test_qwen3_14b_matmul_weights_by_hand():
    # per layer: 2 x 5120x5120 + 2 x 5120x1024 + 3 x 5120x17408
    # = 330,301,440; x 40 + head 151936 x 5120; 14.77e9 with embedding
    assert counts.matmul_params(QWEN3_14B) == 330_301_440 * 40 + 777_912_320
    assert counts.matmul_params(QWEN3_14B) + 777_912_320 == 14_767_882_240


def test_decode_step_yi_two_live_slots():
    flops, nbytes = counts.decode_step(YI, [1000, 2000])
    # 2 tokens x 2 x weights + 4 x 32 layers x 32 heads x 128 x 3000
    assert flops == 2 * 2 * 5_798_625_280 + 1_572_864_000
    # bf16 weights and norms once, and 64 KiB of K/V per context token
    assert nbytes == (5_798_625_280 + 32 * 2 * 4096 + 4096) * 2 \
        + 65_536 * 3000
    t, which = counts.bound_s(flops, nbytes, reduce.peaks_for("TPU v5 lite"))
    assert which == "memory"
    assert t == pytest.approx(11_794_391_040 / 819e9)


def test_decode_step_shared_over_four_chips():
    one = counts.decode_step(QWEN3_14B, [30000])
    four = counts.decode_step(QWEN3_14B, [30000], chips=4)
    assert four == pytest.approx((one[0] / 4, one[1] / 4))
    # qk-norm scales are read too: 2 x 128 per layer
    assert counts.weight_bytes(QWEN3_14B) == 2 * (
        counts.matmul_params(QWEN3_14B) + 40 * 2 * 5120 + 5120 + 40 * 256)


def test_prefill_counts_causal_attention():
    p = counts.matmul_params(YI)
    a = counts.attn_flops(YI, 1)
    # 4 tokens from position 0 attend 1+2+3+4 positions
    assert counts.prefill_flops(YI, [(0, 4)]) == 4 * 2 * p + 10 * a
    # 2 tokens from position 128 attend 129 and 130
    assert counts.prefill_flops(YI, [(128, 2)]) == 2 * 2 * p + 259 * a


def test_config_files_state_the_published_widths():
    for name, want in (("yi-6b", YI),):
        m = model(name)
        assert {k: m[k] for k in want} == want


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        reduce.peaks_for("TPU v9 imaginary")
    assert reduce.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
