"""Per-kernel correctness: interpret-mode Pallas vs. pure-jnp oracle,
swept over shapes and dtypes (assert_allclose per instructions)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attn import flash_decode
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_decode_attn import paged_flash_decode
from repro.kernels.wkv6 import wkv6


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-4)


PREFILL_SHAPES = [
    # (B, T, H, Hkv, Dh, window, causal)
    (1, 128, 4, 4, 64, 0, True),
    (2, 200, 8, 2, 64, 0, True),      # GQA + ragged T (padding)
    (2, 384, 4, 1, 128, 0, True),     # MQA
    (1, 300, 4, 2, 64, 128, True),    # sliding window
    (2, 256, 4, 4, 80, 0, False),     # encoder (hubert head_dim 80)
    (1, 64, 2, 2, 256, 0, True),      # large head dim (recurrentgemma)
]


@pytest.mark.parametrize("shape", PREFILL_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill(shape, dtype):
    B, T, H, Hkv, Dh, window, causal = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, T, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, Dh), dtype)
    lengths = jnp.array([T, max(T // 2, 1)][:B], jnp.int32)
    out = flash_prefill(q, k, v, lengths, causal=causal, window=window,
                        blk_q=128, blk_k=128, interpret=True)
    want = ref.flash_prefill_ref(q, k, v, lengths, causal=causal,
                                 window=window)
    valid = np.arange(T)[None, :, None, None] < np.asarray(lengths)[:, None, None, None]
    if causal:
        # row 0 attends to key 0 only; rows beyond length are unmasked
        # garbage in both impls — compare only valid query rows.
        pass
    np.testing.assert_allclose(
        np.where(valid, np.asarray(out, np.float32), 0),
        np.where(valid, np.asarray(want, np.float32), 0), **_tol(dtype))


DECODE_SHAPES = [
    # (B, S, H, Hkv, Dh, ring)
    (2, 256, 8, 2, 64, False),
    (1, 600, 4, 1, 128, False),       # ragged S (padding) + MQA
    (2, 256, 8, 8, 64, False),        # MHA
    (2, 128, 4, 2, 64, True),         # ring cache, wrapped
    (1, 512, 16, 2, 80, False),
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(shape, dtype):
    B, S, H, Hkv, Dh, ring = shape
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, Dh), dtype)
    kc = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
    vc = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
    pos = jnp.array([S // 3, 2 * S + 5][:B], jnp.int32) if ring else \
        jnp.array([S - 1, S // 2][:B], jnp.int32)
    out = flash_decode(q, kc, vc, pos, ring=ring, blk_s=128, interpret=True)
    want = ref.flash_decode_ref(q, kc, vc, pos, ring=ring)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


PAGED_SHAPES = [
    # (B, S, H, Hkv, Dh, page, ring)
    (2, 256, 8, 2, 64, 128, False),   # GQA G=4, divisible
    (2, 256, 8, 8, 64, 128, False),   # MHA (G=1)
    (1, 600, 4, 1, 128, 128, False),  # MQA + non-divisible S/page
    (1, 300, 4, 2, 64, 128, False),   # non-divisible, dead tail page
    (2, 128, 4, 2, 64, 64, True),     # ring cache, wrapped
    (2, 96, 8, 2, 64, 64, True),      # ring, non-divisible window/page
    (1, 512, 16, 2, 80, 256, False),  # large G, odd head dim
]


@pytest.mark.parametrize("shape", PAGED_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode(shape, dtype):
    """Paged kernel vs. (a) its jnp oracle, (b) the CONTIGUOUS flash
    decode over the same cache contents: page placement is shuffled, so
    passing proves allocation layout cannot change results."""
    B, S, H, Hkv, Dh, page, ring = shape
    rng = np.random.default_rng(4)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), dtype)
    kc = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
    vc = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
    pos = jnp.array([2 * S + 5, S // 3][:B], jnp.int32) if ring else \
        jnp.array([S - 1, S // 2][:B], jnp.int32)

    # scatter the contiguous cache into a RANDOMLY PERMUTED page pool
    n_p = -(-S // page)
    Sp = n_p * page
    n_pages = B * n_p + 3                       # a few never-used pages
    perm = rng.permutation(n_pages)[:B * n_p].reshape(B, n_p)
    kp = jnp.pad(kc, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(
        B, n_p, page, Hkv, Dh)
    vp = jnp.pad(vc, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(
        B, n_p, page, Hkv, Dh)
    k_pool = jnp.zeros((n_pages, page, Hkv, Dh), dtype).at[
        perm.reshape(-1)].set(kp.reshape(-1, page, Hkv, Dh))
    v_pool = jnp.zeros((n_pages, page, Hkv, Dh), dtype).at[
        perm.reshape(-1)].set(vp.reshape(-1, page, Hkv, Dh))
    bt = jnp.asarray(perm, jnp.int32)

    out = paged_flash_decode(q, k_pool, v_pool, bt, pos, s_len=S,
                             ring=ring, interpret=True)
    oracle = ref.paged_flash_decode_ref(q, k_pool, v_pool, bt, pos,
                                        s_len=S, ring=ring)
    contig = flash_decode(q, kc, vc, pos, ring=ring, blk_s=page,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(contig, np.float32), **_tol(dtype))


def test_paged_gather_is_exact():
    """gather_paged_kv reconstructs the contiguous cache bit-for-bit —
    the invariant behind token-id parity between the paged and
    contiguous engines."""
    from repro.models.attention import gather_paged_kv
    rng = np.random.default_rng(5)
    B, S, Hkv, Dh, page = 3, 200, 2, 64, 64
    n_p = -(-S // page)
    kc = rng.normal(size=(B, S, Hkv, Dh)).astype(np.float32)
    perm = rng.permutation(B * n_p + 2)[:B * n_p].reshape(B, n_p)
    kp = np.zeros((B, n_p * page, Hkv, Dh), np.float32)
    kp[:, :S] = kc
    pool = np.zeros((B * n_p + 2, page, Hkv, Dh), np.float32)
    pool[perm.reshape(-1)] = kp.reshape(B * n_p, page, Hkv, Dh)
    got = gather_paged_kv(jnp.asarray(pool), jnp.asarray(perm, jnp.int32), S)
    assert np.array_equal(np.asarray(got), kc)


WKV_SHAPES = [
    (1, 64, 2, 64),
    (2, 100, 4, 64),                  # ragged T (padding)
    (1, 128, 1, 32),
    (2, 48, 8, 16),
]


@pytest.mark.parametrize("shape", WKV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6(shape, dtype):
    B, T, H, hs = shape
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    r = jax.random.normal(ks[0], (B, T, H, hs), dtype)
    k = jax.random.normal(ks[1], (B, T, H, hs), dtype)
    v = jax.random.normal(ks[2], (B, T, H, hs), dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hs))).astype(dtype)
    u = jax.random.normal(ks[4], (H, hs), jnp.float32) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, hs, hs), jnp.float32) * 0.1
    y, sT = wkv6(r, k, v, w, u, s0, blk_t=32, interpret=True)
    y_ref, sT_ref = ref.wkv6_ref(r, k, v, w, u, s0)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **tol)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref), **tol)


def test_ops_dispatcher_equivalence():
    """ops.prefill_attention gives identical results on both paths, and
    the kernel path picks interpret mode by platform alone: the Pallas
    interpreter on the CPU backend, a compiled kernel anywhere else."""
    from repro.kernels import interpret_mode, ops
    assert interpret_mode() == (jax.default_backend() == "cpu")
    assert interpret_mode(False) is False
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 160, 4, 64))
    k = jax.random.normal(ks[1], (2, 160, 2, 64))
    v = jax.random.normal(ks[2], (2, 160, 2, 64))
    lens = jnp.array([160, 90], jnp.int32)
    ops.configure(use_pallas=False)
    a = ops.prefill_attention(q, k, v, lens)
    ops.configure(use_pallas=True)
    b = ops.prefill_attention(q, k, v, lens)
    ops.configure(use_pallas=False)
    valid = np.arange(160)[None, :, None, None] < np.asarray(lens)[:, None, None, None]
    np.testing.assert_allclose(np.where(valid, np.asarray(a), 0),
                               np.where(valid, np.asarray(b), 0),
                               atol=2e-5, rtol=2e-4)
