"""Per-kernel correctness: interpret-mode Pallas vs. pure-jnp oracle,
swept over shapes and dtypes (assert_allclose per instructions)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import chunk_attn, ref
from repro.kernels.chunk_attn import chunk_attention
from repro.kernels.decode_attn import flash_decode
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_decode_attn import paged_flash_decode
from repro.kernels.wkv6 import wkv6
from repro.models import attention
from repro.models import transformer as tfm
from repro.models.attention import full_attention
from repro.sharding import context as shctx


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


CHUNK_CASES = [(B, G, Dh, start) for B in (1, 3) for G in (5, 8)
               for Dh in (64, 128) for start in (0, 128, 268)]


@pytest.mark.parametrize("B,G,Dh,start", CHUNK_CASES)
def test_chunk_attention(B, G, Dh, start):
    """The chunk kernel equals ``full_attention(causal=True,
    q_offset=start)`` for a 32-token chunk at the cache's start, in a
    middle block and at its end, over S = 300 slots in blocks of 128
    (the last one ragged).  With B = 3, row 1's prompt ended at slot 40:
    past it lie slots no chunk wrote (zeros).  Then the fault: every
    slot at or past start + Tc is NaN, behind a layer 0 that is all NaN,
    and the kernel, told to read layer 1, gives the same output, finite:
    it reads no live value from past the bound or from another layer."""
    S, Tc, Hkv, blk = 300, 32, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, Tc, G * Hkv, Dh), bf)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), bf)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), bf)
    if B > 1:
        k, v = (x.at[1, 40:].set(0) for x in (k, v))
    want = full_attention(q, k, v, causal=True, q_offset=start)
    got = chunk_attention(q, k, v, jnp.int32(start), blk_k=blk,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(bf))
    dead = (jnp.arange(S) >= start + Tc)[None, :, None, None]
    k_nan, v_nan = (jnp.stack([jnp.full_like(x, jnp.nan),
                               jnp.where(dead, jnp.nan, x)])
                    for x in (k, v))
    planted = chunk_attention(q, k_nan, v_nan, jnp.int32(start),
                              layer=jnp.int32(1), blk_k=blk, interpret=True)
    assert np.isfinite(np.asarray(planted, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(planted), np.asarray(got))


@pytest.mark.parametrize("Hkv,Dh", [(1, 64), (4, 128), (4, 64), (8, 128)])
def test_chunk_attention_reads_every_head_layout(Hkv, Dh):
    """Each kv head is read out of a block of all of them: one head
    alone, pairs packed in 32-bit words (strided over 2 or 4 pairs), and
    the plain reads wherever words cannot be strided (Dh 64)."""
    S, Tc, G, start = 300, 32, 2, 140
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (2, Tc, G * Hkv, Dh), bf)
    k = jax.random.normal(ks[1], (2, S, Hkv, Dh), bf)
    v = jax.random.normal(ks[2], (2, S, Hkv, Dh), bf)
    want = full_attention(q, k, v, causal=True, q_offset=start)
    got = chunk_attention(q, k, v, jnp.int32(start), blk_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(bf))


def test_chunk_block_fits_every_config_head_layout():
    """Blocks of 512 slots for both cells' caches (and any GQA cache of
    up to 8 KV heads of 128), smaller for wide multi-head caches, never
    below 128 nor past the cache."""
    assert chunk_attn.block_len(4, 128, 4096) == 512          # yi-6b
    assert chunk_attn.block_len(8, 128, 32768) == 512         # qwen3-14b
    assert chunk_attn.block_len(40, 128, 4096) == 128         # llama2-13b
    assert chunk_attn.block_len(32, 64, 32768) == 128         # stablelm
    assert chunk_attn.block_len(8, 192, 32768) == 256         # nemotron
    assert chunk_attn.block_len(4, 128, 300) == 300
    assert chunk_attn.kv_blocks(130, 300, 128) == (2, 3)
    assert chunk_attn.kv_blocks(4096, 4096, 512) == (8, 8)


class _Mesh:
    """Stands in for a registered mesh: the chooser reads only these."""
    axis_names = ("data", "model")
    shape = {"data": 1, "model": 4}


@pytest.mark.parametrize("backend,arch,kw,dtype,mesh,want", [
    ("cpu", "qwen3-14b", {}, jnp.bfloat16, None, False),
    ("tpu", "qwen3-14b", {}, jnp.bfloat16, None, True),
    ("tpu", "qwen3-14b", {}, jnp.float32, None, False),
    ("tpu", "qwen3-14b", {"kv_cache_dtype": "int8"}, jnp.bfloat16, None,
     False),
    ("tpu", "qwen3-14b", {}, jnp.bfloat16, _Mesh, True),      # 8 KV heads
    ("tpu", "qwen3-moe-235b-a22b", {}, jnp.bfloat16, _Mesh, True),
    ("tpu", "qwen3-14b", {"n_kv_heads": 2}, jnp.bfloat16, _Mesh, False),
])
def test_chunk_kernel_is_chosen_by_platform_dtype_and_mesh(
        backend, arch, kw, dtype, mesh, want, monkeypatch):
    """The kernel runs on the TPU over a bf16 cache whose KV heads split
    evenly over the mesh's ``model`` axis; elsewhere ``full_attention``
    does (the CPU, int8 and float32 caches, heads that do not split)."""
    from repro.configs import get_config
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setitem(shctx._CTX, "mesh", mesh)
    cfg = get_config(arch)
    if kw:
        import dataclasses
        cfg = dataclasses.replace(cfg, **kw)
    assert attention.uses_chunk_kernel(cfg, dtype) is want


def _chained_chunks(cfg, params, B=3, S=256, C=64):
    """Chunked prefill of B prompts (lengths 200, 70, 130) in C-token
    chunks: per chunk, the logits of the rows whose prompt ends in it,
    then the batch cache."""
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    lens = jnp.array([200, 70, 130][:B], jnp.int32)
    cache = tfm.init_cache(cfg, B, S, jnp.bfloat16)
    step = jax.jit(lambda p, t, c, s, n: tfm.prefill_chunk(cfg, p, t, c,
                                                           s, n))
    out = []
    for s in range(0, S, C):
        logits, cache = step(params, toks[:, s:s + C], cache, s, lens)
        fin = np.asarray((lens - 1 >= s) & (lens - 1 < s + C))
        out.append(np.asarray(logits, np.float32)[fin])
    return out, cache


@pytest.mark.parametrize("arch", ["yi-6b", "qwen3-14b"])
def test_chained_chunks_through_the_kernel_match_the_jnp_path(
        arch, monkeypatch):
    """prefill_chunk over four chained chunks with the kernel path taken
    (in the Pallas interpreter, blocks of 64 slots, so the bound moves
    across four blocks) gives the logits and batch cache of the jnp path
    on a bf16 smoke model, to bf16 rounding: the logits (to about ±4) and
    the cache (to about ±4.5) within 0.1 of each other (0.043 and 0.047
    apart at most on the CPU), where a wrong head, block or position
    moves them by whole units."""
    cfg = get_smoke_config(arch, max_seq_len=256)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    monkeypatch.setattr(chunk_attn, "BLOCK", 64)
    want, want_cache = _chained_chunks(cfg, params)
    monkeypatch.setattr(attention, "uses_chunk_kernel", lambda c, d: True)
    got, got_cache = _chained_chunks(cfg, params)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=0.1, rtol=0)
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0.1,
                                   rtol=0)


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
