"""Pallas TPU kernel: one prefill chunk's attention against a positional
KV cache (chunked prefill, DESIGN.md §2).

Tc new queries at absolute positions ``[start, start + Tc)`` attend
causally over the cache, whose first ``start + Tc`` slots hold the
prompt's earlier chunks and this one.  The jnp oracle
(``models.attention.full_attention(..., causal=True, q_offset=start)``)
scores every query against all ``S`` cache slots and writes the
(B, Hkv, G, Tc, S) float32 scores and probabilities to HBM; here they
live only in VMEM, one (G·Tc, blk_k) tile at a time, and the cache is
read only up to ``start + Tc``.

Grid: (B, n_kv) with the KV blocks innermost (sequential on one core,
so the online-softmax statistics persist in VMEM scratch).  A K/V block
spans every kv head, ``(blk_k, Hkv, Dh)``: its two minor dims are whole,
so the block matches the cache's own tiled layout and the cache is
consumed where it lies, with no relayout copy.  Each kv head's G query
heads × Tc queries form one (G·Tc, Dh) q tile, so a K/V block is read
once per kv head, not G times.  ``start`` is scalar-prefetched: the K/V
index maps clamp the block index to the last live block, so a dead step
fetches nothing new, and ``pl.when`` skips its compute; only the one or
two blocks the chunk's diagonal crosses build a mask.

The cache may be stacked over layers, (L, B, S, Hkv, Dh), with ``layer``
scalar-prefetched beside ``start``: a layer scan then hands over its
whole carried cache and no per-layer slice is ever copied out.

Precision: QKᵀ and P·V run on the MXU with bf16 operands (the cache as
stored, P rounded to the cache dtype as a default-precision float32
product does on the TPU) and float32 accumulation; the softmax
statistics are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

NEG_INF = -1e30
BLOCK = 512          # cache slots per K/V block; divides 4,096 and 32,768
# scoped VMEM, of a v5e core's 128 MiB: the unrolled per-head (G·Tc,
# blk_k) score tiles outgrow the 16 MiB default
VMEM_LIMIT = 64 << 20


def block_len(n_kv_heads: int, d_head: int, s_len: int) -> int:
    """Cache slots per K/V block: ``BLOCK``, halved down to 128 while a
    bf16 block of every kv head, lanes padded to 128, passes 1 MiB (wide
    multi-head caches, whose unrolled per-head tiles would outgrow VMEM),
    and at most the cache."""
    lanes = -(-d_head // 128) * 128
    blk = BLOCK
    while blk > 128 and blk * n_kv_heads * lanes * 2 > 1 << 20:
        blk //= 2
    return min(blk, s_len)


def kv_blocks(end: int, s_len: int, blk_k: int):
    """(blocks read, blocks in the cache) for keys ``[0, end)`` of an
    ``s_len``-slot cache in ``blk_k``-slot blocks: what the causal bound
    spares."""
    return -(-min(end, s_len) // blk_k), -(-s_len // blk_k)


def _head(ref, h: int):
    """Kv head ``h``'s (blk_k, Dh) slice of a (1, 1, blk_k, Hkv, Dh) K/V
    block.  With Hkv even, the cache's (Hkv, 128) tiling packs the bf16
    values of heads 2j and 2j + 1 into one 32-bit word: the block is read
    as words, rows of pair j taken with a strided load, and the word's
    half shifted or masked into a float32 that holds the bf16 value
    exactly.  That costs a few vector operations a row, where picking
    the head out of the packed tiles directly relays out the block."""
    _, _, blk, hkv, dh = ref.shape
    if hkv % 2 or (hkv > 2 and dh != 128):   # a strided load needs 128
        return ref[0, 0, :, h, :]
    words = ref.bitcast(jnp.uint32).reshape(blk * (hkv // 2), dh)
    pair = pl.ds(h // 2, blk, stride=hkv // 2) if hkv > 2 else slice(None)
    w = words[pair, :]
    w = (w << 16) if h % 2 == 0 else (w & jnp.uint32(0xFFFF0000))
    return pltpu.bitcast(w, jnp.float32).astype(ref.dtype)


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            tc: int, blk_k: int, n_kv: int, scale: float):
    ki = pl.program_id(1)
    start = idx_ref[0]
    end = start + tc                            # keys [0, end) are live
    base = ki * blk_k

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(masked: bool):
        """One online-softmax step of every kv head over this K/V block:
        its G query heads' Tc queries are one (G·Tc, Dh) q tile."""
        rows = q_ref.shape[2]                   # G * Tc, g-major
        if masked:
            shape = (rows, blk_k)
            qpos = start + jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, shape, 0), tc)
            ok = base + jax.lax.broadcasted_iota(jnp.int32, shape, 1) <= qpos
            # slots at or past ``end`` are masked out of the scores; zero
            # their values too, so that whatever they hold (never written,
            # or past the cache's end) cannot reach the output as 0 * inf
            vlive = base + jax.lax.broadcasted_iota(
                jnp.int32, (blk_k, 1), 0) < end
        for h in range(k_ref.shape[3]):
            k, v = _head(k_ref, h), _head(v_ref, h)
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(ok, s, NEG_INF)
                v = jnp.where(vlive, v, 0)
            m_prev = m_ref[h]                                # (rows, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    # a block wholly at or below ``start`` is seen by every query: no
    # mask; the one or two blocks the chunk's diagonal crosses are masked;
    # blocks from ``end`` on are dead
    @pl.when(base + blk_k <= start + 1)
    def _below():
        attend(masked=False)

    @pl.when(jnp.logical_and(base + blk_k > start + 1, base < end))
    def _diagonal():
        attend(masked=True)

    @pl.when(ki == n_kv - 1)
    def _fin():
        for h in range(acc_ref.shape[0]):
            o_ref[0, h] = (acc_ref[h] / l_ref[h]).astype(o_ref.dtype)


def chunk_attention(q, k_cache, v_cache, start, *, layer=None,
                    blk_k: int = BLOCK, interpret: bool | None = None):
    """q: (B, Tc, H, Dh), the chunk's queries at positions
    ``[start, start + Tc)``; caches: (B, S, Hkv, Dh), or (L, B, S, Hkv,
    Dh) with ``layer`` naming the one to read; start, layer: () int32,
    traced.  Returns (B, Tc, H, Dh), equal to ``full_attention(q, k, v,
    causal=True, q_offset=start)`` over that layer's cache.

    ``interpret`` defaults to the platform rule
    (``kernels.interpret_mode``)."""
    if k_cache.ndim == 4:
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    B, Tc, H, Dh = q.shape
    S, Hkv = k_cache.shape[2], k_cache.shape[3]
    G = H // Hkv
    blk_k = min(blk_k, S)
    n_kv = -(-S // blk_k)
    rows = G * Tc
    # (B, Tc, Hkv, G, Dh) -> (B, Hkv, G * Tc, Dh): a kv head's q tile
    qt = q.reshape(B, Tc, Hkv, G, Dh).transpose(0, 2, 3, 1, 4).reshape(
        B, Hkv, rows, Dh)
    idx = jnp.stack([jnp.asarray(start, jnp.int32),
                     jnp.asarray(layer, jnp.int32)])

    def kv_map(b, ki, idx):
        last = (idx[0] + Tc - 1) // blk_k       # the last live block
        return idx[1], b, jnp.minimum(ki, last), 0, 0

    kv_spec = pl.BlockSpec((1, 1, blk_k, Hkv, Dh), kv_map)
    q_spec = pl.BlockSpec((1, Hkv, rows, Dh), lambda b, ki, idx: (b, 0, 0, 0))
    kern = functools.partial(_kernel, tc=Tc, blk_k=blk_k, n_kv=n_kv,
                             scale=Dh ** -0.5)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,              # (start, layer)
            grid=(B, n_kv),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((Hkv, rows, 1), jnp.float32),
                            pltpu.VMEM((Hkv, rows, 1), jnp.float32),
                            pltpu.VMEM((Hkv, rows, Dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret_mode(interpret),
    )(idx, qt, k_cache, v_cache)
    return out.reshape(B, Hkv, G, Tc, Dh).transpose(0, 3, 1, 2, 4).reshape(
        B, Tc, H, Dh)
