"""Pallas TPU kernel: GQA flash-decode (one query token vs. KV cache).

Decode attention is memory-bound: the whole KV cache streams HBM->VMEM
once per step while compute is tiny.  The kernel therefore optimizes for
bandwidth: the cache is blocked along the sequence axis (innermost,
sequential grid dim), all G query heads of one kv head are processed
together (amortizing each K/V tile across G score rows — a GQA-specific
arithmetic-intensity win: bytes/token drop by G vs. per-head kernels),
and running softmax stats live in VMEM scratch.

Supports ring-buffer (sliding-window) caches: validity of slot ``s`` is
``s <= pos  or  pos >= S`` — softmax is permutation-invariant so ring
order never matters (see models/attention.py).

Grid: (B, n_sblocks); each step covers every kv head of one sequence
block, and the positions are scalar-prefetched into SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

NEG_INF = -1e30


def attend_kv_block(pos, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                    base, s_len: int, ring: bool, scale: float):
    """One online-softmax step of every kv head over one (blk, Hkv, Dh)
    K/V block whose first slot is ``base``.  The block spans all kv
    heads (the TPU lowering needs a block's two minor dims to be whole
    or (8, 128)-aligned, and Hkv rarely is); each head's (blk, Dh) slice
    is read out of it in turn.  Shared with the paged kernel."""
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32)               # (G, Dh)
        k = k_ref[0, :, h, :].astype(jnp.float32)         # (blk, Dh)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        slot = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = slot <= pos
        if ring:
            valid = jnp.logical_or(valid, pos >= s_len)
        valid = jnp.logical_and(valid, slot < s_len)    # padding slots dead
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[h]                                 # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def init_stats(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def write_out(o_ref, l_ref, acc_ref):
    for h in range(acc_ref.shape[0]):
        l = jnp.maximum(l_ref[h], 1e-30)
        o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


def stat_scratch(Hkv: int, G: int, Dh: int):
    """Running max, running sum and output accumulator, per kv head."""
    return [pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, Dh), jnp.float32)]


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            blk_s: int, n_s: int, s_orig: int, ring: bool, scale: float):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        init_stats(m_ref, l_ref, acc_ref)

    attend_kv_block(pos_ref[pl.program_id(0)], q_ref, k_ref, v_ref, m_ref,
                    l_ref, acc_ref, base=si * blk_s, s_len=s_orig,
                    ring=ring, scale=scale)

    @pl.when(si == n_s - 1)
    def _fin():
        write_out(o_ref, l_ref, acc_ref)


def flash_decode(q, k_cache, v_cache, pos, *, ring: bool = False,
                 blk_s: int = 512, interpret: bool | None = None):
    """q: (B,1,H,Dh) or (B,H,Dh); caches: (B,S,Hkv,Dh); pos: (B,).

    Returns (B,1,H,Dh).  ``ring=True`` for sliding-window ring caches.
    """
    squeeze = q.ndim == 4
    if q.ndim == 4:
        q = q[:, 0]
    B, H, Dh = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    blk_s = min(blk_s, S)
    pad_s = (-S) % blk_s
    # The cache is consumed in its NATIVE (B, S, Hkv, Dh) layout — the
    # BlockSpec index map picks (b, si, h) tiles directly, so no transpose
    # of the multi-GiB cache ever materializes (§Perf iteration 3: a
    # relayout was measured 2.4x worse; tiling beats relayout).
    if pad_s:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    n_s = k_cache.shape[1] // blk_s
    qg = q.reshape(B, Hkv, G, Dh)

    kern = functools.partial(_kernel, blk_s=blk_s, n_s=n_s, s_orig=S,
                             ring=ring, scale=Dh ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # positions
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), lambda b, si, pos: (b, 0, 0, 0)),
            pl.BlockSpec((1, blk_s, Hkv, Dh),
                         lambda b, si, pos: (b, si, 0, 0)),
            pl.BlockSpec((1, blk_s, Hkv, Dh),
                         lambda b, si, pos: (b, si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, Dh),
                               lambda b, si, pos: (b, 0, 0, 0)),
        scratch_shapes=stat_scratch(Hkv, G, Dh),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        interpret=interpret_mode(interpret),
    )(pos.astype(jnp.int32), qg, k_cache, v_cache)
    out = out.reshape(B, H, Dh)
    return out[:, None] if squeeze else out
