"""Pallas TPU kernel: GQA flash-decode over a PAGED KV cache.

Same bandwidth-bound problem as ``decode_attn.flash_decode`` — one query
token streams the whole KV cache HBM->VMEM — but the cache is no longer
a contiguous (B, S, Hkv, Dh) tensor.  It is a shared page POOL
(n_pages, page_size, Hkv, Dh) plus a per-request block table
(B, pages_per_seq): virtual slot ``s`` of request ``b`` lives in page
``block_tables[b, s // page_size]`` at offset ``s % page_size``
(DESIGN.md §3).

The indirection is done with SCALAR PREFETCH: the block table and the
per-request positions are ``PrefetchScalarGridSpec`` operands, so the
k/v BlockSpec index maps read ``bt[b, j]`` and DMA exactly the page the
(b, j) grid step needs — the pool is never gathered into a contiguous
cache in HBM.  Everything else mirrors the contiguous kernel: grid
(B, n_pages_per_seq) with the page axis innermost/sequential, one page
of every kv head per step, all G query heads of one kv head processed
together, running-softmax stats in VMEM scratch.

Ring/sliding-window validity is preserved: position ``p`` lives at
virtual slot ``p % s_len`` and slot ``s`` is valid iff
``s <= pos or pos >= s_len`` — softmax is permutation-invariant, so
neither ring order nor PAGE order matters (models/attention.py).
Virtual slots past ``s_len`` (the partially-dead last page of a
non-divisible cache length) are masked exactly like sequence padding in
the contiguous kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

from .decode_attn import attend_kv_block, init_stats, stat_scratch, write_out


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, page: int, n_p: int, s_len: int, ring: bool,
            scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_stats(m_ref, l_ref, acc_ref)

    attend_kv_block(pos_ref[pl.program_id(0)], q_ref, k_ref, v_ref, m_ref,
                    l_ref, acc_ref, base=j * page, s_len=s_len, ring=ring,
                    scale=scale)

    @pl.when(j == n_p - 1)
    def _fin():
        write_out(o_ref, l_ref, acc_ref)


def paged_flash_decode(q, k_pool, v_pool, block_tables, pos, *,
                       s_len: int, ring: bool = False,
                       interpret: bool | None = None):
    """q: (B,1,H,Dh) or (B,H,Dh); pools: (n_pages, page, Hkv, Dh);
    block_tables: (B, pages_per_seq) int32; pos: (B,).

    ``s_len`` is the request-level cache length (validity bound and ring
    modulus) — at most ``pages_per_seq * page``; the slack is the dead
    tail of the last page.  Returns the same shape as ``q``.
    """
    squeeze = q.ndim == 4
    if q.ndim == 4:
        q = q[:, 0]
    B, H, Dh = q.shape
    n_pages, page, Hkv = k_pool.shape[:3]
    G = H // Hkv
    n_p = block_tables.shape[1]
    assert s_len <= n_p * page, (s_len, n_p, page)
    qg = q.reshape(B, Hkv, G, Dh)
    # unallocated table tail entries may be garbage: valid-slot masking
    # hides their values, but the index map must still be in range
    bt = jnp.clip(block_tables.astype(jnp.int32), 0, n_pages - 1)

    kern = functools.partial(_kernel, page=page, n_p=n_p, s_len=s_len,
                             ring=ring, scale=Dh ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # block tables + positions
        grid=(B, n_p),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), lambda b, j, bt, pos: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, Dh),
                         lambda b, j, bt, pos: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, Dh),
                         lambda b, j, bt, pos: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, Dh),
                               lambda b, j, bt, pos: (b, 0, 0, 0)),
        scratch_shapes=stat_scratch(Hkv, G, Dh),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        interpret=interpret_mode(interpret),
    )(bt, pos.astype(jnp.int32), qg, k_pool, v_pool)
    out = out.reshape(B, H, Dh)
    return out[:, None] if squeeze else out
