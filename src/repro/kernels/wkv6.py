"""Pallas TPU kernel: RWKV6 WKV recurrence, time-blocked.

The WKV scan is inherently sequential in time (data-dependent decay), so
the TPU win is not parallelism-over-time but *state residency*: the
(hs x hs) per-head state matrix stays in VMEM scratch across the whole
sequence while r/k/v/w stream through in time blocks (one HBM read each,
no state round-trips — a lax.scan materializes the carry through HBM
between steps).  Inside a block a fori_loop walks aligned slabs of time
steps and applies the rank-1 updates to the VMEM-resident state row by
row, with only 2-D row/column operations that the TPU lowering accepts.

A chunked matmul formulation (process blk_t steps as one MXU contraction
using cumulative-decay ratios) is the classic GPU approach; its decay
ratios ``exp(cum[t]-cum[s])`` overflow f32 for strongly-decaying
channels, so we keep the numerically exact sequential-in-block form and
note the chunked variant as future work (EXPERIMENTS.md §Perf).

Grid: (B, H, n_tblocks) — time innermost (sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
            state_ref, *, blk_t: int, n_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                      # (1, hs)
    hs = u.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (hs, hs), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hs, hs), 1))

    def col(row):
        """(1, hs) row -> (hs, 1) column by a masked lane reduction (a
        layout change the TPU lowering supports for any hs)."""
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    # time steps go in aligned slabs of ``sub`` rows: the TPU lowering
    # loads and stores whole (8k, hs) tiles at a dynamic offset, and
    # single rows are split off the loaded value with static slices
    sub = next((n for n in (16, 8) if blk_t % n == 0), blk_t)
    rows = jax.lax.broadcasted_iota(jnp.int32, (sub, hs), 0)

    def slab(t0, _):
        at = pl.ds(pl.multiple_of(t0 * sub, sub), sub)
        r = r_ref[0, 0, at].astype(jnp.float32)           # (sub, hs)
        k = k_ref[0, 0, at].astype(jnp.float32)
        v = v_ref[0, 0, at].astype(jnp.float32)
        w = w_ref[0, 0, at].astype(jnp.float32)
        s = state_ref[...]                                # (hs, hs) [k, v]
        y = jnp.zeros((sub, hs), jnp.float32)
        for i in range(sub):
            rt, kt, vt, wt = (x[i:i + 1] for x in (r, k, v, w))  # (1, hs)
            # y_t = r @ (s + diag(u k) 1 v^T) = r @ s + (r . u k) v
            yt = jax.lax.dot_general(rt, s, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            yt = yt + jnp.sum(rt * u * kt, axis=1, keepdims=True) * vt
            y = jnp.where(rows == i, yt, y)
            s = col(wt) * s + col(kt) * vt
        state_ref[...] = s
        y_ref[0, 0, at] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, blk_t // sub, slab, 0)

    @pl.when(ti == n_t - 1)
    def _fin():
        sT_ref[0, 0] = state_ref[...].astype(sT_ref.dtype)


def wkv6(r, k, v, w, u, s0, *, blk_t: int = 64,
         interpret: bool | None = None):
    """r,k,v,w: (B,T,H,hs); u: (H,hs); s0: (B,H,hs,hs).

    Returns (y (B,T,H,hs) f32, sT (B,H,hs,hs) f32).  Padding: callers mask
    w=1, k=0 on padded steps (identity update) — see models/rwkv.py.
    """
    B, T, H, hs = r.shape
    blk_t = min(blk_t, T)
    pad_t = (-T) % blk_t
    rt, kt, vt, wt = (jnp.moveaxis(x, (1, 2), (2, 1)) for x in (r, k, v, w))
    if pad_t:
        # identity updates on padding: w=1, k=0 -> state untouched
        rt = jnp.pad(rt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        wt = jnp.pad(wt, ((0, 0), (0, 0), (0, pad_t), (0, 0)),
                     constant_values=1.0)
    n_t = rt.shape[2] // blk_t

    kern = functools.partial(_kernel, blk_t=blk_t, n_t=n_t)
    y, sT = pl.pallas_call(
        kern,
        grid=(B, H, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, blk_t, hs), lambda b, h, ti: (b, h, ti, 0)),
            pl.BlockSpec((1, 1, blk_t, hs), lambda b, h, ti: (b, h, ti, 0)),
            pl.BlockSpec((1, 1, blk_t, hs), lambda b, h, ti: (b, h, ti, 0)),
            pl.BlockSpec((1, 1, blk_t, hs), lambda b, h, ti: (b, h, ti, 0)),
            # u as (H, 1, hs): a (1, 1, hs) block spans the array's two
            # minor dims, which the TPU lowering requires
            pl.BlockSpec((1, 1, hs), lambda b, h, ti: (h, 0, 0)),
            pl.BlockSpec((1, 1, hs, hs), lambda b, h, ti: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk_t, hs), lambda b, h, ti: (b, h, ti, 0)),
            pl.BlockSpec((1, 1, hs, hs), lambda b, h, ti: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n_t * blk_t, hs), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hs, hs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(rt, kt, vt, wt, u.reshape(H, 1, hs), s0)
    y = y[:, :, :T] if pad_t else y
    return jnp.moveaxis(y, (1, 2), (2, 1)), sT
