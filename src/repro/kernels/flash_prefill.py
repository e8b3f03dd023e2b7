"""Pallas TPU kernel: causal/windowed FlashAttention for prefill.

TPU adaptation (DESIGN.md §3): instead of a CUDA warp-tiled kernel we
block HBM->VMEM transfers with ``BlockSpec`` and keep the running-softmax
statistics (m, l) and the output accumulator in VMEM scratch across the
sequential innermost grid dimension (TPU grids iterate minor-to-major on
a single core, so scratch persists across the kv-block loop).  Matmul
dims are multiples of 128 so both score and value products hit the MXU.

VMEM working set per grid step (defaults blk_q = blk_k = 128, Dh <= 256):
    q tile        128 x 256 x 4B = 128 KiB
    k,v tiles   2 x 128 x 256 x 4B = 256 KiB
    acc + stats  128 x 256 x 4B + 2 x 128 x 4B ~= 129 KiB
  ~= 0.5 MiB << 16 MiB VMEM  ->  plenty of room for double buffering.

Grid: (B, H, n_qblocks, n_kvblocks); GQA is handled by indexing the kv
head ``h // group`` in the k/v BlockSpecs.  Causally dead (q,kv) blocks
are skipped with ``pl.when`` (zero compute, still iterated).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

NEG_INF = -1e30


def _kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            blk_q: int, blk_k: int, n_kv: int, causal: bool, window: int,
            scale: float):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * blk_q
    k_start = ki * blk_k
    live = True
    if causal:
        live = k_start <= q_start + blk_q - 1
    if window:
        live = jnp.logical_and(live, q_start - (k_start + blk_k - 1) < window)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # (blk_q, Dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (blk_k, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = kpos < lens_ref[b]
        if causal:
            ok = jnp.logical_and(ok, qpos >= kpos)
        if window:
            ok = jnp.logical_and(ok, qpos - kpos < window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_prefill(q, k, v, lengths=None, *, causal: bool = True,
                  window: int = 0, blk_q: int = 128, blk_k: int = 128,
                  interpret: bool | None = None):
    """q: (B,T,H,Dh); k,v: (B,T,Hkv,Dh); lengths: (B,) valid key counts.

    Returns (B,T,H,Dh).  ``interpret`` defaults to the platform rule
    (``kernels.interpret_mode``): the Pallas interpreter on the CPU
    backend, the compiled kernel on the TPU.
    """
    B, T, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    blk_q = min(blk_q, T)
    blk_k = min(blk_k, T)
    pad_q = (-T) % blk_q
    pad_k = (-T) % blk_k
    qt = jnp.moveaxis(q, 2, 1)                      # (B,H,T,Dh)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = qt.shape[2] // blk_q
    nk = kt.shape[2] // blk_k

    kern = functools.partial(
        _kernel, blk_q=blk_q, blk_k=blk_k, n_kv=nk, causal=causal,
        window=window, scale=Dh ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # valid key counts
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, Dh),
                         lambda b, h, qi, ki, lens: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, Dh),
                         lambda b, h, qi, ki, lens, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, Dh),
                         lambda b, h, qi, ki, lens, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, Dh),
                               lambda b, h, qi, ki, lens: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret_mode(interpret),
    )(lengths.astype(jnp.int32), qt, kt, vt)
    out = out[:, :, :T] if pad_q else out
    return jnp.moveaxis(out, 1, 2)
