"""Attention: GQA self-attention, cross-attention, decode-with-cache.

Three memory regimes:

* ``full_attention``        — materializes (B,H,T,T) scores; short T only.
* ``blocked_attention``     — exact causal/windowed flash-style attention in
  pure jnp: a lax.scan over the *statically enumerated* lower-triangular
  (q-block, kv-block) pair list with online softmax.  Memory is
  O(blk²·B·H); FLOPs match the causal optimum (no masked-out block is ever
  computed).  This is the reference the Pallas ``flash_prefill`` kernel is
  checked against, and the fallback path on CPU.
* ``decode_attention``      — one query token vs. a (possibly ring-buffer)
  KV cache.

Sliding-window caches are rings: position ``p`` lives at slot ``p % W``;
softmax is permutation-invariant so slot order inside the cache never
matters once RoPE is applied at write time.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import layers
from .config import ModelConfig
from repro.kernels import chunk_attn

NEG_INF = -1e30

# Activation pins (see repro.sharding.context): without them GSPMD
# T-shards q/k/v and ALL-GATHERS the full tensors on every blocked-
# attention pair step (measured 252 TB/device on llama-3.2-vision-90b
# prefill_32k — EXPERIMENTS.md §Perf iteration 1).
from repro.sharding import context as shctx


def set_mesh(mesh):   # kept for the dryrun API
    shctx.set_mesh(mesh)


def _pin_heads(x):
    return shctx.pin_heads(x)


# ------------------------------------------------------------------ math --
def _gqa_scores(q, k):
    """q: (B,Tq,H,Dh), k: (B,Tk,Hkv,Dh) -> (B,Hkv,G,Tq,Tk) f32."""
    B, Tq, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Tq, Hkv, G, Dh).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    return jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf) * (Dh ** -0.5)


def _gqa_out(p, v):
    """p: (B,Hkv,G,Tq,Tk) f32, v: (B,Tk,Hkv,Dh) -> (B,Tq,H,Dh)."""
    B, Hkv, G, Tq, Tk = p.shape
    Dh = v.shape[-1]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Tq, Hkv * G, Dh)


def full_attention(q, k, v, *, causal: bool, lengths=None, window: int = 0,
                   q_offset=0):
    """Reference attention, O(T²) memory. q_offset: position of q[0]."""
    B, Tq, H, Dh = q.shape
    Tk = k.shape[1]
    s = _gqa_scores(q, k)                              # (B,Hkv,G,Tq,Tk)
    qpos = jnp.arange(Tq) + q_offset
    kpos = jnp.arange(Tk)
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    bias = jnp.where(mask, 0.0, NEG_INF)[None, None, None]
    if lengths is not None:
        kvalid = kpos[None, :] < lengths[:, None]      # (B,Tk)
        bias = bias + jnp.where(kvalid, 0.0, NEG_INF)[:, None, None, None, :]
    p = jax.nn.softmax(s + bias, axis=-1)
    return _gqa_out(p, v).astype(q.dtype)


# -------------------------------------------------- int8 KV quantization --
def quantize_kv(x):
    """Symmetric per-(token, head) int8: x (..., Dh) -> (q int8, scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=False) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of quantize_kv; on target this happens in the decode
    kernel's VMEM registers (HBM traffic stays int8)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ------------------------------------------- int4 spill-tier compression --
# Host-side (numpy) helpers for the SPILL tier (DESIGN.md §3 "Tier
# precision"): pages crossing the host link may be packed two int4
# values per byte with per-(token, head) f32 scales.  These run on the
# host around the PCIe copy — never inside a jitted computation — so
# they are numpy, not jnp.

def pack_int4(q) -> np.ndarray:
    """Pack int8 values in [-8, 7] two-per-byte along the LAST axis.
    An odd tail is zero-padded — ``unpack_int4(p, n)`` restores the
    exact original length."""
    q = np.asarray(q, np.int8)
    if q.shape[-1] % 2:
        q = np.concatenate(
            [q, np.zeros(q.shape[:-1] + (1,), np.int8)], axis=-1)
    u = (q.astype(np.int16) & 0xF).astype(np.uint8)
    return (u[..., 0::2] | (u[..., 1::2] << 4)).astype(np.uint8)


def unpack_int4(p, n: int) -> np.ndarray:
    """Inverse of :func:`pack_int4`: bytes -> int8 values, trimmed to
    the original last-axis length ``n``."""
    p = np.asarray(p, np.uint8)
    assert n <= 2 * p.shape[-1], (n, p.shape)
    lo = (p & 0xF).astype(np.int16)
    hi = (p >> 4).astype(np.int16)
    out = np.empty(p.shape[:-1] + (2 * p.shape[-1],), np.int16)
    out[..., 0::2] = lo
    out[..., 1::2] = hi
    out = np.where(out >= 8, out - 16, out).astype(np.int8)
    return out[..., :n]


def quantize_kv_int4(x):
    """Symmetric per-(token, head) int4 for spilled pages:
    x (..., Dh) float -> (packed uint8 (..., ceil(Dh/2)), scale f32).
    Mirrors :func:`quantize_kv` with a 4-bit grid (limit 7)."""
    x = np.asarray(x, np.float32)
    scale = np.abs(x).max(axis=-1) / 7.0
    scale = np.maximum(scale, 1e-8).astype(np.float32)
    q = np.clip(np.rint(x / scale[..., None]), -7, 7).astype(np.int8)
    return pack_int4(q), scale


def dequantize_kv_int4(packed, scale, n: int, dtype=np.float32):
    """Inverse of :func:`quantize_kv_int4` (``n`` = original Dh)."""
    q = unpack_int4(packed, n)
    return (q.astype(np.float32) * scale[..., None]).astype(dtype)


# ------------------------------------------------- blocked causal (jnp) ---
def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def blocked_attention(q, k, v, *, causal: bool = True, lengths=None,
                      window: int = 0, blk: int = 512):
    """Exact flash-style attention; scans only live (qb,kb) block pairs."""
    B, T, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    blk = max(1, min(blk, T))
    nb = -(-T // blk)
    Tp = nb * blk
    q, k, v = (_pad_to(x, Tp, 1) for x in (q, k, v))

    wb = -(-window // blk) if window else nb           # kv-block reach
    pairs = [(qb, kb) for qb in range(nb) for kb in range(nb)
             if (kb <= qb if causal else True)
             and (qb - kb <= wb if window else True)]
    qb_idx = jnp.array([p[0] for p in pairs], jnp.int32)
    kb_idx = jnp.array([p[1] for p in pairs], jnp.int32)

    qg = q.reshape(B, Tp, Hkv, G, Dh)
    acc = jnp.zeros((nb, B, Hkv, G, blk, Dh), jnp.float32)
    m = jnp.full((nb, B, Hkv, G, blk, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((nb, B, Hkv, G, blk, 1), jnp.float32)
    scale = Dh ** -0.5
    kpos_all = jnp.arange(blk)

    def body(carry, pair):
        acc, m, l = carry
        qb, kb = pair
        qblk = jax.lax.dynamic_slice_in_dim(qg, qb * blk, blk, 1)
        kblk = jax.lax.dynamic_slice_in_dim(k, kb * blk, blk, 1)
        vblk = jax.lax.dynamic_slice_in_dim(v, kb * blk, blk, 1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk.astype(jnp.float32),
                       kblk.astype(jnp.float32)) * scale
        qpos = qb * blk + kpos_all
        kpos = kb * blk + kpos_all
        ok = jnp.ones((blk, blk), bool)
        if causal:
            ok &= qpos[:, None] >= kpos[None, :]
        if window:
            ok &= qpos[:, None] - kpos[None, :] < window
        bias = jnp.where(ok, 0.0, NEG_INF)[None, None, None]
        if lengths is not None:
            kvalid = kpos[None, :] < lengths[:, None]
            bias = bias + jnp.where(kvalid, 0., NEG_INF)[:, None, None, None, :]
        else:
            kvalid_pad = kpos[None, :] < T
            bias = bias + jnp.where(kvalid_pad, 0., NEG_INF)[None, None, None]
        s = s + bias

        m_old = jax.lax.dynamic_index_in_dim(m, qb, 0, keepdims=False)
        l_old = jax.lax.dynamic_index_in_dim(l, qb, 0, keepdims=False)
        a_old = jax.lax.dynamic_index_in_dim(acc, qb, 0, keepdims=False)
        m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_old * alpha + p.sum(-1, keepdims=True)
        a_new = a_old * alpha + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vblk.astype(jnp.float32))
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qb, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qb, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qb, 0)
        return (acc, m, l), None

    # `vmem_fused:` scope: these intermediates correspond 1:1 to the
    # Pallas flash_prefill kernel's VMEM-resident tiles (validated in
    # tests/test_kernels.py); the roofline parser can model them as fused
    # (hlo_analysis.module_stats(fused_kernels=True)).
    with jax.named_scope("vmem_fused:flash_prefill"):
        (acc, m, l), _ = jax.lax.scan(body, (acc, m, l), (qb_idx, kb_idx))
        out = acc / jnp.maximum(l, 1e-30)
    out = jnp.moveaxis(out, 0, 1).reshape(B, nb, Hkv, G, blk, Dh)
    out = jnp.moveaxis(out, -2, 2).reshape(B, Tp, Hkv * G, Dh)
    return out[:, :T].astype(q.dtype)


# ----------------------------------------------------------------- decode --
# NOTE (§Perf iteration 3, REFUTED): a decode-native (B,Hkv,S,Dh) cache
# layout was hypothesized to remove per-layer transpose+copy pairs; it
# measured 2.4x WORSE (the mid-axis scatter of the token update costs
# more than the transposes it saves).  Reverted to (B,S,Hkv,Dh).
def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0):
    """q: (B,1,H,Dh); caches: (B,S,Hkv,Dh); pos: (B,) index of the NEW token
    (already written into the cache)."""
    B, _, H, Dh = q.shape
    S = k_cache.shape[1]
    # maps to the Pallas flash_decode kernel (kernels/decode_attn.py)
    with jax.named_scope("vmem_fused:flash_decode"):
        s = _gqa_scores(q, k_cache)                    # (B,Hkv,G,1,S)
        slot = jnp.arange(S)
        if window:
            # ring cache: slot s holds position pos - ((pos-s) mod S);
            # valid once pos >= S-1, else only slots <= pos.
            valid = (slot[None] <= pos[:, None]) | (pos[:, None] >= S)
        else:
            valid = slot[None] <= pos[:, None]         # (B,S)
        s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, None, None, :]
        p = jax.nn.softmax(s, axis=-1)
        out = _gqa_out(p, v_cache)
    return out.astype(q.dtype)


# ------------------------------------------------------------- paged ------
def gather_paged_kv(pool, block_tables, s_len: int, layer=None):
    """Reassemble per-request caches from a shared page pool.

    pool: (n_pages, page, ...) — K, V, or int8 scale pool — or, with
    ``layer``, every layer's pool (layers, n_pages, page, ...), of which
    one gather reads layer ``layer``'s pages;
    block_tables: (B, pages_per_seq) int32; returns (B, s_len, ...).
    Virtual slot ``s`` of request ``b`` is page ``block_tables[b, s //
    page]`` offset ``s % page`` (DESIGN.md §3).  The gather reconstructs
    the EXACT contiguous layout, so downstream attention is bit-identical
    to the contiguous cache path — page placement cannot change results.
    """
    g = pool[block_tables] if layer is None else pool[layer, block_tables]
    B, n_p, page = g.shape[:3]                   # (B, n_p, page, ...)
    g = g.reshape((B, n_p * page) + g.shape[3:])
    return g[:, :s_len]


def paged_decode_attention(q, k_pool, v_pool, block_tables, pos, *,
                           s_len: int, window: int = 0, layer=None):
    """Decode attention over a paged KV cache (jnp oracle for the Pallas
    ``kernels/paged_decode_attn.py`` kernel): gather pages back into the
    contiguous layout, then run ``decode_attention`` unchanged.  Softmax
    permutation-invariance is what makes page order irrelevant.
    ``layer`` as in ``gather_paged_kv``."""
    k_cache = gather_paged_kv(k_pool, block_tables, s_len, layer)
    v_cache = gather_paged_kv(v_pool, block_tables, s_len, layer)
    return decode_attention(q, k_cache, v_cache, pos, window=window)


def self_attn_decode_paged(cfg: ModelConfig, p, x, pos, cache, block_tables,
                           *, layer, page_size: int, s_len: int,
                           window: int = 0):
    """One-token decode against the shared page pool.  Mirrors
    ``self_attn_decode`` except the new token's K/V scatter indirects
    through the block table: virtual slot ``pos`` (``pos % s_len`` for
    ring caches) lands in page ``block_tables[b, slot // page]`` offset
    ``slot % page``.  int8 caches are 4-tuples with scale pools.

    The pool leaves hold every layer of the group, (reps, n_pages, page,
    ...): the step writes the token into layer ``layer`` with one scatter
    and gathers that layer's pages, so a pool carried through the layer
    scan is updated in place and never restacked."""
    B = x.shape[0]
    quant = cfg.kv_cache_dtype == "int8"
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    cos, sin = layers.rope_angles(pos[:, None], cfg.d_head, cfg.rope_theta)
    q = layers.apply_rope(q, cos, sin)
    k = layers.apply_rope(k, cos, sin)
    if quant:
        k_pool, v_pool, k_s, v_s = cache
        kq, ks_new = quantize_kv(k[:, 0])
        vq, vs_new = quantize_kv(v[:, 0])
    else:
        k_pool, v_pool = cache
        kq, vq = k[:, 0], v[:, 0]
    n_p = block_tables.shape[1]
    slot = (pos % s_len) if window else pos
    # dead slots walk pos past their table; clip keeps the (masked)
    # write in range — their tables point at the trash page anyway
    entry = jnp.take_along_axis(
        block_tables, jnp.clip(slot // page_size, 0, n_p - 1)[:, None],
        axis=1)[:, 0]                                          # (B,)
    at = (layer, entry, slot % page_size)
    k_pool = k_pool.at[at].set(kq)
    v_pool = v_pool.at[at].set(vq)
    if quant:
        k_s = k_s.at[at].set(ks_new)
        v_s = v_s.at[at].set(vs_new)
        with jax.named_scope("vmem_fused:paged_flash_decode_int8"):
            kd = dequantize_kv(
                gather_paged_kv(k_pool, block_tables, s_len, layer),
                gather_paged_kv(k_s, block_tables, s_len, layer), q.dtype)
            vd = dequantize_kv(
                gather_paged_kv(v_pool, block_tables, s_len, layer),
                gather_paged_kv(v_s, block_tables, s_len, layer), q.dtype)
        with jax.named_scope("vmem_fused:paged_flash_decode"):
            out = decode_attention(q, kd, vd, pos, window=window)
    else:
        # maps to the Pallas paged kernel (kernels/paged_decode_attn.py)
        with jax.named_scope("vmem_fused:paged_flash_decode"):
            out = paged_decode_attention(q, k_pool, v_pool, block_tables,
                                         pos, s_len=s_len, window=window,
                                         layer=layer)
    out = out.reshape(B, 1, cfg.q_dim) @ p["wo"]
    new_cache = (k_pool, v_pool, k_s, v_s) if quant else (k_pool, v_pool)
    return out, new_cache


# ------------------------------------------------------------ sublayers ---
def attn_init(key, cfg: ModelConfig, dtype, cross: bool = False):
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    kv_src = cfg.d_model  # vision embeds are projected to d_model first
    p = {
        "wq": layers.dense_init(ks[0], d, cfg.q_dim, dtype),
        "wk": layers.dense_init(ks[1], kv_src, cfg.kv_dim, dtype),
        "wv": layers.dense_init(ks[2], kv_src, cfg.kv_dim, dtype),
        "wo": layers.dense_init(ks[3], cfg.q_dim, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.d_head,), dtype)
        p["k_norm"] = jnp.zeros((cfg.d_head,), dtype)
    if cross:
        p["gate"] = jnp.zeros((), dtype)  # tanh-gated cross-attn (llama3.2v)
    return p


def _project_q(cfg, p, x):
    B, T, _ = x.shape
    q = (x @ p["wq"]).reshape(B, T, cfg.n_heads, cfg.d_head)
    if cfg.qk_norm:
        q = layers.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
    return q


def _project_kv(cfg, p, x):
    B, T, _ = x.shape
    k = (x @ p["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    v = (x @ p["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        k = layers.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v


def self_attn_forward(cfg: ModelConfig, p, x, positions, lengths=None, *,
                      window: int = 0, make_cache: bool = False,
                      cache_len: int = 0):
    """Full-sequence self-attention (train / encoder / prefill).

    positions: (T,) or (B,T) absolute positions for RoPE.
    Returns (out, cache|None); cache K/V hold RoPE'd keys.  With window>0
    the cache is a ring of size min(cache_len or window, window).
    """
    B, T, _ = x.shape
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    cos, sin = layers.rope_angles(
        positions if positions.ndim == 2 else positions[None].repeat(B, 0),
        cfg.d_head, cfg.rope_theta)
    q = layers.apply_rope(q, cos, sin)
    k = layers.apply_rope(k, cos, sin)

    if T <= 1024:
        out = full_attention(q, k, v, causal=cfg.causal, lengths=lengths,
                             window=window)
    else:
        qa, ka, va = q, k, v
        # §Perf 1: the head pin fixes the prefill T-sharding pathology
        # (463x collective cut) but measured 1.8x WORSE collectives when
        # applied to the TRAINING forward (backward through the expanded
        # KV adds all-reduces) — prefill only.
        if make_cache and shctx.get_mesh() is not None \
                and cfg.n_heads > cfg.n_kv_heads:
            # expand KV to full heads so the head dim divides the model
            # axis, then pin everything head-sharded: every blocked-
            # attention slice is shard-local (no per-step all-gathers).
            G = cfg.n_heads // cfg.n_kv_heads
            ka = jnp.repeat(k, G, axis=2)
            va = jnp.repeat(v, G, axis=2)
        qa = _pin_heads(qa)
        ka = _pin_heads(ka)
        va = _pin_heads(va)
        out = blocked_attention(qa, ka, va, causal=cfg.causal,
                                lengths=lengths, window=window)
    out = out.reshape(B, T, cfg.q_dim) @ p["wo"]

    cache = None
    if make_cache:
        if window and window < (cache_len or T):
            kr, vr = _ring_from_prefill(k, v, lengths, window)
        else:
            S = cache_len or T
            kr = _pad_to(k, S, 1)[:, :S]
            vr = _pad_to(v, S, 1)[:, :S]
        if cfg.kv_cache_dtype == "int8":
            kq, ks = quantize_kv(kr)
            vq, vs = quantize_kv(vr)
            cache = (kq, vq, ks, vs)
        else:
            cache = (kr, vr)
    return out, cache


def _ring_from_prefill(k, v, lengths, W):
    """Gather the last W live positions of each sequence into ring layout."""
    B, T = k.shape[:2]
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    last = lengths[:, None] - 1                                  # (B,1)
    slots = jnp.arange(W)[None]                                  # (1,W)
    src = last - ((last - slots) % W)                            # position at slot
    valid = src >= jnp.maximum(0, lengths[:, None] - W)
    src_c = jnp.clip(src, 0, T - 1)
    kr = jnp.take_along_axis(k, src_c[..., None, None], axis=1)
    vr = jnp.take_along_axis(v, src_c[..., None, None], axis=1)
    kr = jnp.where(valid[..., None, None], kr, 0)
    vr = jnp.where(valid[..., None, None], vr, 0)
    return kr, vr


def uses_chunk_kernel(cfg: ModelConfig, dtype) -> bool:
    """Whether a chunk step's attention runs the Pallas kernel
    (``kernels/chunk_attn.py``): on the TPU, over a bf16 cache, with each
    device holding whole groups of KV heads (no mesh, or the heads split
    evenly over ``model``).  Elsewhere ``full_attention`` runs: on the
    CPU, where it is the oracle, and for int8 caches, which it reads
    dequantized."""
    if jax.default_backend() != "tpu" or cfg.kv_cache_dtype == "int8" \
            or dtype != jnp.bfloat16:
        return False
    mesh = shctx.get_mesh()
    return mesh is None or ("model" in mesh.axis_names
                            and cfg.n_kv_heads % mesh.shape["model"] == 0)


def chunk_block(cfg: ModelConfig, s_len: int) -> int:
    """Cache slots per K/V block of the chunk kernel for ``cfg``."""
    return chunk_attn.block_len(cfg.n_kv_heads, cfg.d_head, s_len)


def _chunk_kernel(cfg, q, k_cache, v_cache, start, layer):
    """The chunk kernel over the stacked caches, under a registered mesh
    once per device on its own KV heads: query head h reads KV head
    h // G and both are split in contiguous blocks, so each device's
    query heads and KV heads form whole groups."""
    def attend(q, k, v, start, layer):
        return chunk_attn.chunk_attention(
            q, k, v, start, layer=layer,
            blk_k=chunk_block(cfg, k_cache.shape[2]))
    mesh = shctx.get_mesh()
    if mesh is None:
        return attend(q, k_cache, v_cache, start, layer)
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import batch_axes
    baxes = batch_axes(mesh, q.shape[0])
    heads = P(baxes, None, "model", None)
    cache = P(None, baxes, None, "model", None)
    return jax.shard_map(
        attend, mesh=mesh, in_specs=(heads, cache, cache, P(), P()),
        out_specs=heads, check_vma=False,
    )(q, k_cache, v_cache, jnp.asarray(start, jnp.int32),
      jnp.asarray(layer, jnp.int32))


def self_attn_chunk(cfg: ModelConfig, p, x, start, cache, *, layer,
                    lengths):
    """Chunked-prefill self-attention (DESIGN.md §2): Tc new tokens at
    absolute positions [start, start+Tc) attend causally over the cache
    prefix written by earlier chunks plus themselves.

    x: (B,Tc,d); start: () int32 (traced — one executable serves every
    chunk offset); cache: the leaves of every layer of the group,
    (reps, B, S, Hkv, Dh) (int8 caches are 4-tuples with their scales),
    of which this block writes and reads layer ``layer``, so a cache
    carried through the layer scan is updated in place; lengths: (B,)
    each row's prompt tokens inside this chunk.  Requires a POSITIONAL
    (non-ring) cache: chunks are written contiguously from 0, so the
    causal mask alone hides every unwritten slot (kpos > max qpos) — no
    validity bookkeeping needed.  A row whose prompt ended before
    ``start`` (``lengths`` 0) keeps its cache bit for bit; a row that
    ends inside the chunk writes garbage K/V past its length, which
    decode overwrites before it ever becomes valid (same invariant as
    padded whole-prompt prefill).
    """
    B, Tc, _ = x.shape
    quant = cfg.kv_cache_dtype == "int8"
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    positions = start + jnp.arange(Tc)[None] + jnp.zeros((B, 1), jnp.int32)
    cos, sin = layers.rope_angles(positions, cfg.d_head, cfg.rope_theta)
    q = layers.apply_rope(q, cos, sin)
    k = layers.apply_rope(k, cos, sin)
    if quant:
        k_cache, v_cache, k_s, v_s = cache
        kq, ks_new = quantize_kv(k)
        vq, vs_new = quantize_kv(v)
    else:
        k_cache, v_cache = cache
        kq, vq = k, v
    active = lengths > 0

    def write(leaf, new):
        at = (layer, 0, start) + (0,) * (leaf.ndim - 3)
        old = jax.lax.dynamic_slice(leaf, at, (1,) + new.shape)[0]
        keep = active.reshape((B,) + (1,) * (new.ndim - 1))
        return jax.lax.dynamic_update_slice(
            leaf, jnp.where(keep, new.astype(leaf.dtype), old)[None], at)

    k_cache, v_cache = write(k_cache, kq), write(v_cache, vq)
    if quant:
        k_s, v_s = write(k_s, ks_new), write(v_s, vs_new)
    if uses_chunk_kernel(cfg, k_cache.dtype):
        out = _chunk_kernel(cfg, q, k_cache, v_cache, start, layer)
    else:
        kd, vd = k_cache[layer], v_cache[layer]
        if quant:
            with jax.named_scope("vmem_fused:flash_prefill_int8"):
                kd = dequantize_kv(kd, k_s[layer], q.dtype)
                vd = dequantize_kv(vd, v_s[layer], q.dtype)
        out = full_attention(q, kd, vd, causal=True, q_offset=start)
    out = out.reshape(B, Tc, cfg.q_dim) @ p["wo"]
    new_cache = (k_cache, v_cache, k_s, v_s) if quant else (k_cache, v_cache)
    return out, new_cache


def distributed_decode_attention(q, k_cache, v_cache, pos, mesh, *,
                                 window: int = 0):
    """Flash-decode over a SEQUENCE-sharded KV cache (distributed
    segmented softmax — beyond-paper, DESIGN.md §5).

    Each `model` shard holds an S/m slice of the cache (what makes a
    100-layer 32k cache fit a 16 GiB chip); the per-shard partial
    (max, numerator, denominator) triples combine with one pmax + two
    psums on (B,H,Dh)-sized tensors instead of the (B,H,S)-score
    all-gather GSPMD would otherwise insert.  Ring caches work
    unchanged: softmax is permutation-invariant and slot validity is
    computed from GLOBAL slot ids.
    """
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import batch_axes

    B, _, H, Dh = q.shape
    S = k_cache.shape[1]
    baxes = batch_axes(mesh, B)

    def local(q, k, v, pos):
      with jax.named_scope("vmem_fused:flash_decode"):
        s_loc = k.shape[1]
        shard = jax.lax.axis_index("model")
        s = _gqa_scores(q, k)                          # (B,Hkv,G,1,s_loc)
        slot = shard * s_loc + jnp.arange(s_loc)       # global slot ids
        if window:
            valid = (slot[None] <= pos[:, None]) | (pos[:, None] >= S)
        else:
            valid = slot[None] <= pos[:, None]
        s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, None, None, :]
        m_loc = s.max(-1)                              # (B,Hkv,G,1)
        m_glob = jax.lax.pmax(m_loc, "model")
        p_ = jnp.exp(s - m_glob[..., None])
        l_loc = p_.sum(-1)
        num_loc = jnp.einsum("bhgqk,bkhd->bhgqd", p_, v.astype(jnp.float32))
        l_glob = jax.lax.psum(l_loc, "model")
        num_glob = jax.lax.psum(num_loc, "model")
        out = num_glob / jnp.maximum(l_glob[..., None], 1e-30)
        B_, Hkv_, G_ = out.shape[:3]
      return out.reshape(B_, Hkv_ * G_, 1, Dh).swapaxes(1, 2)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(baxes, None, None, None),
                  P(baxes, "model", None, None),
                  P(baxes, "model", None, None),
                  P(baxes)),
        out_specs=P(baxes, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, pos)
    return out.astype(q.dtype)


def _seq_shard_mesh(cfg, S, B):
    """Mesh if the decode cache is sequence-sharded (mirror of the
    sharding/partition.py cache rule), else None."""
    mesh = shctx.get_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    msize = mesh.shape["model"]
    if cfg.n_kv_heads % msize == 0:      # head-sharded instead
        return None
    if S >= 2048 and S % msize == 0:
        return mesh
    return None


def self_attn_decode(cfg: ModelConfig, p, x, pos, cache, *, window: int = 0):
    """One-token decode. x: (B,1,d); pos: (B,) position of this token.
    int8 caches are 4-tuples (kq, vq, k_scale, v_scale)."""
    B = x.shape[0]
    quant = cfg.kv_cache_dtype == "int8"
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    cos, sin = layers.rope_angles(pos[:, None], cfg.d_head, cfg.rope_theta)
    q = layers.apply_rope(q, cos, sin)
    k = layers.apply_rope(k, cos, sin)
    if quant:
        k_cache, v_cache, k_s, v_s = cache
        kq, ks_new = quantize_kv(k[:, 0])
        vq, vs_new = quantize_kv(v[:, 0])
    else:
        k_cache, v_cache = cache
        kq, vq = k[:, 0], v[:, 0]
    S = k_cache.shape[1]
    slot = (pos % S) if window else pos
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, slot].set(kq)
    v_cache = v_cache.at[bidx, slot].set(vq)
    if quant:
        k_s = k_s.at[bidx, slot].set(ks_new)
        v_s = v_s.at[bidx, slot].set(vs_new)
        # dequant inside the fused scope: an int8 decode kernel dequants
        # in-register; HBM reads stay int8 (see §Perf "beyond" item)
        with jax.named_scope("vmem_fused:flash_decode_int8"):
            kd = dequantize_kv(k_cache, k_s, q.dtype)
            vd = dequantize_kv(v_cache, v_s, q.dtype)
    else:
        kd, vd = k_cache, v_cache
    mesh = _seq_shard_mesh(cfg, S, B)
    if mesh is not None:
        out = distributed_decode_attention(q, kd, vd, pos, mesh,
                                           window=window)
    else:
        out = decode_attention(q, kd, vd, pos, window=window)
    out = out.reshape(B, 1, cfg.q_dim) @ p["wo"]
    new_cache = (k_cache, v_cache, k_s, v_s) if quant else (k_cache, v_cache)
    return out, new_cache


def cross_attn_forward(cfg: ModelConfig, p, x, vis_kv):
    """Cross-attention over fixed vision KV. vis_kv: (k,v) (B,Nv,Hkv,Dh)."""
    B, T, _ = x.shape
    q = _project_q(cfg, p, x)
    k, v = vis_kv
    out = full_attention(q, k, v, causal=False)
    out = out.reshape(B, T, cfg.q_dim) @ p["wo"]
    return jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype) * out


def cross_kv(cfg: ModelConfig, p, vis_embeds):
    """Precompute vision K/V once per request (prefill)."""
    return _project_kv(cfg, p, vis_embeds)
