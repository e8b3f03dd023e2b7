"""Model assembly: block groups -> lax.scan, forward / prefill / decode.

Every architecture is a sequence of *block groups*; each group is a
repeated block pattern whose parameters are stacked on a leading axis and
executed with ``jax.lax.scan`` (so a 100-layer model lowers to HLO the
size of one pattern).  Caches mirror the grouping: per group, per pattern
slot, a type-specific state stacked on the repeat axis.

Public API (all pure functions of (cfg, params, ...)):

    init_params(cfg, key, dtype)
    forward(cfg, params, ...)            -> logits (B,T,V)   [train/encoder]
    prefill(cfg, params, ...)            -> (last_logits, cache)
    decode_step(cfg, params, token, cache) -> (logits, cache)
    init_cache(cfg, batch, cache_len)    -> zeroed cache pytree
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.sharding import context as shctx

from . import attention, layers, moe, rglru, rwkv
from .config import (BLOCK_ATTN, BLOCK_CROSS, BLOCK_MOE, BLOCK_REC,
                     BLOCK_RWKV, ModelConfig)


# ----------------------------------------------------------------- init ---
def _block_init(cfg: ModelConfig, btype: str, key, dtype):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if btype == BLOCK_ATTN:
        return {
            "ln1": jnp.zeros((d,), dtype), "ln2": jnp.zeros((d,), dtype),
            "attn": attention.attn_init(ks[0], cfg, dtype),
            "mlp": layers.mlp_init(ks[1], d, cfg.d_ff, cfg.act, dtype),
        }
    if btype == BLOCK_MOE:
        return {
            "ln1": jnp.zeros((d,), dtype), "ln2": jnp.zeros((d,), dtype),
            "attn": attention.attn_init(ks[0], cfg, dtype),
            "moe": moe.moe_init(ks[1], cfg, dtype),
        }
    if btype == BLOCK_CROSS:
        return {
            "ln1": jnp.zeros((d,), dtype), "ln2": jnp.zeros((d,), dtype),
            "attn": attention.attn_init(ks[0], cfg, dtype, cross=True),
            "mlp": layers.mlp_init(ks[1], d, cfg.d_ff, cfg.act, dtype),
        }
    if btype == BLOCK_REC:
        return {
            "ln1": jnp.zeros((d,), dtype), "ln2": jnp.zeros((d,), dtype),
            "rec": rglru.rglru_init(ks[0], cfg, dtype),
            "mlp": layers.mlp_init(ks[1], d, cfg.d_ff, "gelu", dtype),
        }
    if btype == BLOCK_RWKV:
        return {
            "ln1": jnp.zeros((d,), dtype), "ln2": jnp.zeros((d,), dtype),
            "rwkv": rwkv.rwkv_init(ks[0], cfg, dtype),
        }
    raise ValueError(btype)


def init_params(cfg: ModelConfig, key, dtype=jnp.float32):
    ks = jax.random.split(key, 4 + len(cfg.block_groups()))
    params = {"embed": layers.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
              "ln_f": jnp.zeros((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        params["unembed"] = layers.embed_init(ks[1], cfg.vocab_size,
                                              cfg.d_model, dtype)
    if cfg.arch_type == "vlm":
        params["vis_proj"] = layers.dense_init(ks[2], cfg.d_vision,
                                               cfg.d_model, dtype)
    groups = []
    for gi, (pattern, reps) in enumerate(cfg.block_groups()):
        gkey = ks[4 + gi]
        slot_params = []
        for j, btype in enumerate(pattern):
            rkeys = jax.random.split(jax.random.fold_in(gkey, j), reps)
            slot_params.append(
                jax.vmap(lambda k: _block_init(cfg, btype, k, dtype))(rkeys))
        groups.append(tuple(slot_params))
    params["groups"] = tuple(groups)
    return params


# ---------------------------------------------------------------- cache ---
def _attn_cache_len(cfg: ModelConfig, cache_len: int) -> int:
    return cfg.attn_cache_len(cache_len)


def attn_cache_len(cfg: ModelConfig, cache_len: int) -> int:
    """Public alias: per-request attention-cache length (window-capped)."""
    return cfg.attn_cache_len(cache_len)


def effective_window(cfg: ModelConfig) -> int:
    if cfg.sliding_window:
        return cfg.sliding_window
    if cfg.arch_type == "hybrid":
        return cfg.local_window
    return 0


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.float32):
    """Zeroed cache; attention caches sized min(cache_len, window)."""
    S = _attn_cache_len(cfg, cache_len)
    groups = []
    for pattern, reps in cfg.block_groups():
        slots = []
        for btype in pattern:
            if btype in (BLOCK_ATTN, BLOCK_MOE):
                kv_dt = jnp.int8 if cfg.kv_cache_dtype == "int8" else dtype
                slot = {
                    "k": jnp.zeros((reps, batch, S, cfg.n_kv_heads,
                                    cfg.d_head), kv_dt),
                    "v": jnp.zeros((reps, batch, S, cfg.n_kv_heads,
                                    cfg.d_head), kv_dt),
                }
                if cfg.kv_cache_dtype == "int8":
                    slot["k_s"] = jnp.zeros(
                        (reps, batch, S, cfg.n_kv_heads), jnp.float32)
                    slot["v_s"] = jnp.zeros(
                        (reps, batch, S, cfg.n_kv_heads), jnp.float32)
                slots.append(slot)
            elif btype == BLOCK_CROSS:
                slots.append({
                    "k": jnp.zeros((reps, batch, cfg.n_vision_tokens,
                                    cfg.n_kv_heads, cfg.d_head), dtype),
                    "v": jnp.zeros((reps, batch, cfg.n_vision_tokens,
                                    cfg.n_kv_heads, cfg.d_head), dtype),
                })
            elif btype == BLOCK_REC:
                st = rglru.init_state(cfg, batch, dtype)
                slots.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (reps,) + x.shape), st))
            elif btype == BLOCK_RWKV:
                st = rwkv.init_state(cfg, batch, dtype)
                slots.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (reps,) + x.shape), st))
        groups.append(tuple(slots))
    return {"pos": jnp.zeros((batch,), jnp.int32), "groups": tuple(groups)}


def supports_paged_decode(cfg: ModelConfig) -> bool:
    """Paged KV only applies to self-attention caches: the config must
    have a decode step and at least one ATTN/MOE block.  Attention-free
    (RWKV) and encoder-only configs keep the slot pool — their per-slot
    state is O(1) in sequence length, so paging buys nothing."""
    if not cfg.has_decode:
        return False
    return any(b in (BLOCK_ATTN, BLOCK_MOE)
               for pat, _ in cfg.block_groups() for b in pat)


def init_paged_cache(cfg: ModelConfig, batch: int, cache_len: int,
                     n_pages: int, page_size: int, dtype=jnp.float32):
    """Paged decode-pool cache (DESIGN.md §3): self-attention K/V live in
    a SHARED page pool (reps, n_pages, page_size, Hkv, Dh) indexed
    through per-slot block tables; everything sequence-length-independent
    (recurrent state, cross-attention vision KV, positions) stays a
    per-slot tensor exactly as in ``init_cache``.

    block_tables: (batch, ceil(attn_cache_len/page_size)) int32 — virtual
    slot ``s`` of pool slot ``b`` is page ``block_tables[b, s//page]``
    offset ``s % page``.  The caller (engine) owns table contents.
    """
    S = _attn_cache_len(cfg, cache_len)
    n_p = -(-S // page_size)
    groups = []
    for pattern, reps in cfg.block_groups():
        slots = []
        for btype in pattern:
            if btype in (BLOCK_ATTN, BLOCK_MOE):
                kv_dt = jnp.int8 if cfg.kv_cache_dtype == "int8" else dtype
                slot = {
                    "k": jnp.zeros((reps, n_pages, page_size, cfg.n_kv_heads,
                                    cfg.d_head), kv_dt),
                    "v": jnp.zeros((reps, n_pages, page_size, cfg.n_kv_heads,
                                    cfg.d_head), kv_dt),
                }
                if cfg.kv_cache_dtype == "int8":
                    slot["k_s"] = jnp.zeros(
                        (reps, n_pages, page_size, cfg.n_kv_heads),
                        jnp.float32)
                    slot["v_s"] = jnp.zeros(
                        (reps, n_pages, page_size, cfg.n_kv_heads),
                        jnp.float32)
                slots.append(slot)
            elif btype == BLOCK_CROSS:
                slots.append({
                    "k": jnp.zeros((reps, batch, cfg.n_vision_tokens,
                                    cfg.n_kv_heads, cfg.d_head), dtype),
                    "v": jnp.zeros((reps, batch, cfg.n_vision_tokens,
                                    cfg.n_kv_heads, cfg.d_head), dtype),
                })
            elif btype == BLOCK_REC:
                st = rglru.init_state(cfg, batch, dtype)
                slots.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (reps,) + x.shape), st))
            elif btype == BLOCK_RWKV:
                st = rwkv.init_state(cfg, batch, dtype)
                slots.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (reps,) + x.shape), st))
        groups.append(tuple(slots))
    return {"pos": jnp.zeros((batch,), jnp.int32),
            "block_tables": jnp.zeros((batch, n_p), jnp.int32),
            "groups": tuple(groups)}


# ---------------------------------------------------------- block apply ---
def _apply_block(cfg: ModelConfig, btype: str, p, x, *, mode: str,
                 positions=None, lengths=None, cache=None, pos=None,
                 vis=None, moe_impl="local", mesh=None, cache_len=0,
                 chunk_start=None, block_tables=None, page_size=0,
                 paged_len=0, layer=None):
    """One block. mode: 'fwd' | 'prefill' | 'chunk' | 'decode'.
    Returns (x, new_cache_slot).  'chunk' continues an existing cache
    from absolute position ``chunk_start`` (chunked prefill).  A non-None
    ``block_tables`` switches decode attention to the paged KV pool,
    whose leaves then hold every layer of the group; ``layer`` is this
    block's index into them."""
    win = effective_window(cfg)
    new_cache = cache

    if btype in (BLOCK_ATTN, BLOCK_MOE):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        if mode in ("decode", "chunk"):
            ctuple = (cache["k"], cache["v"], cache["k_s"], cache["v_s"]) \
                if cfg.kv_cache_dtype == "int8" else \
                (cache["k"], cache["v"])
            if mode == "chunk":
                a, new_cache = attention.self_attn_chunk(
                    cfg, p["attn"], h, chunk_start, ctuple, layer=layer,
                    lengths=lengths)
            elif block_tables is not None:
                a, new_cache = attention.self_attn_decode_paged(
                    cfg, p["attn"], h, pos, ctuple, block_tables,
                    layer=layer, page_size=page_size, s_len=paged_len,
                    window=win)
            else:
                a, new_cache = attention.self_attn_decode(
                    cfg, p["attn"], h, pos, ctuple, window=win)
        else:
            a, kv = attention.self_attn_forward(
                cfg, p["attn"], h, positions, lengths,
                window=win, make_cache=(mode == "prefill"),
                cache_len=cache_len)
            if mode == "prefill":
                new_cache = {"k": kv[0], "v": kv[1]}
                if cfg.kv_cache_dtype == "int8":
                    new_cache["k_s"], new_cache["v_s"] = kv[2], kv[3]
        x = x + a
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        if btype == BLOCK_ATTN:
            x = x + layers.mlp_apply(p["mlp"], h, cfg.act)
        else:
            x = x + _apply_moe(cfg, p["moe"], h, moe_impl, mesh)
        if mode in ("decode", "chunk"):
            nc = {"k": new_cache[0], "v": new_cache[1]}
            if cfg.kv_cache_dtype == "int8":
                nc["k_s"], nc["v_s"] = new_cache[2], new_cache[3]
            new_cache = nc
        return x, new_cache

    if btype == BLOCK_CROSS:
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        if mode == "prefill" or (mode == "fwd" and vis is not None):
            kv = attention.cross_kv(cfg, p["attn"], vis)
            if mode == "prefill":
                new_cache = {"k": kv[0], "v": kv[1]}
        else:  # decode: reuse cached vision KV
            kv = (cache["k"], cache["v"])
        a = attention.cross_attn_forward(cfg, p["attn"], h, kv)
        x = x + a
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.mlp_apply(p["mlp"], h, cfg.act)
        return x, new_cache

    if btype == BLOCK_REC:
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        state = cache if cache is not None else rglru.init_state(
            cfg, x.shape[0], x.dtype)
        if mode == "decode":
            r, new_state = rglru.rec_block_decode(cfg, p["rec"], h, state)
        else:
            r, new_state = rglru.rec_block_forward(cfg, p["rec"], h, state,
                                                   lengths)
        x = x + r
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.mlp_apply(p["mlp"], h, "gelu")
        return x, (new_state if mode != "fwd" else cache)

    if btype == BLOCK_RWKV:
        state = cache if cache is not None else jax.tree.map(
            lambda s: s, rwkv.init_state(cfg, x.shape[0], x.dtype))
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        tm, x_tm, s_new = rwkv.time_mix(cfg, p["rwkv"], h, state["x_tm"],
                                        state["s"], lengths)
        x = x + tm
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        cm, x_cm = rwkv.channel_mix(cfg, p["rwkv"], h, state["x_cm"], lengths)
        x = x + cm
        new_state = {"s": s_new, "x_tm": x_tm, "x_cm": x_cm}
        return x, (new_state if mode != "fwd" else cache)

    raise ValueError(btype)


def _apply_moe(cfg, p, x, impl, mesh):
    if impl == "ref":
        return moe.moe_dense_ref(cfg, p, x)
    if impl == "local":
        return moe.moe_local(cfg, p, x)
    if impl == "ep":
        from jax.sharding import PartitionSpec as P
        fn = functools.partial(moe.moe_ep, cfg)
        pspec = {
            "router": P(None, None),
            "w_gate": P("data", None, "model"),
            "w_up": P("data", None, "model"),
            "w_down": P("data", "model", None),
        }
        if cfg.shared_expert:
            pspec["shared"] = {"gate": P(None, "model"),
                               "up": P(None, "model"),
                               "down": P("model", None)}
        # batch over (pod, data) when divisible; else replicate (every
        # data shard routes the same tokens to its local experts — the
        # a2a round-trip stays correct, see moe_ep docstring). B=1 decode.
        baxes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
        bsz = 1
        for a in baxes:
            bsz *= mesh.shape[a]
        bspec = (baxes if len(baxes) > 1 else baxes[0]) \
            if x.shape[0] % bsz == 0 else None
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(pspec, P(bspec, None, None)),
            out_specs=P(bspec, None, None),
            check_vma=False,
        )(p, x)
    raise ValueError(impl)


# -------------------------------------------------------------- drivers ---
def _embed_input(cfg, params, tokens, embeds):
    if embeds is not None:
        return embeds
    return layers.embed_apply(params["embed"], tokens)


def _project_vision(cfg, params, vision_embeds):
    if vision_embeds is None:
        return None
    return vision_embeds @ params["vis_proj"]


def _run_groups(cfg, params, x, *, mode, positions=None, lengths=None,
                cache=None, pos=None, vis=None, moe_impl="local", mesh=None,
                cache_len=0, remat=False, chunk_start=None,
                block_tables=None, page_size=0, paged_len=0):
    # paged decode updates the shared attention pool in place, and a
    # chunk step the batch cache: their attention slots ride in the
    # scan's carry with the layer index, where as xs/ys the scan would
    # restack a fresh copy of the whole pool or cache
    in_place = mode == "chunk" or (mode == "decode"
                                   and block_tables is not None)
    new_groups = []
    for gi, (pattern, reps) in enumerate(cfg.block_groups()):
        gparams = params["groups"][gi]
        gcache = cache["groups"][gi] if cache is not None else None
        carried = [in_place and b in (BLOCK_ATTN, BLOCK_MOE)
                   for b in pattern]

        def body(carry, scans):
            # (§Perf 1c: a replicated-residual pin here measured WORSE —
            # XLA's weight-gathered sequence-parallel MLP beats
            # replicated-activations TP at 32k tokens; see EXPERIMENTS.md)
            xx, layer, pools = carry if in_place else (carry, None, ())
            pools = list(pools)
            new_slots = []
            for j in range(len(pattern)):
                p_j = scans[j]
                if carried[j]:
                    c_j = pools[j]
                else:
                    c_j = scans[len(pattern) + j] if gcache is not None \
                        else None
                xx, nc = _apply_block(
                    cfg, pattern[j], p_j, xx, mode=mode, positions=positions,
                    lengths=lengths, cache=c_j, pos=pos, vis=vis,
                    moe_impl=moe_impl, mesh=mesh, cache_len=cache_len,
                    chunk_start=chunk_start, block_tables=block_tables,
                    page_size=page_size, paged_len=paged_len, layer=layer)
                if carried[j]:
                    pools[j] = nc
                    new_slots.append(None)
                else:
                    new_slots.append(nc if nc is not None else 0)
            if in_place:
                return (xx, layer + 1, tuple(pools)), tuple(new_slots)
            return xx, tuple(new_slots)

        if remat:
            # activation checkpointing per block group: backward recomputes
            # the block from its input — temp memory drops from
            # O(layers x activations) to O(layers x d_model carries).
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        if in_place:
            pools = tuple(c if k else None for c, k in zip(gcache, carried))
            scans = tuple(gparams) + tuple(
                None if k else c for c, k in zip(gcache, carried))
            (x, _, pools), new_slot_caches = jax.lax.scan(
                body, (x, jnp.int32(0), pools), scans)
            new_slot_caches = tuple(p if k else c for p, c, k in
                                    zip(pools, new_slot_caches, carried))
        else:
            scans = tuple(gparams) + (tuple(gcache) if gcache is not None
                                      else ())
            x, new_slot_caches = jax.lax.scan(body, x, scans)
        new_groups.append(new_slot_caches)
    return x, tuple(new_groups)


def forward(cfg: ModelConfig, params, tokens=None, embeds=None,
            vision_embeds=None, lengths=None, moe_impl="local", mesh=None,
            remat=False, at=None):
    """Full-sequence forward, no cache (training / encoder inference).
    ``at``: optional (n,) positions; logits only there, (B, n, vocab)
    (a long sequence's whole (T, vocab) f32 logits take GiBs)."""
    x = _embed_input(cfg, params, tokens, embeds)
    B, T, _ = x.shape
    vis = _project_vision(cfg, params, vision_embeds)
    positions = jnp.arange(T)
    x, _ = _run_groups(cfg, params, x, mode="fwd", positions=positions,
                       lengths=lengths, vis=vis, moe_impl=moe_impl, mesh=mesh,
                       remat=remat)
    if at is not None:
        x = x[:, at]
    x = layers.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return layers.unembed_apply(head, x)


def prefill(cfg: ModelConfig, params, tokens=None, embeds=None,
            vision_embeds=None, lengths=None, cache_len: Optional[int] = None,
            moe_impl="local", mesh=None):
    """Process full prompts, return (last-token logits, cache)."""
    x = _embed_input(cfg, params, tokens, embeds)
    B, T, _ = x.shape
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    cache_len = cache_len or cfg.max_seq_len
    vis = _project_vision(cfg, params, vision_embeds)
    positions = jnp.arange(T)
    cache0 = init_cache(cfg, B, cache_len, x.dtype)
    x, new_groups = _run_groups(
        cfg, params, x, mode="prefill", positions=positions, lengths=lengths,
        cache=cache0, vis=vis, moe_impl=moe_impl, mesh=mesh,
        cache_len=cache_len)
    x = layers.rms_norm(x, params["ln_f"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.clip(lengths - 1, 0, T - 1)[:, None, None], axis=1)[:, 0]
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = layers.unembed_apply(head, last)
    return logits, {"pos": lengths.astype(jnp.int32), "groups": new_groups}


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill needs a POSITIONAL KV cache (chunks written
    contiguously, causal mask hides unwritten slots).  Ring caches
    (sliding-window / hybrid-local) and cross-attention vision KV are
    excluded — those configs fall back to whole-prompt prefill."""
    return cfg.chunkable_prefill


def prefill_chunk(cfg: ModelConfig, params, tokens, cache, start, lengths,
                  moe_impl="local", mesh=None):
    """One chunked-prefill step (DESIGN.md §2): process prompt tokens at
    absolute positions [start, start+Tc) against an existing cache.

    tokens: (B,Tc) — the chunk slice of the padded prompt matrix
    (garbage beyond a row's length is fine); cache: pytree from
    ``init_cache`` threaded through successive chunks; start: () int
    (traced — one executable serves every offset); lengths: (B,) FULL
    prompt lengths.

    Returns (last_logits (B,V), new_cache).  ``last_logits[b]`` is the
    next-token distribution for row ``b`` ONLY when its final prompt
    position lies inside this chunk; the caller gathers first tokens
    chunk by chunk.  Rows already fully processed (length <= start) keep
    their cache state bit-for-bit (recurrent carries are frozen).
    The caller owns ``cache['pos']`` and must set it to ``lengths``
    after the final chunk (mirrors ``prefill``'s returned pos).
    """
    x = layers.embed_apply(params["embed"], tokens)
    B, Tc, _ = x.shape
    rel_len = jnp.clip(lengths - start, 0, Tc)
    x, new_groups = _run_groups(
        cfg, params, x, mode="chunk", lengths=rel_len, cache=cache,
        chunk_start=start, moe_impl=moe_impl, mesh=mesh)
    # freeze the recurrent carries (e.g. RWKV token-shift) of rows that
    # finished in an earlier chunk, which this chunk's garbage tail would
    # otherwise clobber; attention caches freeze those rows themselves,
    # in the chunk's slots alone (``self_attn_chunk``)
    active = lengths > start                               # (B,)
    def _keep(new, old):
        m = active.reshape((1, B) + (1,) * (new.ndim - 2))
        return jnp.where(m, new, old)
    new_groups = tuple(
        tuple(new if b in (BLOCK_ATTN, BLOCK_MOE)
              else jax.tree.map(_keep, new, old)
              for b, new, old in zip(pattern, ng, og))
        for (pattern, _), ng, og in zip(cfg.block_groups(), new_groups,
                                        cache["groups"]))
    x = layers.rms_norm(x, params["ln_f"], cfg.norm_eps)
    idx = jnp.clip(lengths - 1 - start, 0, Tc - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = layers.unembed_apply(head, last)
    return logits, {"pos": cache["pos"], "groups": new_groups}


def decode_step(cfg: ModelConfig, params, token, cache, moe_impl="local",
                mesh=None, page_size: int = 0, paged_len: int = 0):
    """token: (B,) int32 (or (B,d) embeds for encoder-less flows).
    Returns (logits (B,V), new cache).

    Caches from ``init_paged_cache`` (detected by their ``block_tables``
    leaf) decode against the shared page pool; ``page_size`` must then be
    the pool's page size and ``paged_len`` the request-level cache length
    (defaults to the block tables' full virtual span) — both static, so
    the jitted executable is shared across table contents."""
    x = layers.embed_apply(params["embed"], token[:, None])
    pos = cache["pos"]
    bt = cache.get("block_tables")
    if bt is not None:
        assert page_size > 0, "paged decode_step needs page_size"
        paged_len = paged_len or bt.shape[1] * page_size
    x, new_groups = _run_groups(cfg, params, x, mode="decode", pos=pos,
                                cache=cache, moe_impl=moe_impl, mesh=mesh,
                                block_tables=bt, page_size=page_size,
                                paged_len=paged_len)
    x = layers.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = layers.unembed_apply(head, x[:, 0])
    new = {"pos": pos + 1, "groups": new_groups}
    if bt is not None:
        new["block_tables"] = bt
    return logits, new
