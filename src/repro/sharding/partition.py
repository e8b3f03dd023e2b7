"""Partition rules: params, caches, optimizer state, batches.

Strategy (DESIGN.md §5):
  * tensor-parallel over ``model``: fused q/kv/o projections, MLP d_ff,
    vocab embeddings, RWKV square projections, RG-LRU width;
  * expert-parallel over ``data`` + expert-ff over ``model`` for MoE
    (consumed by the shard_map EP path, models/moe.py);
  * batch over (pod, data) whenever divisible;
  * decode KV caches: kv-heads over ``model`` when divisible, else the
    SEQUENCE axis goes over ``model`` (bounds per-device cache bytes for
    the 100-layer VLM at 32k context — the thing that OOMs otherwise);
  * paged KV pools: kv-heads over ``model`` when divisible, pages whole
    on every device (block tables index them globally);
  * everything falls back to replication when a dim does not divide.

All rules are shape-driven (checked against the actual mesh axis sizes),
so the same code serves the 16x16 pod, the 2x16x16 multi-pod and tiny
test meshes.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import BLOCK_ATTN, BLOCK_MOE, ModelConfig
from repro.launch.mesh import batch_axes


def _div(n: int, mesh, axis: str) -> bool:
    return axis in mesh.axis_names and n % mesh.shape[axis] == 0


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s


def param_specs(cfg: ModelConfig, params, mesh):
    """PartitionSpec pytree matching `params` (which may be shapes)."""

    def rule(path, leaf):
        ndim = len(leaf.shape)
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        name = names[-1]
        shape = leaf.shape

        def last2(spec_a, spec_b):
            """Spec for the trailing two dims, None-padded for scan dims."""
            return P(*([None] * (ndim - 2) + [spec_a, spec_b]))

        if name in ("embed", "unembed"):
            return P("model", None) if _div(shape[0], mesh, "model") \
                else P(None, None)
        if name == "vis_proj":
            return P(None, "model") if _div(shape[1], mesh, "model") \
                else P(None, None)
        if ndim < 2:
            return P(*([None] * ndim))
        # MoE experts: (R, E, d, f) / (R, E, f, d)
        if name in ("w_gate", "w_up"):
            e_ok = _div(shape[1], mesh, "data")
            f_ok = _div(shape[3], mesh, "model")
            return P(None, "data" if e_ok else None, None,
                     "model" if f_ok else None)
        if name == "w_down":
            e_ok = _div(shape[1], mesh, "data")
            f_ok = _div(shape[2], mesh, "model")
            return P(None, "data" if e_ok else None,
                     "model" if f_ok else None, None)
        if name == "router":
            return P(*([None] * ndim))
        # column-parallel (output dim sharded).  (§Perf 1b: a row-parallel
        # wk/wv variant measured neutral on the VLM and 1.7x WORSE on
        # recurrentgemma — reverted.)
        if name in ("wq", "wk", "wv", "gate", "up", "wx", "wg",
                    "wr", "wi", "ck", "cr"):
            return last2(None, "model") if _div(shape[-1], mesh, "model") \
                else P(*([None] * ndim))
        # row-parallel (input dim sharded, output reduced)
        if name in ("wo", "down", "cv"):
            return last2("model", None) if _div(shape[-2], mesh, "model") \
                else P(*([None] * ndim))
        if name == "conv":  # (R, cw, w)
            return last2(None, "model") if _div(shape[-1], mesh, "model") \
                else P(*([None] * ndim))
        return P(*([None] * ndim))

    return jax.tree_util.tree_map_with_path(rule, params)


def cache_specs(cfg: ModelConfig, cache, mesh, batch: int):
    """PartitionSpec pytree for a decode cache."""
    baxes = batch_axes(mesh, batch)

    def rule(path, leaf):
        ndim = len(leaf.shape)
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        name = names[-1]
        shape = leaf.shape
        if name == "pos":
            return P(baxes)
        if name in ("k", "v"):          # (R, B, S, Hkv, Dh)
            if _div(shape[3], mesh, "model"):
                return P(None, baxes, None, "model", None)   # kv-heads
            if shape[2] >= 2048 and _div(shape[2], mesh, "model"):
                return P(None, baxes, "model", None, None)   # seq-sharded
            return P(None, baxes, None, None, None)
        if name in ("k_s", "v_s"):       # int8 cache scales (R, B, S, Hkv)
            if _div(shape[3], mesh, "model"):
                return P(None, baxes, None, "model")
            if shape[2] >= 2048 and _div(shape[2], mesh, "model"):
                return P(None, baxes, "model", None)
            return P(None, baxes, None, None)
        if name == "s":                  # rwkv state (R, B, H, hs, hs)
            if _div(shape[2], mesh, "model"):
                return P(None, baxes, "model", None, None)
            return P(None, baxes, None, None, None)
        if name in ("x_tm", "x_cm"):     # (R, B, d)
            return P(None, baxes, "model") \
                if _div(shape[2], mesh, "model") else P(None, baxes, None)
        if name == "h":                  # (R, B, w)
            return P(None, baxes, "model") \
                if _div(shape[2], mesh, "model") else P(None, baxes, None)
        if name == "conv":               # (R, B, cw-1, w)
            return P(None, baxes, None, "model") \
                if _div(shape[3], mesh, "model") else P(None, baxes, None, None)
        return P(*([None] * ndim))

    return jax.tree_util.tree_map_with_path(rule, cache)


def paged_cache_specs(cfg: ModelConfig, cache, mesh, batch: int):
    """PartitionSpec pytree for a paged decode cache
    (``transformer.init_paged_cache``).  A shared page pool
    (R, n_pages, page, Hkv, Dh) holds every slot's pages and the
    replicated block tables index it globally, so its pages stay whole on
    every device and only KV heads go over ``model``; per-slot leaves
    follow ``cache_specs``."""
    specs = cache_specs(cfg, cache, mesh, batch)
    heads = "model" if _div(cfg.n_kv_heads, mesh, "model") else None

    def pool(leaf):                     # k/v (R, n, page, Hkv, Dh); k_s/v_s
        return P(None, None, None, heads, *([None] * (len(leaf.shape) - 4)))

    groups = tuple(
        tuple(jax.tree.map(pool, cslot) if btype in (BLOCK_ATTN, BLOCK_MOE)
              else sslot
              for btype, cslot, sslot in zip(pattern, cgroup, sgroup))
        for (pattern, _), cgroup, sgroup in zip(
            cfg.block_groups(), cache["groups"], specs["groups"]))
    return dict(specs, groups=groups)


def batch_specs(cfg: ModelConfig, batch_tree, mesh):
    """Input batch: shard the leading batch dim over (pod, data)."""

    def rule(path, leaf):
        ndim = len(leaf.shape)
        baxes = batch_axes(mesh, leaf.shape[0]) if ndim else None
        return P(*([baxes] + [None] * (ndim - 1))) if ndim else P()

    return jax.tree_util.tree_map_with_path(rule, batch_tree)


def logits_spec(cfg: ModelConfig, mesh, batch: int, with_time: bool = False):
    baxes = batch_axes(mesh, batch)
    v_ok = _div(cfg.vocab_size, mesh, "model")
    dims = [baxes] + ([None] if with_time else []) + \
        ["model" if v_ok else None]
    return P(*dims)


def to_shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
