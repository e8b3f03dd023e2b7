"""Random weights from the run's seed, for the dense GQA decoder.

The benchmark makes the weights, not the program: one jitted call from
the seed, in the served dtype, laid out as the program's parameter tree
(``embed``, ``unembed``, ``ln_f`` and one block group whose leaves are
stacked over layers).  Layer ``i`` is drawn from its own key, so the
plain reference can draw any one layer again, alone, and get the same
numbers without holding the model.

Norm scales are stored as offsets from 1 (a norm multiplies by
``1 + w``), as the program stores them.  They are drawn, not zero, so
that the comparison sees them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def root_key(seed: int):
    """A key for any whole seed up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_shapes(m: dict) -> dict:
    """(shape, std) of every leaf of one layer, in a fixed order."""
    d, ff = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    attn = {"wq": ((d, q), d ** -0.5), "wk": ((d, kv), d ** -0.5),
            "wv": ((d, kv), d ** -0.5), "wo": ((q, d), q ** -0.5)}
    if m["qk_norm"]:
        attn["q_norm"] = ((m["d_head"],), NORM_STD)
        attn["k_norm"] = ((m["d_head"],), NORM_STD)
    return {"ln1": ((d,), NORM_STD), "ln2": ((d,), NORM_STD), "attn": attn,
            "mlp": {"gate": ((d, ff), d ** -0.5), "up": ((d, ff), d ** -0.5),
                    "down": ((ff, d), ff ** -0.5)}}


def make_layer(m: dict, key, dtype=jnp.bfloat16) -> dict:
    """Layer weights from the layer's key: leaf ``j`` of the fixed order
    is drawn from ``fold_in(key, j)``."""
    spec = layer_shapes(m)
    flat, tree = jax.tree.flatten(spec, is_leaf=lambda x: isinstance(x, tuple)
                                  and isinstance(x[0], tuple))
    leaves = [_normal(jax.random.fold_in(key, j), shape, std, dtype)
              for j, (shape, std) in enumerate(flat)]
    return jax.tree.unflatten(tree, leaves)


def keys(root):
    """(embed, unembed, ln_f, layers) keys from the seed's root key."""
    k = jax.random.split(root, 4)
    return k[0], k[1], k[2], k[3]


def layer_key(root, i):
    return jax.random.fold_in(keys(root)[3], i)


def make_embed(m: dict, key, dtype=jnp.bfloat16):
    return _normal(key, (m["vocab_size"], m["d_model"]), m["d_model"] ** -0.5,
                   dtype)


def make_final_norm(m: dict, key, dtype=jnp.bfloat16):
    return _normal(key, (m["d_model"],), NORM_STD, dtype)


def make_params(m: dict, root, dtype=jnp.bfloat16):
    """The whole tree from ``root_key(seed)``.  Call it under
    ``jax.jit`` with the key as an argument, so that one program serves
    every seed."""
    k_emb, k_unemb, k_norm, _ = keys(root)
    lkeys = jax.vmap(lambda i: layer_key(root, i))(jnp.arange(m["n_layers"]))
    return {"embed": make_embed(m, k_emb, dtype),
            "unembed": make_embed(m, k_unemb, dtype),
            "ln_f": make_final_norm(m, k_norm, dtype),
            "groups": ((jax.vmap(lambda k: make_layer(m, k, dtype))(lkeys),),)}
