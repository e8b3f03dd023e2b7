"""Plain reference of the dense GQA decoder, in float32.

The architecture of both benchmark configurations (Yi-6B, Qwen3-14B):
token embedding; per layer, pre-norm RMSNorm, grouped-query attention
with rotary position embedding on the first and second halves of each
head (and, for Qwen3, RMSNorm over each query and key head before the
rotation), causal softmax attention, output projection and residual;
then pre-norm RMSNorm and a SwiGLU MLP, ``down(silu(gate x) * up x)``,
and residual; a final RMSNorm and an untied output head.  Norms scale by
``1 + w``: the stored offset convention of the weights (``weights.py``).

Nothing here imports the program.  The weights are drawn again from the
seed, one layer at a time (``weights.make_layer``), so the reference
never holds the whole model: it runs the whole batch of sequences
through one layer, frees it, and goes on.  Every matrix product runs at
``jax.default_matmul_precision("highest")``: on a TPU a float32 product
is otherwise made from bfloat16 passes.

``lowp=True`` gives the control: the same computation with every weight
matrix and every activation that enters a matrix product rounded to
float8 (e4m3, one scale per output column or per token), the precision
step below the served bfloat16.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

QBLOCK = 512          # query rows per attention block


def _fp8(x, axis):
    """Round to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, lowp):
    if lowp:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _rope(x, pos, theta):
    """x (B, T, H, D); rotate the first half of each head against the
    second."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None].astype(jnp.float32) * inv          # (B, T, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal GQA.  q (B, T, H, D), k/v (B, T, Hkv, D); query rows in
    blocks of QBLOCK so the scores of a block are all that is held."""
    B, T, H, D = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    nb = T // QBLOCK
    qb = q.reshape(B, nb, QBLOCK, H, D).swapaxes(0, 1)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / np.sqrt(D)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.swapaxes(0, 1).reshape(B, T, H, D)


def _layer(m, lowp, x, p, pos):
    """One decoder layer in float32 on hidden states x (B, T, d)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    B, T, _ = x.shape
    H, Hkv, D = m["n_heads"], m["n_kv_heads"], m["d_head"]
    eps = m["norm_eps"]
    a = p["attn"]
    h = _rms(x, p["ln1"], eps)
    q = _mm(h, a["wq"], lowp).reshape(B, T, H, D)
    k = _mm(h, a["wk"], lowp).reshape(B, T, Hkv, D)
    v = _mm(h, a["wv"], lowp).reshape(B, T, Hkv, D)
    if m["qk_norm"]:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q = _rope(q, pos, m["rope_theta"])
    k = _rope(k, pos, m["rope_theta"])
    o = _attention(q, k, v).reshape(B, T, H * D)
    x = x + _mm(o, a["wo"], lowp)
    h = _rms(x, p["ln2"], eps)
    f = p["mlp"]
    g = jax.nn.silu(_mm(h, f["gate"], lowp)) * _mm(h, f["up"], lowp)
    return x + _mm(g, f["down"], lowp)


def _head(m, lowp, x, ln_f, unembed, at):
    """Logits (B, n, vocab) at positions ``at`` (B, n)."""
    xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
    xs = _rms(xs, ln_f.astype(jnp.float32), m["norm_eps"])
    return _mm(xs, unembed.astype(jnp.float32).T, lowp)


def _gaps(ref, tok):
    """Per position: how far the logit of ``tok`` lies below the
    reference's best.  ref (B, n, V), tok (B, n)."""
    best = ref.max(-1)
    return best - jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]


def _shardings(m: dict, mesh):
    """Tensor parallelism over the mesh's ``model`` axis for the weights
    (projections by output column, ``wo`` and ``down`` by input row,
    the embedding tables by vocabulary row); everything else
    replicated.  None without a mesh."""
    if mesh is None:
        return None, None, None
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    col, row = NamedSharding(mesh, P(None, "model")), \
        NamedSharding(mesh, P("model", None))
    spec = W.layer_shapes(m)
    layer = jax.tree.map(
        lambda _: rep, spec,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    for k in ("wq", "wk", "wv"):
        layer["attn"][k] = col
    layer["attn"]["wo"] = row
    layer["mlp"]["gate"] = layer["mlp"]["up"] = col
    layer["mlp"]["down"] = row
    return rep, layer, row


def logits(m: dict, seed: int, toks: np.ndarray, at: np.ndarray, *,
           lowps=(False,), mesh=None):
    """Reference logits (B, n, vocab) at positions ``at`` (B, n) of the
    token rows ``toks`` (B, T), T a multiple of QBLOCK; one array per
    entry of ``lowps`` (False: float32, True: the float8 control), all
    from one pass over the layers.  With a ``mesh``, the weights are
    split over its ``model`` axis (``_shardings``)."""
    rep, layer_sh, table_sh = _shardings(m, mesh)
    put = jnp.asarray if rep is None else (lambda a: jax.device_put(a, rep))
    T = toks.shape[1]
    pos = put(np.broadcast_to(np.arange(T, dtype=np.int32),
                              toks.shape).copy())
    root = W.root_key(seed)
    k_emb, k_unemb, k_norm, _ = W.keys(root)
    table = jax.jit(functools.partial(W.make_embed, m),
                    out_shardings=table_sh)
    with jax.default_matmul_precision("highest"):
        embed = table(k_emb)
        x = jax.jit(lambda e, t: jnp.take(e, t, axis=0).astype(jnp.float32),
                    out_shardings=rep)(embed, put(toks))
        del embed
        streams = [x for _ in lowps]
        make = jax.jit(functools.partial(W.make_layer, m),
                       out_shardings=layer_sh)
        step = {lp: jax.jit(functools.partial(_layer, m, lp),
                            out_shardings=rep) for lp in lowps}
        for i in range(m["n_layers"]):
            p = make(W.layer_key(root, i))
            streams = [step[lp](s, p, pos) for lp, s in zip(lowps, streams)]
            del p
        ln_f = jax.jit(functools.partial(W.make_final_norm, m),
                       out_shardings=rep)(k_norm)
        unembed = table(k_unemb)
        return [jax.jit(functools.partial(_head, m, lp), out_shardings=rep)(
            s, ln_f, unembed, put(at)) for lp, s in zip(lowps, streams)]


def logit_gaps(m: dict, seed: int, seqs: Sequence[np.ndarray],
               served: Sequence[np.ndarray], *, control: bool = False,
               mesh=None):
    """For each sequence (prompt ids) and the tokens served after it,
    the gap of every served token below the reference's best logit.
    With ``control``, also the gap of the token that the float8 control
    puts first at the same positions.

    Returns a list of dicts ``{"served": gaps, "control": gaps}`` (numpy,
    one entry per served token).  With a ``mesh``, the weights are
    split over it (``logits``)."""
    B = len(seqs)
    lens = [len(p) + len(s) - 1 for p, s in zip(seqs, served)]
    T = -(-max(lens) // QBLOCK) * QBLOCK
    n = max(len(s) for s in served)
    toks = np.zeros((B, T), np.int32)
    at = np.zeros((B, n), np.int32)
    tgt = np.zeros((B, n), np.int32)
    for b, (p, s) in enumerate(zip(seqs, served)):
        full = np.concatenate([p, s[:-1]]).astype(np.int32)
        toks[b, :len(full)] = full
        # logits at the last prompt position and every served position
        # but the last predict the served tokens; pad entries repeat the
        # last position (their gaps are dropped below)
        idx = len(p) - 1 + np.arange(len(s))
        at[b] = np.concatenate([idx, np.full(n - len(s), idx[-1])])
        tgt[b] = np.concatenate([s, np.full(n - len(s), s[-1])])
    out = logits(m, seed, toks, at, lowps=(False, True) if control
                 else (False,), mesh=mesh)
    gaps = jax.jit(_gaps)
    res = {"served": np.asarray(gaps(out[0], tgt))}
    if control:
        pick = jnp.argmax(out[1], -1).astype(jnp.int32)
        res["control"] = np.asarray(gaps(out[0], pick))
    return [{k: v[b, :len(served[b])] for k, v in res.items()}
            for b in range(B)]
