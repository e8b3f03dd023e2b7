"""The plain reference against the program's own forward pass, at smoke
width on the CPU, and the weights it draws again layer by layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import weights as W

KEYS = ("arch_type", "n_layers", "d_model", "n_heads", "n_kv_heads",
        "d_head", "d_ff", "vocab_size", "act", "qk_norm", "rope_theta",
        "norm_eps", "tie_embeddings", "max_seq_len")


def smoke(arch, **kw):
    from repro.configs import get_smoke_config
    cfg = get_smoke_config(arch, max_seq_len=256)
    cfg = dataclasses.replace(cfg, **kw)
    return cfg, {k: getattr(cfg, k) for k in KEYS}


@pytest.mark.parametrize("arch", ["yi-6b", "qwen3-14b"])
def test_layers_drawn_alone_equal_the_stacked_tree(arch):
    _, m = smoke(arch)
    root = W.root_key(2 ** 40 + 3)
    tree = jax.jit(lambda k: W.make_params(m, k))(root)
    stacked = tree["groups"][0][0]
    for i in range(m["n_layers"]):
        alone = jax.jit(lambda k: W.make_layer(m, k))(W.layer_key(root, i))
        same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a[i], b)),
                            stacked, alone)
        assert all(jax.tree.leaves(same))
    emb = jax.jit(lambda k: W.make_embed(m, k))(W.keys(root)[0])
    assert jnp.array_equal(emb, tree["embed"])


@pytest.mark.parametrize("arch,kw", [("yi-6b", {"norm_eps": 1e-5}),
                                     ("qwen3-14b", {})])
def test_reference_agrees_with_program_forward(arch, kw, monkeypatch):
    from repro.models import transformer as tfm
    monkeypatch.setattr(reference, "QBLOCK", 32)
    cfg, m = smoke(arch, **kw)
    seed = 12345
    params = jax.jit(lambda k: W.make_params(m, k))(W.root_key(seed))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, m["vocab_size"], (2, 64), dtype=np.int32)
    at = np.array([[5, 31, 63], [0, 40, 62]], np.int32)
    with jax.default_matmul_precision("highest"):
        full = tfm.forward(cfg, params, tokens=jnp.asarray(toks))
    want = np.take_along_axis(np.asarray(full), at[:, :, None], axis=1)
    got, low = reference.logits(m, seed, toks, at, lowps=(False, True))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)
    # the float8 control departs by far more than float32 rounding
    assert np.abs(np.asarray(low) - want).max() > 100 * \
        np.abs(np.asarray(got) - want).max()


def test_gaps_are_zero_for_the_argmax_and_positive_otherwise(monkeypatch):
    monkeypatch.setattr(reference, "QBLOCK", 32)
    _, m = smoke("yi-6b")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, m["vocab_size"], 20, dtype=np.int32)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :20] = prompt
    ref = np.asarray(reference.logits(m, 7, toks, np.array([[19]]))[0])[0, 0]
    best, other = int(ref.argmax()), int(ref.argmin())
    g = reference.logit_gaps(m, 7, [prompt], [np.array([best], np.int32)])
    assert g[0]["served"].tolist() == [0.0]
    g = reference.logit_gaps(m, 7, [prompt], [np.array([other], np.int32)])
    assert g[0]["served"][0] == pytest.approx(ref.max() - ref.min(), rel=1e-5)
