"""Per-architecture smoke tests (reduced configs, CPU).

For each assigned architecture: instantiate the reduced same-family
variant (2 layers, d_model<=512, <=4 experts), run one forward and one
train step, assert output shapes and finiteness; run a short
prefill+decode and check it against the full-forward oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config, get_smoke_config, list_archs
from repro.data import tokens as data_tokens
from repro.models import transformer as tfm
from repro.train import optimizer, train_loop

ALL = list(list_archs())


def _inputs(cfg, key, B, S):
    tok = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    kw = {}
    if cfg.arch_type == "audio":
        kw["embeds"] = jax.random.normal(key, (B, S, cfg.d_model)) * 0.02
        tok = None
    if cfg.arch_type == "vlm":
        kw["vision_embeds"] = jax.random.normal(
            key, (B, cfg.n_vision_tokens, cfg.d_vision)) * 0.02
    return tok, kw


def _moe_impl(cfg):
    return "ref" if cfg.n_experts else "local"


@pytest.mark.parametrize("arch", ALL)
def test_forward_smoke(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 4 and cfg.d_model <= 512
    assert cfg.n_experts <= 4
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    B, S = 2, 40
    tok, kw = _inputs(cfg, key, B, S)
    logits = tfm.forward(cfg, params, tokens=tok, moe_impl=_moe_impl(cfg), **kw)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    at = jnp.array([0, 17, S - 1])
    rows = tfm.forward(cfg, params, tokens=tok, moe_impl=_moe_impl(cfg),
                       at=at, **kw)
    np.testing.assert_allclose(rows, logits[:, at], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arch", ALL)
def test_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    opt_cfg = optimizer.AdamWConfig(lr=1e-3, total_steps=10)
    opt_state = optimizer.init(params)
    step = jax.jit(train_loop.make_train_step(cfg, opt_cfg,
                                              moe_impl=_moe_impl(cfg)))
    batch = next(data_tokens.batches(cfg, batch_size=2, seq_len=32))
    params2, opt_state, metrics = step(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually changed
    delta = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch", [a for a in ALL
                                  if get_config(a).has_decode])
def test_prefill_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    B, S = 2, 24
    lengths = jnp.array([16, 12], jnp.int32)
    tok, kw = _inputs(cfg, key, B, S)
    mi = _moe_impl(cfg)
    full = tfm.forward(cfg, params, tokens=tok, moe_impl=mi, **kw)
    prompt = tok[:, :16]
    logits, cache = tfm.prefill(cfg, params, tokens=prompt, lengths=lengths,
                                cache_len=S + 4, moe_impl=mi, **kw)
    for b in range(B):
        np.testing.assert_allclose(logits[b], full[b, lengths[b] - 1],
                                   atol=2e-4, rtol=2e-3)
    for _ in range(4):
        next_tok = tok[jnp.arange(B), cache["pos"]]
        logits, cache = tfm.decode_step(cfg, params, next_tok, cache,
                                        moe_impl=mi)
        for b in range(B):
            np.testing.assert_allclose(logits[b], full[b, cache["pos"][b] - 1],
                                       atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("arch", ["yi-6b", "qwen3-14b"])
def test_swa_variant_decode(arch):
    """Sliding-window serving variant: decode works past the window."""
    cfg = get_smoke_config(arch, sliding_window=16)
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    B = 2
    lengths = jnp.array([20, 24], jnp.int32)
    tok = jax.random.randint(key, (B, 24), 0, cfg.vocab_size)
    logits, cache = tfm.prefill(cfg, params, tokens=tok, lengths=lengths,
                                cache_len=64)
    # cache must be window-sized, not seq-sized
    k0 = cache["groups"][0][0]["k"]
    assert k0.shape[2] == 16
    for _ in range(8):
        nt = jnp.argmax(logits, -1).astype(jnp.int32)
        logits, cache = tfm.decode_step(cfg, params, nt, cache)
        assert bool(jnp.isfinite(logits).all())


def test_encoder_has_no_decode():
    cfg = get_config("hubert-xlarge")
    assert not cfg.has_decode
    assert not cfg.subquadratic  # and it is excluded from decode shapes


def test_assigned_registry_complete():
    assert len(ASSIGNED) == 10
    families = {get_config(a).arch_type for a in ASSIGNED}
    assert families == {"dense", "ssm", "moe", "audio", "hybrid", "vlm"}
