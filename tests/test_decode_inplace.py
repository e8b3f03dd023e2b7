"""The paged decode step updates its page pool in place (DESIGN.md §3):
the pool rides through the layer scan in its carry and the engine's jit
donates it, so a step writes only the new token's K/V.  Results are
bit-identical to the formulation that scanned each layer's pool as xs
and restacked the updated one as ys; the donated buffers are consumed
on every step; and what still holds pool data across a step (a spilled
page's capture) survives the donation."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import BucketServeScheduler, MemoryBudget, SchedulerConfig
from repro.core.engine import (ServingEngine, join_pool, paged_decode_jit,
                               split_pool)
from repro.core.request import Request, TaskType
from repro.core.telemetry import Tracer
from repro.models import layers
from repro.models import transformer as tfm
from repro.models.config import BLOCK_ATTN, BLOCK_MOE

S, PAGE, SLOTS, N_PAGES = 256, 32, 3, 20
POOLS = {"bf16": ("yi-6b", {}),
         "int8": ("qwen3-14b", {"kv_cache_dtype": "int8"})}


def _xs_ys_step(cfg, params, token, cache, *, page_size):
    """The paged step as it ran before the pool rode in the carry: the
    scan takes each layer's pool as xs and restacks the written one as
    ys.  A layer's pool is handed to attention as a stack of one."""
    x = layers.embed_apply(params["embed"], token[:, None])
    bt, pos = cache["block_tables"], cache["pos"]
    groups = []
    for gi, (pattern, reps) in enumerate(cfg.block_groups()):
        def body(xx, scans):
            new = []
            for j, btype in enumerate(pattern):
                paged = btype in (BLOCK_ATTN, BLOCK_MOE)
                c = scans[len(pattern) + j]
                xx, nc = tfm._apply_block(
                    cfg, btype, scans[j], xx, mode="decode", pos=pos,
                    cache=jax.tree.map(lambda a: a[None], c) if paged else c,
                    block_tables=bt, page_size=page_size,
                    paged_len=bt.shape[1] * page_size, layer=0)
                new.append(jax.tree.map(lambda a: a[0], nc) if paged else nc)
            return xx, tuple(new)
        x, g = jax.lax.scan(body, x, tuple(params["groups"][gi])
                            + tuple(cache["groups"][gi]))
        groups.append(g)
    x = layers.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return layers.unembed_apply(head, x[:, 0]), {
        "pos": pos + 1, "block_tables": bt, "groups": tuple(groups)}


def _filled_cache(cfg):
    """A paged cache of random K/V: two live slots over scattered pages,
    and a dead slot whose table is all trash page and whose position has
    walked past it."""
    cache = tfm.init_paged_cache(cfg, SLOTS, S, N_PAGES + 1, PAGE)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    cache["groups"] = jax.tree.map(
        lambda a: (jax.random.normal(next(keys), a.shape) * 3).astype(a.dtype),
        cache["groups"])
    bt = np.full((SLOTS, S // PAGE), N_PAGES, np.int32)
    bt[0, :3] = [4, 7, 1]
    bt[1, :2] = [9, 2]
    cache["block_tables"] = jnp.asarray(bt)
    cache["pos"] = jnp.asarray([70, 40, S + 44], jnp.int32)
    return cache


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_in_place_step_is_bit_identical_to_xs_ys_step(pool):
    arch, kw = POOLS[pool]
    cfg = get_smoke_config(arch, max_seq_len=S, **kw)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    cache = _filled_cache(cfg)
    want = jax.tree.map(jnp.copy, cache)
    step = paged_decode_jit(cfg, moe_impl="local", page_size=PAGE,
                            paged_len=S)
    ref = jax.jit(functools.partial(_xs_ys_step, cfg, page_size=PAGE))
    rest, kv = split_pool(cfg, cache)
    tok = jnp.asarray([3, 5, 7], jnp.int32)
    for _ in range(4):
        logits, rest, kv = step(params, tok, rest, kv)
        want_logits, want = ref(params, tok, want)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(want_logits))
        got = join_pool(rest, kv)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


def _engine(cfg, params, **kw):
    sched = BucketServeScheduler(
        cfg, MemoryBudget(hbm_bytes_per_device=2 ** 34, n_devices=1,
                          weight_bytes=0),
        SchedulerConfig(max_batch=SLOTS, memory_model="paged",
                        page_size=PAGE))
    return ServingEngine(cfg, params, sched, max_slots=SLOTS, cache_len=S,
                         chunk_tokens=64, paged=True, page_size=PAGE,
                         kv_pool_tokens=1024, **kw)


def test_every_decode_step_consumes_the_pool_it_was_given():
    cfg = get_smoke_config("yi-6b", max_seq_len=S)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tracer = Tracer()
    eng = _engine(cfg, params, tracer=tracer)
    be = eng.backend
    inner, steps = be.decode_iter, []

    def decode_iter(pool, context_tokens):
        given = jax.tree.leaves(split_pool(cfg, be.pool_cache)[1])
        out = inner(pool, context_tokens)
        steps.append(all(x.is_deleted() for x in given))
        return out
    be.decode_iter = decode_iter
    rng = np.random.default_rng(0)
    eng.submit([Request(rid=i, prompt_len=int(rng.integers(8, 150)),
                        max_new_tokens=6, arrival=0.02 * i,
                        task_type=TaskType.ONLINE) for i in range(5)])
    assert len(eng.run(max_wall_s=300)) == 5
    assert steps and all(steps)
    in_place = [e["args"]["in_place"] for e in tracer.events
                if e["name"] == "kv.pool_in_place"]
    dispatches = [e for e in tracer.events
                  if e["name"] == "serve.decode.dispatch"]
    assert len(in_place) == len(dispatches) == len(steps)
    assert set(in_place) == {1}


def test_a_spilled_page_survives_the_step_that_consumes_its_pool():
    """A spill captures its page as slices of the pool leaves; the next
    decode steps donate those leaves, and the capture still restores
    into another page bit for bit, which later steps leave alone."""
    cfg = get_smoke_config("qwen3-14b", max_seq_len=S)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params, session_ttl=1000.0,
                  host_pool_tokens=4 * PAGE)
    be = eng.backend
    be.begin([])
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 16))
    be.pool_cache["groups"] = jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape).astype(a.dtype),
        be.pool_cache["groups"])
    kv = lambda: jax.tree.leaves(split_pool(cfg, be.pool_cache)[1])
    src, dst = 3, 5
    want = [np.asarray(x[:, src]) for x in kv()]
    copier = be.kv.retention.copier
    copier.spill(src, 0)
    for _ in range(2):
        be.decode_iter([], 0)
    copier.poll()
    copier.restore(0, dst)
    copier.poll()
    be.decode_iter([], 0)
    for x, w in zip(kv(), want):
        np.testing.assert_array_equal(np.asarray(x[:, dst]), w)
