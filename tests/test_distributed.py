"""Distributed correctness: sharded execution == single-device oracle.

jax locks the device count at first init, so these tests run their
bodies in a fresh subprocess with --xla_force_host_platform_device_count
(the dry-run pattern), keeping the main pytest process single-device.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(body: str, n: int = 8, timeout: int = 560):
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_forward_matches_single_device():
    """Dense GQA forward under TP+DP sharding == unsharded result."""
    run_devices("""
        from repro.configs import get_smoke_config
        from repro.models import transformer as tfm
        from repro.sharding import partition
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke_config("yi-6b", n_kv_heads=2, n_heads=4)
        key = jax.random.PRNGKey(0)
        params = tfm.init_params(cfg, key)
        tok = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
        want = tfm.forward(cfg, params, tokens=tok)

        mesh = make_host_mesh(2, 4)
        specs = partition.param_specs(cfg, params, mesh)
        sparams = jax.device_put(params, partition.to_shardings(mesh, specs))
        stok = jax.device_put(tok, NamedSharding(mesh, P("data", None)))
        got = jax.jit(lambda p, t: tfm.forward(cfg, p, tokens=t))(sparams, stok)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-3)
        print("OK")
    """)


def test_moe_ep_train_step_grads_match():
    """EP shard_map MoE train step == local train step (params + loss)."""
    run_devices("""
        from repro.configs import get_smoke_config
        from repro.models import transformer as tfm
        from repro.train import optimizer, train_loop
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke_config("qwen3-moe-235b-a22b", n_experts=4, top_k=2,
                               capacity_factor=4.0)
        key = jax.random.PRNGKey(0)
        params = tfm.init_params(cfg, key)
        opt_cfg = optimizer.AdamWConfig(lr=1e-3, total_steps=4)
        batch = {"tokens": jax.random.randint(key, (4, 24), 0,
                                              cfg.vocab_size)}
        p_ref, _, m_ref = jax.jit(train_loop.make_train_step(
            cfg, opt_cfg, moe_impl="local"))(params, optimizer.init(params),
                                             batch)
        mesh = make_host_mesh(2, 4)
        p_ep, _, m_ep = jax.jit(train_loop.make_train_step(
            cfg, opt_cfg, moe_impl="ep", mesh=mesh))(
                params, optimizer.init(params), batch)
        np.testing.assert_allclose(float(m_ep["loss"]), float(m_ref["loss"]),
                                   rtol=1e-4)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_ep)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-4, rtol=5e-3)
        print("OK")
    """)


def test_decode_with_sharded_cache_matches():
    """Decode step with a model/data-sharded KV cache == unsharded."""
    run_devices("""
        from repro.configs import get_smoke_config
        from repro.models import transformer as tfm
        from repro.sharding import partition
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke_config("yi-6b", n_kv_heads=4, n_heads=4,
                               max_seq_len=64)
        key = jax.random.PRNGKey(0)
        params = tfm.init_params(cfg, key)
        tok = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
        logits, cache = tfm.prefill(cfg, params, tokens=tok, cache_len=64)
        nt = logits.argmax(-1).astype(jnp.int32)
        want, _ = tfm.decode_step(cfg, params, nt, cache)

        mesh = make_host_mesh(2, 4)
        pspec = partition.param_specs(cfg, params, mesh)
        cspec = partition.cache_specs(cfg, cache, mesh, 4)
        sp = jax.device_put(params, partition.to_shardings(mesh, pspec))
        sc = jax.device_put(cache, partition.to_shardings(mesh, cspec))
        st = jax.device_put(nt, NamedSharding(mesh, P("data")))
        got, _ = jax.jit(lambda p, t, c: tfm.decode_step(cfg, p, t, c))(
            sp, st, sc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-3)
        print("OK")
    """)


def test_chunk_kernel_under_mesh_matches_jnp_path():
    """Chained prefill chunks on a (1, 4) mesh, the batch cache's 4 KV
    heads split over ``model`` as qwen3-14b's 8 are: with the kernel path
    taken (in the Pallas interpreter, once per device under shard_map,
    blocks of 64 slots) the logits and the cache match the jnp path's on
    the same mesh, to bf16 rounding (within 0.1, as on one device), and
    the chunk step's program holds the kernel inside a shard_map."""
    run_devices("""
        from repro.configs import get_smoke_config
        from repro.kernels import chunk_attn
        from repro.launch.mesh import make_host_mesh
        from repro.models import attention
        from repro.models import transformer as tfm
        from repro.sharding import context as shctx
        from repro.sharding import partition

        cfg = get_smoke_config("qwen3-14b", n_heads=8, n_kv_heads=4,
                               max_seq_len=256)
        bf = jnp.bfloat16
        params = tfm.init_params(cfg, jax.random.PRNGKey(0), dtype=bf)
        mesh = make_host_mesh(1, 4)
        shctx.set_mesh(mesh)
        chunk_attn.BLOCK = 64
        B, S, C = 2, 256, 64
        sp = jax.device_put(params, partition.to_shardings(
            mesh, partition.param_specs(cfg, params, mesh)))
        cache0 = tfm.init_cache(cfg, B, S, bf)
        layout = partition.to_shardings(
            mesh, partition.cache_specs(cfg, cache0, mesh, B))
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab_size)
        lens = jnp.array([200, 90], jnp.int32)

        def chunks():
            step = jax.jit(lambda p, t, c, s, n: tfm.prefill_chunk(
                cfg, p, t, c, s, n), out_shardings=(None, layout))
            cache = jax.device_put(cache0, layout)
            out = []
            for s in range(0, S, C):
                args = (sp, toks[:, s:s + C], cache, s, lens)
                logits, cache = step(*args)
                fin = np.asarray((lens - 1 >= s) & (lens - 1 < s + C))
                out.append(np.asarray(logits, np.float32)[fin])
            return out, cache, str(jax.make_jaxpr(step)(*args))

        want, want_cache, jaxpr = chunks()
        assert "pallas_call" not in jaxpr
        attention.uses_chunk_kernel = lambda c, d: True
        got, got_cache, jaxpr = chunks()
        assert "shard_map" in jaxpr and "pallas_call" in jaxpr
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=0.1, rtol=0)
        for a, b in zip(jax.tree.leaves(got_cache),
                        jax.tree.leaves(want_cache)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=0.1, rtol=0)
        print("OK")
    """, n=4)


def test_mesh_serving_shards_pool_and_matches_forward():
    """chip_smoke.py's four-chip phase at smoke widths (with 4 KV heads,
    so they divide the model axis as qwen3-14b's 8 do): serve.py --model 4
    lays the paged pool out over all four devices (not replicated), serves
    every request in full, and agrees with the cache-free forward."""
    out = run_devices(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        from repro.configs import get_smoke_config
        from repro.launch import serve
        serve.get_smoke_config = lambda arch, **kw: get_smoke_config(
            arch, n_heads=8, n_kv_heads=4, **kw)
        chip_smoke.PHASES[4] += ["--smoke"]
        chip_smoke.run(4)
        print("OK")
    """, n=4)
    assert "per-device shard" in out and "0 mismatches" in out


def test_paged_serving_on_data_and_model_mesh():
    """serve.py --data 2 --model 2 --paged: the page pool keeps its pages
    whole on every device (the block tables index them globally) with KV
    heads over ``model``, and every request is served in full."""
    run_devices("""
        from repro.launch import serve
        engine = serve.main(["--smoke", "--data", "2", "--model", "2",
                             "--paged", "--chunk", "64", "--slots", "4",
                             "--requests", "6", "--dataset", "mixed",
                             "--rps", "1000"])
        for r in engine.requests:
            assert len(engine.outputs[r.rid]) == r.max_new_tokens, r.rid
        cfg = engine.cfg
        k = engine.backend.pool_cache["groups"][0][0]["k"]
        heads = cfg.n_kv_heads // 2 if cfg.n_kv_heads % 2 == 0 \
            else cfg.n_kv_heads
        assert k.sharding.shard_shape(k.shape) == k.shape[:3] + (
            heads, cfg.d_head), k.sharding
        print("OK")
    """, n=4)


def test_production_mesh_shapes():
    run_devices("""
        from repro.launch.mesh import make_production_mesh, batch_axes
        m1 = make_production_mesh()
        assert dict(m1.shape) == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        assert batch_axes(m2, 256) == ("pod", "data")
        assert batch_axes(m2, 16) is None or batch_axes(m2, 16) == "pod"
        assert batch_axes(m1, 1) is None
        print("OK")
    """, n=512)


def test_distributed_flash_decode_matches_oracle():
    """Segmented-softmax decode over a seq-sharded cache == local decode."""
    run_devices("""
        from repro.models import attention
        from repro.launch.mesh import make_host_mesh
        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        B, S, H, Hkv, Dh = 2, 64, 8, 2, 32
        q = jax.random.normal(ks[0], (B, 1, H, Dh))
        kc = jax.random.normal(ks[1], (B, S, Hkv, Dh))
        vc = jax.random.normal(ks[2], (B, S, Hkv, Dh))
        pos = jnp.array([40, 17], jnp.int32)
        want = attention.decode_attention(q, kc, vc, pos)
        mesh = make_host_mesh(2, 4)
        got = jax.jit(lambda *a: attention.distributed_decode_attention(
            *a, mesh))(q, kc, vc, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)
        # ring-cache variant, pos beyond S
        pos2 = jnp.array([130, 31], jnp.int32)
        want2 = attention.decode_attention(q, kc, vc, pos2, window=S)
        got2 = jax.jit(lambda *a: attention.distributed_decode_attention(
            *a, mesh, window=S))(q, kc, vc, pos2)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                                   atol=1e-5, rtol=1e-4)
        print("OK")
    """)
