"""Compile-for-v5e guards: the TPU compiler, run here against a DESCRIBED
v5e that is not attached (nothing executes).

Interpret-mode tests cannot see what the chip's compiler refuses: block
shapes off the (8, 128) tiling, rank-1 blocks, or a step that does not
fit the device's memory.  These tests lower the five Pallas kernels at
real widths with ``interpret=False``, and the full-width serving steps at
``chip_smoke.py``'s sizing (yi-6b on one chip, qwen3-14b over four), and
read the compiler's own memory analysis.  The chunk step takes its TPU
path here (``jax.default_backend`` patched to report the TPU, as the
chip would): its attention is the Pallas chunk kernel.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, so a test worker
that is not given this file must never touch it.
"""
import functools
import importlib.util
import json
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core import paging
from repro.core.engine import insert_pages_jit, paged_decode_jit, split_pool
from repro.kernels.chunk_attn import chunk_attention
from repro.kernels.decode_attn import flash_decode
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_decode_attn import paged_flash_decode
from repro.kernels.wkv6 import wkv6
from repro.launch.hlo_analysis import parse_module
from repro.models import transformer as tfm
from repro.sharding import context as shctx
from repro.sharding import partition

V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)     # a v5e's reported bytes_limit
ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# yi-6b attention widths: H=32, Hkv=4, Dh=128, 4,096-token context
B, H, HKV, DH, S, PAGE = 8, 32, 4, 128, 4096, 128


def _kernel_case(name, sh):
    bf = jnp.bfloat16
    i32 = jnp.int32
    if name == "flash_prefill":
        return (lambda q, k, v, lens: flash_prefill(q, k, v, lens,
                                                    interpret=False),
                _sds((2, 512, H, DH), bf, sh), _sds((2, 512, HKV, DH), bf, sh),
                _sds((2, 512, HKV, DH), bf, sh), _sds((2,), i32, sh))
    if name == "flash_decode":
        return (lambda q, k, v, pos: flash_decode(q, k, v, pos,
                                                  interpret=False),
                _sds((B, H, DH), bf, sh), _sds((B, S, HKV, DH), bf, sh),
                _sds((B, S, HKV, DH), bf, sh), _sds((B,), i32, sh))
    if name.startswith("chunk_attention"):
        # a 128-token chunk against the stacked batch cache, one layer
        # read: yi-6b's 4 rows, or qwen3-14b's 2 slots on one of four
        # chips (10 query heads over 2 KV heads, 32,768 slots)
        L, b, s, h, hkv = ((32, 4, S, H, HKV) if name == "chunk_attention"
                           else (40, 2, 32768, 10, 2))
        return (lambda q, k, v, st, l: chunk_attention(
                    q, k, v, st, layer=l, interpret=False),
                _sds((b, 128, h, DH), bf, sh),
                _sds((L, b, s, hkv, DH), bf, sh),
                _sds((L, b, s, hkv, DH), bf, sh), _sds((), i32, sh),
                _sds((), i32, sh))
    if name == "paged_flash_decode":
        n_pages = B * S // PAGE + 1
        return (lambda q, k, v, bt, pos: paged_flash_decode(
                    q, k, v, bt, pos, s_len=S, interpret=False),
                _sds((B, H, DH), bf, sh), _sds((n_pages, PAGE, HKV, DH), bf, sh),
                _sds((n_pages, PAGE, HKV, DH), bf, sh),
                _sds((B, S // PAGE), i32, sh), _sds((B,), i32, sh))
    # rwkv6-3b widths: 40 heads of size 64
    hw, hs, t = 40, 64, 256
    x = _sds((1, t, hw, hs), bf, sh)
    return (lambda r, k, v, w, u, s0: wkv6(r, k, v, w, u, s0,
                                           interpret=False),
            x, x, x, x, _sds((hw, hs), jnp.float32, sh),
            _sds((1, hw, hs, hs), jnp.float32, sh))


@pytest.mark.parametrize("name", ["flash_prefill", "flash_decode",
                                  "paged_flash_decode", "wkv6",
                                  "chunk_attention", "chunk_attention_tp4"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, *args = _kernel_case(name, one_chip)
    hlo = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in the HLO"


def _smoke_sizing(chips: int = 1):
    """(slots, pool tokens) of one of chip_smoke.py's phases."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    argv = mod.PHASES[chips]
    return (int(argv[argv.index("--slots") + 1]),
            int(argv[argv.index("--pool-tokens") + 1]))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _chunk_attention_in_place(compiled, cfg, devices: int = 1) -> None:
    """The chunk step's attention is the Pallas kernel, reading the batch
    cache where it lies: the program holds the kernel, no float32 buffer
    the size of a row's [Tc x cache_len] scores (the jnp path's, with the
    chunk and the cache length both among its dims), and no copy of a
    batch-cache leaf in another layout than the step's own input."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    S, tc = cfg.max_seq_len, 128
    tail = [S, cfg.n_kv_heads // devices, cfg.d_head]
    scores = tc * S * cfg.n_heads // devices
    comps, _ = parse_module(text)
    fused = {m for c in comps.values() for i in c.instrs if i.op == "fusion"
             for m in re.findall(r"calls=%?([\w.\-]+)", i.rest)}
    layouts = set()
    for name, comp in comps.items():
        if name in fused:
            continue
        for ins in comp.instrs:
            for dtype, dims, layout in re.findall(
                    r"(\w+)\[([\d,]*)\](\{[^}]*\})?", ins.shape):
                dims = [int(d) for d in dims.split(",") if d]
                n = int(np.prod(dims)) if dims else 0
                assert not (dtype == "f32" and S in dims and tc in dims
                            and n >= scores), (ins.name, ins.shape)
                if dims[-3:] == tail and layout:
                    # minor-to-major of the last three dims, and the tiling
                    order, _, tiles = layout.strip("{}").partition(":")
                    layouts.add((tuple(int(d) - len(dims) for d in
                                       order.split(",")[:3]),
                                 re.sub(r"S\(\d+\)", "", tiles)))
    assert len(layouts) == 1, layouts


@pytest.fixture
def tpu_backend(monkeypatch):
    """Code that asks ``jax.default_backend`` takes its TPU branch (and
    the kernels compile, not interpret), as on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def yi6b(one_chip):
    cfg = get_config("yi-6b")
    make = functools.partial(tfm.init_params, cfg, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(make, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), shapes)
    return cfg, params


def _on(tree, sh):
    return jax.tree.map(lambda x: _sds(x.shape, x.dtype, sh), tree)


def _paged_decode(cfg, params, tok, cache, layout=None):
    """The paged decode step compiled as the engine jits it, its page
    pool donated; also the pool's bytes and its largest leaf's."""
    rest, pool = split_pool(cfg, cache)
    step = paged_decode_jit(cfg, moe_impl="local", page_size=PAGE,
                            paged_len=cfg.max_seq_len, layout=layout)
    leaves = [x.size * x.dtype.itemsize for x in jax.tree.leaves(pool)]
    return step.lower(params, tok, rest, pool).compile(), sum(leaves), \
        max(leaves)


def _in_place(compiled, pool_bytes: int, leaf_bytes: int) -> None:
    """The step writes the pool in place: its output aliases the whole
    donated pool, and it keeps no pool-sized buffer of its own (donation
    alone, with the pool scanned as xs/ys, kept a second pool here)."""
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < leaf_bytes


def test_full_width_paged_decode_step_fits_v5e(yi6b, one_chip):
    """yi-6b bf16 paged decode at chip_smoke's pool: weights and the
    pool, donated and updated in place, stay under the chip's
    bytes_limit."""
    cfg, params = yi6b
    slots, pool_tokens = _smoke_sizing()
    S = cfg.max_seq_len
    n_pages = paging.device_pool_pages(cfg, pool_tokens, PAGE)
    cache = _on(jax.eval_shape(lambda: tfm.init_paged_cache(
        cfg, slots, S, n_pages, PAGE, jnp.bfloat16)), one_chip)
    tok = _sds((slots,), jnp.int32, one_chip)
    step, pool_bytes, leaf_bytes = _paged_decode(cfg, params, tok, cache)
    _in_place(step, pool_bytes, leaf_bytes)
    assert _device_bytes(step) < V5E_BYTES_LIMIT


def test_full_width_prefill_chunk_fits_v5e(yi6b, one_chip, tpu_backend):
    """The largest serving step: a 128-token prefill chunk for a full
    batch of chip_smoke's slots over 4,096-token bf16 caches (its input
    and its fresh output), with the resident decode pool counted on top.
    The engine awaits a chunk's input cache before it enqueues the step,
    so no other batch cache is live beside them.  Its attention is the
    chunk kernel over the cache as it lies."""
    cfg, params = yi6b
    slots, pool_tokens = _smoke_sizing()
    S = cfg.max_seq_len
    shapes = jax.eval_shape(lambda: tfm.init_cache(cfg, slots, S,
                                                   jnp.bfloat16))
    step = _compile(
        lambda p, t, c, s, n: tfm.prefill_chunk(cfg, p, t, c, s, n),
        params, _sds((slots, 128), jnp.int32, one_chip),
        _on(shapes, one_chip), _sds((), jnp.int32, one_chip),
        _sds((slots,), jnp.int32, one_chip))
    pool = paging.device_pool_pages(cfg, pool_tokens, PAGE) * PAGE \
        * cfg.cache_bytes_per_token()
    assert _device_bytes(step) + pool < V5E_BYTES_LIMIT
    _chunk_attention_in_place(step, cfg)


class _FourChips:
    """qwen3-14b at its published widths and 32,768-token context on a
    described v5e 2x2, as a (1, 4) mesh laid out by
    sharding/partition.py: the weights, and the caches a sizing of
    (slots, pool tokens) gives, as shapes on the described devices."""

    def __init__(self, topo, slots: int, pool_tokens: int):
        self.mesh = mesh = Mesh(np.array(topo.devices).reshape(1, 4),
                                ("data", "model"),
                                axis_types=(AxisType.Auto,) * 2)
        self.cfg = cfg = get_config("qwen3-14b")
        self.slots, S = slots, cfg.max_seq_len
        self.rep = NamedSharding(mesh, PartitionSpec())
        make = functools.partial(tfm.init_params, cfg, dtype=jnp.bfloat16)
        shapes = jax.eval_shape(make, jax.random.PRNGKey(0))
        self.params = self.placed(shapes,
                                  partition.param_specs(cfg, shapes, mesh))
        n_pages = paging.device_pool_pages(cfg, pool_tokens, PAGE)
        pool = jax.eval_shape(lambda: tfm.init_paged_cache(
            cfg, slots, S, n_pages, PAGE, jnp.bfloat16))
        self.layout = partition.to_shardings(
            mesh, partition.paged_cache_specs(cfg, pool, mesh, slots))
        self.pool = jax.tree.map(lambda x, s: _sds(x.shape, x.dtype, s),
                                 pool, self.layout)
        batch = jax.eval_shape(lambda: tfm.init_cache(cfg, slots, S,
                                                      jnp.bfloat16))
        self.batch = self.placed(batch, partition.cache_specs(
            cfg, batch, mesh, slots))

    def placed(self, tree, specs):
        return jax.tree.map(
            lambda x, s: _sds(x.shape, x.dtype, NamedSharding(self.mesh, s)),
            tree, specs)

    @staticmethod
    def per_device(tree) -> float:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(tree)) / 4

    def decode(self):
        return _paged_decode(self.cfg, self.params,
                             _sds((self.slots,), jnp.int32, self.rep),
                             self.pool, self.layout)

    def chunk(self):
        """The 128-token chunk step over a full batch of slots, the mesh
        registered as the server registers it: its input batch cache and
        its fresh output are both live."""
        cfg, rep = self.cfg, self.rep
        was = shctx.get_mesh()
        shctx.set_mesh(self.mesh)
        try:
            return _compile(
                lambda p, t, c, s, n: tfm.prefill_chunk(cfg, p, t, c, s, n),
                self.params, _sds((self.slots, 128), jnp.int32, rep),
                self.batch, _sds((), jnp.int32, rep),
                _sds((self.slots,), jnp.int32, rep))
        finally:
            shctx.set_mesh(was)


def test_four_chip_steps_fit_v5e(topo, tpu_backend):
    """chip_smoke.py --chips 4: qwen3-14b at its published 32,768-token
    context on a (1, 4) mesh, laid out by sharding/partition.py.  Per
    device, the paged decode step (its pool shard donated and updated in
    place), the 128-token chunk step over a full batch of slots and the
    forward recompute, each with the resident pool, stay under
    bytes_limit.  The chunk step's attention is the chunk kernel, once
    per device on its own KV heads, over the cache shard as it lies."""
    four = _FourChips(topo, *_smoke_sizing(chips=4))
    cfg, params, rep = four.cfg, four.params, four.rep
    S = cfg.max_seq_len
    decode, pool_bytes, leaf_bytes = four.decode()
    _in_place(decode, pool_bytes // 4, leaf_bytes // 4)
    assert _device_bytes(decode) < V5E_BYTES_LIMIT
    pool_per_device = four.per_device(four.pool)
    chunk = four.chunk()
    assert _device_bytes(chunk) + pool_per_device < V5E_BYTES_LIMIT
    _chunk_attention_in_place(chunk, cfg, devices=4)
    # chip_smoke's forward recompute of a full-context request, logits
    # taken only at its served positions, beside the resident pool
    recompute = _compile(
        lambda p, t, at: tfm.forward(cfg, p, tokens=t, at=at),
        params, _sds((1, S), jnp.int32, rep), _sds((8,), jnp.int32, rep))
    assert _device_bytes(recompute) + pool_per_device < V5E_BYTES_LIMIT


def test_longdoc_cell_fits_v5e(topo, tpu_backend):
    """The benchmark's qwen3-14b.longdoc cell (bench/configs/qwen3-14b.json:
    2 slots, a 65,664-token pool, one whole 32,768-token context a slot)
    on a described v5e 2x2.  Per device, what each serving step holds:
    the decode step (weights, pool donated, and a chunked prefill's batch
    cache waiting between chunks), the chunk step (weights, its input and
    output batch caches, the pool) and the page insert (weights, the
    batch cache, the pool donated and written in place, with no second
    pool beside it) stay under bytes_limit."""
    with open(ROOT / "bench" / "configs" / "qwen3-14b.json") as f:
        cell = json.load(f)
    four = _FourChips(topo, cell["slots"], cell["pool_tokens"])
    weights = four.per_device(four.params)
    pool, batch = four.per_device(four.pool), four.per_device(four.batch)
    decode, pool_bytes, leaf_bytes = four.decode()
    _in_place(decode, pool_bytes // 4, leaf_bytes // 4)
    assert _device_bytes(decode) + batch < V5E_BYTES_LIMIT
    chunk = four.chunk()
    assert _device_bytes(chunk) + pool < V5E_BYTES_LIMIT
    _chunk_attention_in_place(chunk, four.cfg, devices=4)
    insert = insert_pages_jit(four.cfg, page_size=PAGE, layout=four.layout)
    maxp = four.cfg.max_seq_len // PAGE
    insert = insert.lower(
        four.pool, four.batch, _sds((four.slots * maxp,), jnp.int32, four.rep),
        _sds((four.slots,), jnp.int32, four.rep)).compile()
    # the output aliases the donated pool; the compiler relays out one
    # pool leaf at a time for the scatter, and holds no second pool
    m = insert.memory_analysis()
    assert m.alias_size_in_bytes >= pool_bytes // 4
    assert m.temp_size_in_bytes < pool_bytes // 4
    assert _device_bytes(insert) + weights < V5E_BYTES_LIMIT
