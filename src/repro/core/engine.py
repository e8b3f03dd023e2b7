"""Real JAX execution backend — BucketServe policies driving actual models.

This is the execution layer the cost model (core/simulator.py) stands in
for at paper scale: at tiny-model scale (CPU) it runs the *same*
scheduler objects against real jitted prefill/decode computations, token
for token.  All orchestration — arrivals, batch formation, OOM/slot
re-queue, chunk interleaving, timing — lives in core/serving_loop.py;
this module only executes.

TPU-native continuous batching (DESIGN.md §3): the decode pool is a
FIXED-CAPACITY slot tensor — cache pytree with a leading slot axis, an
alive mask, and per-slot next-token ids.  Each iteration decodes all
slots (dead slots compute garbage that is masked); completed requests
free their slot and new prefilled requests are scattered in with ONE
batched gather/scatter per cache leaf (not a device round-trip per
request).  Static shapes throughout: one compiled executable per bucket
pad-shape for prefill (bucketing bounds the executable count — the
recompilation argument for bucketing on TPU), one per chunk shape when
chunked prefill is on, one for decode.

Paged decode pool (``paged=True``, DESIGN.md §3): slot KV caches are no
longer preallocated at ``cache_len`` — self-attention K/V live in a
SHARED page pool indexed through per-slot block tables
(``transformer.init_paged_cache`` + ``attention.self_attn_decode_paged``,
Pallas kernel in ``kernels/paged_decode_attn.py``).  A
:class:`~repro.core.paging.BlockAllocator` hands out pages at
prefill-insert, extends tables page by page as decode advances, and
frees on release; the ServingLoop gates admission on free PAGES
(``admit_blocks``) and preempts the youngest pooled request when pages
run out mid-decode (``decode_preempt`` -> requeue).  Dead slots point
at a dedicated trash page so their masked garbage writes can never
corrupt a live request's pages.  Shapes stay static: the pool and the
(slots, pages_per_seq) block table are fixed tensors, so ONE decode
executable serves every allocation layout.  The host-side page policy
— sizing, admission, extension, release, retention — is one
:class:`~repro.core.paging.PagedKv` (``self.kv``), the same class the
cost model holds; this backend adds the device arrays and the slots.

Cross-request prefix cache (``prefix_cache=True``, DESIGN.md §3
"Prefix sharing"): admission matches each prompt against a radix index
of token-id page chunks (``core/prefix_cache.py``); matched FULL pages
are attached to the request's block table BY REFERENCE (the allocator
refcounts pages) and chunked prefill resumes after the cached prefix —
the batch cache is seeded from the pool with the exact inverse of the
insert scatter, so hit-path token ids are bit-identical to a cold run.
At insert, shared prefix pages are never re-scattered; freshly
prefilled full prompt pages are pinned into the index for future hits,
and LRU zero-ref prefixes are evicted when admission or decode
starves.

Session retention (``session_ttl``, DESIGN.md §3 "Session retention"):
release routes through :class:`~repro.core.retention.KvRetention`
instead of freeing unconditionally — a finished request's FULL
transcript pages (prompt AND generated: page content is a pure
function of the token path) extend the radix index, and the partial
tail page stays pinned under the session key with a TTL.  The next
turn of the same conversation re-sends the transcript as its prompt
prefix, matches it at admission (the pinned tail transfers to its
block table at the right virtual index), seeds the batch cache up to
the EXACT unaligned token, and resumes chunked prefill past the
restored transcript — decode then continues into the reused tail page
without a re-scatter of the transcript's pages.

Host spill tier (``host_pool_tokens``, DESIGN.md §3 "Host spill
tier"): retention eviction SPILLS cold retained pages to a host-RAM
pool instead of destroying them — ``_EngineCopier`` captures the
page's K/V as immutable device-side slices at eviction time and
materializes them to host on the next ``maintain`` poll (double
buffered, overlapping decode); a later hit on a spilled path restores
the bytes into a reserved pool page while the ServingLoop parks the
request, so the next turn pays a PCIe copy instead of a re-prefill,
bit-identically.

Chunked prefill (DESIGN.md §2): long prompts are split into
``chunk_tokens``-sized spans; the serving loop interleaves decode
iterations between spans, so a 2k-token prefill no longer stalls every
live decode stream.  The chunk offset is a traced scalar — one compiled
executable serves every offset of a given (chunk_len, batch) shape.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.launch.hlo_analysis import collective_calls
from repro.kernels.chunk_attn import kv_blocks
from repro.models import attention
from repro.models import transformer as tfm
from repro.models.attention import dequantize_kv_int4, quantize_kv_int4
from repro.models.config import BLOCK_ATTN, BLOCK_MOE, ModelConfig
from repro.sharding import context as shctx
from repro.sharding import partition
from . import paging
from .batcher import FormedBatch
from .faults import FaultInjector
from .request import Request
from .serving_loop import (LoopConfig, PrefillJob, ServeResult, ServingLoop,
                           WallClock, plan_chunks)
from .telemetry import NULL_SPAN, NULL_TRACER, ProfilerTracer


def _named(fn, name: str):
    """``fn`` under ``name``, the name its jitted program carries in
    compile events and device traces (a ``functools.partial`` has none
    of its own)."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


def split_pool(cfg: ModelConfig, cache):
    """``(rest, pool)`` of a paged cache, or of any tree shaped like one
    (its shardings): ``pool`` holds the page-pool slots of the attention
    blocks, ``rest`` the positions, the block tables and every other
    slot, each with None where the other holds a slot."""
    def part(in_pool: bool):
        return tuple(tuple(slot if (b in (BLOCK_ATTN, BLOCK_MOE)) == in_pool
                           else None for b, slot in zip(pattern, gslots))
                     for (pattern, _), gslots in zip(cfg.block_groups(),
                                                     cache["groups"]))
    return {**cache, "groups": part(False)}, part(True)


def join_pool(rest, pool):
    """The cache ``rest`` with ``pool``'s slots in place of its own."""
    return {**rest, "groups": tuple(
        tuple(r if p is None else p for r, p in zip(gr, gp))
        for gr, gp in zip(rest["groups"], pool))}


def paged_decode_jit(cfg: ModelConfig, *, moe_impl: str, page_size: int,
                     paged_len: int, layout=None):
    """The paged decode step as the engine runs it: ``(params, tok,
    rest, pool) -> (logits, rest, pool)`` over a cache split by
    ``split_pool``.  ``pool`` is donated, so the step writes each token
    into the page pool in place and hands the same buffers back.
    ``layout`` is the cache's shardings under a mesh (None without one);
    the outputs come back in it, so they alias the inputs."""
    def step(params, tok, rest, pool):
        logits, new = tfm.decode_step(cfg, params, tok, join_pool(rest, pool),
                                      moe_impl=moe_impl, page_size=page_size,
                                      paged_len=paged_len)
        return (logits,) + split_pool(cfg, new)
    out = (None, None, None) if layout is None else \
        (None,) + split_pool(cfg, layout)
    return jax.jit(_named(step, "decode_step"), donate_argnums=(3,),
                   out_shardings=out)


def insert_pages_jit(cfg: ModelConfig, *, page_size: int, layout=None):
    """The insert of one prefill batch into the paged pool as the engine
    runs it: ``(cache, batch, dst, slot_of_row) -> cache``.  ``cache``,
    the whole pool cache, is donated, so the batch's pages are written
    into the pool in place.  ``batch`` is the batch's contiguous cache,
    ``(B, S)`` per attention leaf, seen as ``B x pages_per_seq`` pages:
    page ``i`` goes to pool page ``dst[i]``, and row ``r``'s per-slot
    state and position to slot ``slot_of_row[r]``; an index past the end
    (a row that does not continue, a page it does not hold or shares)
    writes nothing.  The shapes depend on ``B`` alone, so one executable
    serves every insert of a batch size.  ``layout`` is the cache's
    shardings under a mesh (None without one)."""
    def scatter_pages(pool_leaf, batch_leaf, dst):
        reps, B, S = batch_leaf.shape[:3]
        pad = dst.shape[0] // B * page_size - S
        if pad:
            widths = [(0, 0)] * batch_leaf.ndim
            widths[2] = (0, pad)
            batch_leaf = jnp.pad(batch_leaf, widths)
        pages = batch_leaf.reshape((reps, dst.shape[0], page_size)
                                   + batch_leaf.shape[3:])
        return pool_leaf.at[:, dst].set(pages, mode="drop")

    def insert(cache, batch, dst, slot_of_row):
        def per_slot(pool_leaf, batch_leaf):
            return pool_leaf.at[:, slot_of_row].set(batch_leaf, mode="drop")
        groups = tuple(
            tuple({k: scatter_pages(ps[k], bs[k], dst) for k in ps}
                  if b in (BLOCK_ATTN, BLOCK_MOE)
                  else jax.tree.map(per_slot, ps, bs)
                  for b, ps, bs in zip(pattern, pslots, bslots))
            for (pattern, _), pslots, bslots in zip(
                cfg.block_groups(), cache["groups"], batch["groups"]))
        pos = cache["pos"].at[slot_of_row].set(batch["pos"], mode="drop")
        return {**cache, "pos": pos, "groups": groups}
    return jax.jit(_named(insert, "insert_pages"), donate_argnums=(0,),
                   out_shardings=layout)


class _BlockTableMirror:
    """Host mirror of the device block-table tensor.

    ``decode_preempt`` used to rescan every pooled request's FULL table
    with ``np.array_equal`` on every dispatch — O(pool x pages_per_seq)
    int32 compares per decode iteration whether or not anything grew.
    The mirror tracks how many pages per rid are already uploaded and
    writes only the newly appended suffix, so a steady-state iteration
    where one request crosses a page boundary costs ONE cell write.
    ``writes`` counts int32 cells written — the timing-free regression
    hook tests compare against the rescanning reference."""

    def __init__(self, n_slots: int, pages_per_seq: int, trash: int):
        self.host = np.full((n_slots, pages_per_seq), trash, np.int32)
        self.trash = trash
        self.dirty = False
        self._uploaded: Dict[int, int] = {}     # rid -> pages uploaded
        self.writes = 0

    def insert(self, slot: int, rid: int, table: Sequence[int]) -> None:
        """A freshly prefilled request lands in ``slot``: full-row
        write (its pages are all new to the device tensor)."""
        self.host[slot] = self.trash
        self.host[slot, :len(table)] = table
        self.writes += self.host.shape[1]
        self._uploaded[rid] = len(table)
        self.dirty = True

    def clear(self, slot: int, rid: int) -> None:
        self.host[slot] = self.trash
        self._uploaded.pop(rid, None)
        self.writes += self.host.shape[1]
        self.dirty = True

    def forget(self, rid: int) -> None:
        self._uploaded.pop(rid, None)

    def sync(self, slot: int, rid: int, alloc) -> None:
        """Write only the pages appended since the last upload —
        O(growth), not O(table)."""
        n0 = self._uploaded.get(rid, 0)
        n1 = alloc.table_len(rid)
        if n1 > n0:
            self.host[slot, n0:n1] = alloc.table_tail(rid, n0)
            self.writes += n1 - n0
            self._uploaded[rid] = n1
            self.dirty = True


class _EngineCopier:
    """Host<->device KV page mover for the real engine — the data half
    of the spill tier (the retention layer makes every DECISION; this
    object only moves bytes bit-exactly).

    Double-buffered spill: ``spill`` captures the page's K/V as
    device-side slices (JAX arrays are immutable values, so the capture
    is safe the moment it is dispatched — the freed page can be
    reallocated and overwritten without corrupting it) and the
    device->host materialization into the preallocated host pool is
    deferred to ``poll``, which the retention tick calls once per loop
    iteration — so the copy overlaps decode instead of blocking the
    step that evicted the page.  ``restore`` scatters the host copy
    back into the reserved pool page at initiation (a functional
    ``.at[].set`` — by the time the held request prefills, the gather
    in ``_seed_prefix`` reads values bit-identical to the ones
    spilled).

    Quantized spill (``spill_dtype``, DESIGN.md §3 "Tier precision"):
    the device->host materialization COMPRESSES the K/V payload leaves
    ("k"/"v") to the spill dtype — int8 (one scale per token-head row,
    same rule as ``attention.quantize_kv``) or int4 (two values packed
    per byte, ``attention.quantize_kv_int4``) — with the f32 per-page
    scale planes stored alongside the slot; the restore path
    dequantizes back to the pool leaf's dtype.  Two lossless special
    cases anchor the bit-accuracy story: bf16 spill is a raw
    pass-through of every leaf (pre-quantization behavior), and an
    int8 HOT pool's already-int8 leaves pass through an int8 spill
    tier untouched (re-quantizing integer codes would NOT round-trip).
    The pool's own "k_s"/"v_s" scale planes are always raw — they ARE
    the precision bookkeeping."""

    _Q_KEYS = ("k", "v")                    # payload leaves; scales raw

    def __init__(self, backend: "JaxEngineBackend", host_pages: int,
                 spill_dtype: str = ""):
        self.be = backend
        self.host_pages = host_pages
        self.spill_dtype = spill_dtype
        self._host: Dict[tuple, np.ndarray] = {}
        self._scales: Dict[tuple, np.ndarray] = {}  # compressed leaves only
        self._staged: Dict[int, list] = {}      # hslot -> [(leafkey, slice)]
        self._pending: List[Tuple[int, int]] = []   # (hslot, dest page)

    def _attn_leaves(self):
        for gi, (pattern, reps) in enumerate(self.be.cfg.block_groups()):
            for j, btype in enumerate(pattern):
                if btype in (BLOCK_ATTN, BLOCK_MOE):
                    slot = self.be.pool_cache["groups"][gi][j]
                    for k, leaf in slot.items():
                        yield (gi, j, k), leaf

    def _host_leaf(self, lk: tuple, like) -> np.ndarray:
        h = self._host.get(lk)
        if h is None:
            h = np.zeros((like.shape[0], self.host_pages) + like.shape[1:],
                         dtype=like.dtype)
            self._host[lk] = h
        return h

    def _scale_leaf(self, lk: tuple, like) -> np.ndarray:
        s = self._scales.get(lk)
        if s is None:
            s = np.zeros((like.shape[0], self.host_pages) + like.shape[1:],
                         np.float32)
            self._scales[lk] = s
        return s

    def _quantizes(self, lk: tuple, dtype) -> bool:
        """Does this leaf compress on spill?  Deterministic per leaf for
        the whole run — the restore path keys off the same rule."""
        if self.spill_dtype in ("", "bf16") or lk[2] not in self._Q_KEYS:
            return False
        if self.spill_dtype == "int8" and dtype == np.int8:
            return False                    # int8 pool: lossless pass-through
        return True

    def _materialize(self, hslot: int, lk: tuple, sl) -> None:
        arr = np.asarray(sl)
        if not self._quantizes(lk, arr.dtype):
            self._host_leaf(lk, arr)[:, hslot] = arr
            return
        x = arr.astype(np.float32)
        if self.spill_dtype == "int4":
            payload, scale = quantize_kv_int4(x)
        else:                               # int8 spill of a float pool
            scale = np.maximum(np.abs(x).max(axis=-1) / 127.0,
                               1e-8).astype(np.float32)
            payload = np.clip(np.rint(x / scale[..., None]),
                              -127, 127).astype(np.int8)
        self._host_leaf(lk, payload)[:, hslot] = payload
        self._scale_leaf(lk, scale)[:, hslot] = scale

    def _decompress(self, lk: tuple, src: np.ndarray, hslots: List[int],
                    leaf) -> np.ndarray:
        """Invert ``_materialize`` for a batch of host slots; the
        target is the pool leaf's own dtype (int4->int8 re-expands the
        integer codes, everything else lands on the float cache
        dtype)."""
        if lk not in self._scales:
            return src                      # raw pass-through leaf
        scale = self._scales[lk][:, hslots]
        if self.spill_dtype == "int4":
            x = dequantize_kv_int4(src, scale, leaf.shape[-1])
        else:
            x = src.astype(np.float32) * scale[..., None]
        if leaf.dtype == np.int8:
            return np.clip(np.rint(x), -127, 127).astype(np.int8)
        return x.astype(leaf.dtype)

    def spill(self, page: int, hslot: int) -> None:
        self._staged[hslot] = [(lk, leaf[:, page])
                               for lk, leaf in self._attn_leaves()]

    def poll(self) -> None:
        """Drain both directions (called by the retention tick, between
        device steps): staged spills materialize to host RAM, then
        pending restores scatter back with ONE batched pool update per
        leaf — a per-page functional ``.at[].set`` would copy the whole
        pool once per restored page.  The retention layer guarantees a
        restore's pages are never read before its modeled completion,
        and completion is polled through this same tick, so the scatter
        always lands before the held request's prefill gathers it."""
        for hslot, slices in self._staged.items():
            for lk, sl in slices:
                self._materialize(hslot, lk, sl)
        self._staged.clear()
        if not self._pending:
            return
        hslots = [h for h, _ in self._pending]
        dst = jnp.asarray([p for _, p in self._pending], jnp.int32)
        self._pending = []
        be = self.be
        new_groups = []
        for gi, (pattern, reps) in enumerate(be.cfg.block_groups()):
            slots_out = []
            for j, btype in enumerate(pattern):
                slot = be.pool_cache["groups"][gi][j]
                if btype in (BLOCK_ATTN, BLOCK_MOE):
                    out = {}
                    for k, leaf in slot.items():
                        lk = (gi, j, k)
                        src = self._decompress(lk, self._host[lk][:, hslots],
                                               hslots, leaf)
                        out[k] = leaf.at[:, dst].set(jnp.asarray(src))
                    slots_out.append(out)
                else:
                    slots_out.append(slot)
            new_groups.append(tuple(slots_out))
        be.pool_cache = {**be.pool_cache, "groups": tuple(new_groups)}

    def drop(self, hslot: int) -> None:
        self._staged.pop(hslot, None)   # host cells just become garbage

    def restore(self, hslot: int, page: int) -> None:
        self._pending.append((hslot, page))


class JaxEngineBackend:
    """ExecutionBackend over jitted prefill/decode on the local device."""

    prefill_needs_slots = True
    # armed by the ServingLoop when the scheduler is slack-aware: a
    # CLOCK-FREE key (Request -> seconds) preferring the victim with
    # the most remaining deadline slack (DESIGN.md §8)
    slack_of = None
    # host spans of the protocol calls (core/telemetry.py); the
    # ServingEngine hands over its loop's tracer
    tracer = NULL_TRACER

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 8,
                 cache_len: Optional[int] = None, moe_impl: str = "local",
                 time_scale: float = 1.0,
                 chunk_tokens: Optional[int] = None,
                 paged: bool = False, page_size: int = 128,
                 kv_pool_tokens: Optional[int] = None,
                 prefix_cache: bool = False,
                 session_ttl: Optional[float] = None,
                 host_pool_tokens: Optional[int] = None,
                 spill_bw: float = 16e9,
                 spill_dtype: str = ""):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.cache_len = cache_len or cfg.max_seq_len
        self.moe_impl = moe_impl
        self.chunk_tokens = chunk_tokens
        self.clock = WallClock(time_scale)
        self.supports_decode = cfg.has_decode
        self.flops_per_token = 2.0 * cfg.active_param_count()
        # caches take the weights' dtype (the int8 pool variant aside):
        # a bf16 model serves from a bf16 KV cache
        self.kv_dtype = params["embed"].dtype
        assert paged or not (prefix_cache or session_ttl is not None
                             or host_pool_tokens), \
            "KV retention and its host tier ride on the paged KV pool"
        # the host-side page book; None exactly when the pool is not paged
        self.kv: Optional[paging.PagedKv] = None
        if paged:
            assert tfm.supports_paged_decode(cfg), \
                f"{cfg.name}: paged KV needs self-attention decode"
            # by default the same HBM BYTE budget as a contiguous bf16
            # pool of max_slots
            self.kv = kv = paging.PagedKv(
                cfg, page_size, self.cache_len, pool_tokens=kv_pool_tokens,
                default_tokens=max_slots * cfg.attn_cache_len(self.cache_len),
                prefix_cache=prefix_cache, session_ttl=session_ttl,
                host_pool_tokens=host_pool_tokens, spill_bw=spill_bw,
                spill_dtype=spill_dtype)
            self.trash_page = kv.alloc.n_pages   # pool index n_pages
            make = functools.partial(
                tfm.init_paged_cache, cfg, max_slots, self.cache_len,
                kv.alloc.n_pages + 1, page_size, self.kv_dtype)
            self._pool_layout = self._layout(make, max_slots,
                                             partition.paged_cache_specs)
            self.pool_cache = jax.jit(_named(make, "init_kv_pool"),
                                      out_shardings=self._pool_layout)()
            self._bt = _BlockTableMirror(max_slots, kv.pages_per_seq,
                                         self.trash_page)
            self._upload_tables()
            if kv.alloc.host_pages:
                kv.retention.copier = _EngineCopier(
                    self, kv.alloc.host_pages, spill_dtype)
            self._decode_step = paged_decode_jit(
                cfg, moe_impl=moe_impl, page_size=page_size,
                paged_len=kv.cap, layout=self._pool_layout)
            self._insert = insert_pages_jit(cfg, page_size=page_size,
                                            layout=self._pool_layout)
            self._decode_fn = self._decode_in_place
        else:
            make = functools.partial(tfm.init_cache, cfg, max_slots,
                                     self.cache_len, self.kv_dtype)
            self._pool_layout = self._layout(make, max_slots)
            self.pool_cache = jax.jit(_named(make, "init_kv_pool"),
                                      out_shardings=self._pool_layout)()
            step = functools.partial(tfm.decode_step, cfg, moe_impl=moe_impl)
            # the step hands the pool back in the layout it was built in,
            # so one decode executable serves every step
            self._decode_step = self._decode_fn = jax.jit(
                _named(step, "decode_step"),
                out_shardings=(None, self._pool_layout))
        mesh = shctx.get_mesh()
        self._tok_layout = (None if mesh is None
                            else NamedSharding(mesh, PartitionSpec()))
        self._mesh = mesh
        # (collectives, bytes) per call of each step program under a
        # mesh, read once from its compiled HLO (``_trace_collectives``)
        self._collectives: Dict[tuple, Tuple[int, int]] = {}
        self._batch_layouts: Dict[int, object] = {}
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        self._slot_of: Dict[int, int] = {}
        self.next_tok = jnp.zeros((max_slots,), jnp.int32)
        self.outputs: Dict[int, List[int]] = {}
        self._prefill_fns: Dict[tuple, callable] = {}
        self.n_prefill_shapes = 0

    def _layout(self, make, batch: int, specs=partition.cache_specs):
        """Under a registered mesh, the shardings ``specs`` gives the
        cache ``make()`` returns (KV heads over ``model``); a cache built
        with them as ``out_shardings`` is made in place, so no device ever
        holds the whole of it.  None without a mesh."""
        mesh = shctx.get_mesh()
        if mesh is None:
            return None
        return partition.to_shardings(
            mesh, specs(self.cfg, jax.eval_shape(make), mesh, batch))

    @staticmethod
    def _placed(tree, layout):
        """``tree`` in ``layout`` (a no-op where it is already there, and
        without a mesh): an eager update between steps may leave a leaf
        laid out otherwise, and a jitted step compiles anew for each input
        layout it sees."""
        return tree if layout is None else jax.device_put(tree, layout)

    def compiled_shapes(self) -> Tuple[int, int]:
        """(prefill, decode) executables compiled so far, read from the
        jit caches: a retrace shows here even where a shape's key does
        not change."""
        prefill = sum(fn._cache_size() for key, fn in
                      self._prefill_fns.items() if key[0] != "zeros")
        return prefill, self._decode_step._cache_size()

    def _batch_cache(self, bsz: int):
        """A zeroed contiguous cache for one chunked-prefill batch, built
        in its layout; the builder is compiled once per batch size."""
        key = ("zeros", bsz)
        if key not in self._prefill_fns:
            make = functools.partial(tfm.init_cache, self.cfg, bsz,
                                     self.cache_len, self.kv_dtype)
            self._batch_layouts[bsz] = self._layout(make, bsz)
            self._prefill_fns[key] = jax.jit(
                _named(make, "zero_batch_cache"),
                out_shardings=self._batch_layouts[bsz])
        return self._prefill_fns[key]()

    def _upload_tables(self) -> None:
        """Push the host block-table mirror, placed like the pool's own
        table leaf: replicated over the mesh, if there is one, else
        uncommitted like the rest of the pool (one committed leaf among
        uncommitted ones gives the decode step a second signature)."""
        tr = self.tracer
        with (tr.span("serve.kv.upload_tables") if tr.enabled
              else NULL_SPAN):
            if self._pool_layout is None:
                tables = jnp.asarray(self._bt.host)
            else:
                tables = jax.device_put(self._bt.host,
                                        self._pool_layout["block_tables"])
        self.pool_cache["block_tables"] = tables
        self._bt.dirty = False

    def _trace_collectives(self, key: tuple, fn, args) -> None:
        """Under a mesh, the collectives that the program ``fn`` runs on
        ``args`` per call: their count and the bytes each device sends,
        read from the compiled HLO once per program (``key``) and then
        recorded, while tracing, as counter ``tp.collectives``
        (``<key[0]>.count``, ``<key[0]>.bytes``).  The executable comes
        from jit's own caches: the same arguments the call is about to
        be given compile nothing twice."""
        if self._mesh is None:
            return
        got = self._collectives.get(key)
        if got is None:
            got = self._collectives[key] = collective_calls(
                fn.lower(*args).compile().as_text())
        tr = self.tracer
        if tr.enabled:
            tr.counter("tp", "tp.collectives", self.clock.now(),
                       {f"{key[0]}.count": got[0],
                        f"{key[0]}.bytes": got[1]})

    def _chunk_kv_blocks(self, end: int) -> dict:
        """Counter ``attn.chunk_kv_blocks`` of a chunk step whose keys are
        ``[0, end)``: the batch cache's K/V blocks the chunk attention
        reads and holds, and whether the Pallas kernel runs it (1) or
        ``full_attention`` over the whole cache (0)."""
        s_len = self.cfg.attn_cache_len(self.cache_len)
        read, total = kv_blocks(end, s_len,
                                attention.chunk_block(self.cfg, s_len))
        return {"read": read, "total": total,
                "kernel": int(attention.uses_chunk_kernel(self.cfg,
                                                          self.kv_dtype))}

    # ------------------------------------------------------------- jits --
    def _prefill_fn(self, pad_to: int, bsz: int):
        key = ("prefill", pad_to, bsz)
        if key not in self._prefill_fns:
            cfg, moe_impl = self.cfg, self.moe_impl

            def fn(p, tokens, lengths):
                return tfm.prefill(cfg, p, tokens=tokens, lengths=lengths,
                                   cache_len=self.cache_len,
                                   moe_impl=moe_impl)
            self._prefill_fns[key] = jax.jit(fn)
            self.n_prefill_shapes += 1
        return self._prefill_fns[key]

    def _chunk_fn(self, chunk_len: int, bsz: int):
        key = ("chunk", chunk_len, bsz)
        if key not in self._prefill_fns:
            cfg, moe_impl = self.cfg, self.moe_impl

            def fn(p, tokens, cache, start, lengths):
                return tfm.prefill_chunk(cfg, p, tokens, cache, start,
                                         lengths, moe_impl=moe_impl)
            # the batch cache comes back in the layout it was built in
            self._prefill_fns[key] = jax.jit(
                fn, out_shardings=(None, self._batch_layouts[bsz]))
            self.n_prefill_shapes += 1
        return self._prefill_fns[key]

    # --------------------------------------------------------- protocol --
    def begin(self, requests: Sequence[Request]) -> None:
        for r in requests:
            r.materialize_tokens(self.cfg.vocab_size)
            if r.sliced_tokens > 0:
                # cold resume of a slice-promoted request (checkpointed
                # drain, core/recovery.py): the promoted ids are the
                # LAST sliced_tokens of the prompt — seed the output
                # list with them so generated-token indexing
                # (the retained transcript, slice yields) keeps its absolute
                # alignment on a backend that never ran the original
                # decode steps
                self.outputs[r.rid] = [
                    int(t) for t in
                    r.tokens[r.prompt_len - r.sliced_tokens:r.prompt_len]]
            else:
                self.outputs[r.rid] = []
        self.clock.start()

    @property
    def alloc(self) -> Optional[paging.BlockAllocator]:
        """The paged pool's allocator (None unpaged), as
        ``bench/harness.py`` reads it at each decode step for
        ``kv.pool_util``; the program itself reads ``kv.alloc``."""
        return None if self.kv is None else self.kv.alloc

    def kv_budget_tokens(self) -> float:
        # slot caches are preallocated at cache_len: memory safety is
        # structural, the loop's admission control is slot-based
        return math.inf

    def free_slots(self) -> int:
        return sum(1 for r in self.slot_req if r is None)

    # ------------------------------------------------- paged KV (§3) -----
    def admit_blocks(self, requests: Sequence[Request]) -> int:
        if self.kv is None:
            return len(requests)
        return self.kv.admit(requests)

    def decode_preempt(self, pool: Sequence[Request]) -> List[Request]:
        if self.kv is None:
            return []
        victims = self.kv.extend(pool, self.slack_of)
        for v in victims:
            slot = self._slot_of.pop(v.rid, None)
            if slot is not None:
                self.slot_req[slot] = None
                self._bt.clear(slot, v.rid)
            else:
                self._bt.forget(v.rid)
            # outputs survive here: the loop decides whether the victim
            # keeps a slice (on_slice_yield truncates) or restarts
            # (on_preempt_reset wipes)
        for r in pool:                       # tables may have grown a page
            slot = self._slot_of.get(r.rid)
            if slot is not None:
                # incremental: only newly appended pages are written —
                # the old full-table np.array_equal rescan paid
                # O(pool x pages_per_seq) on EVERY dispatch
                self._bt.sync(slot, r.rid, self.kv.alloc)
        return victims

    def on_slice_yield(self, req: Request, keep: int) -> None:
        """Slice-boundary preemption kept ``keep`` generated tokens
        (now promoted into the prompt): drop only the unaligned tail —
        the resume prefill's argmax re-appends from position keep."""
        out = self.outputs.get(req.rid)
        if out is not None:
            del out[keep:]

    def on_preempt_reset(self, req: Request) -> None:
        self.outputs[req.rid] = []       # regenerated after re-prefill

    # ------------------------------------------- fault/drain teardown -----
    def abort_prefill(self, req: Request) -> None:
        """A mid-prefill request leaves before its KV enters the slot
        pool (prefill-job abandon, checkpointed drain): free its
        admission-reserved pages outright.  No slot was taken yet —
        slots are assigned in ``_finish_prefill``."""
        if self.kv is not None:
            self.kv.free(req)
            self._bt.forget(req.rid)

    def evict_request(self, req: Request) -> None:
        """Tear down a pooled request's slot + pages WITHOUT retention
        registration (decode-pool kill / drain): its partial KV never
        becomes a radix path.  ``outputs`` survives — the loop still
        reads ``generated_tokens`` to promote the preserved slice."""
        slot = self._slot_of.pop(req.rid, None)
        if slot is not None:
            self.slot_req[slot] = None
        if self.kv is not None:
            self.kv.free(req)
            if slot is not None:
                self._bt.clear(slot, req.rid)
            else:
                self._bt.forget(req.rid)

    def chunk_plan(self, batch: FormedBatch) -> List[Tuple[int, int]]:
        total = max(batch.pad_to, 8)     # min real-tensor prompt width
        c = self.chunk_tokens if tfm.supports_chunked_prefill(self.cfg) \
            else None
        skip = self.kv.prefix_skip(batch) if self.kv is not None else 0
        return plan_chunks(total, c, skip=skip)

    def transfer_seconds(self, batch: FormedBatch) -> float:
        return 0.0            # prefill writes straight into the slot pool

    def prefill_chunk(self, job: PrefillJob, idx: int) -> float:
        reqs = job.batch.requests
        B = len(reqs)
        start, clen = job.chunks[idx]
        # chunk-mode execution whenever the plan is split OR starts past
        # position 0 (a cached prefix was skipped — the single remaining
        # span still continues an existing cache)
        chunked = len(job.chunks) > 1 or job.chunks[0][0] > 0
        tr = self.tracer
        h = job.handle
        if h is None:
            with (tr.span("serve.prefill.batch_cache") if tr.enabled
                  else NULL_SPAN):
                h = job.handle = self._prefill_handle(job, chunked)
        if not chunked:
            with (tr.span("serve.prefill.dispatch") if tr.enabled
                  else NULL_SPAN):
                fn = self._prefill_fn(clen, B)
                logits, cache = fn(self.params, jnp.asarray(h["toks"]),
                                   h["lens"])
            with (tr.span("serve.prefill.first_tokens") if tr.enabled
                  else NULL_SPAN):
                h["first"][:] = np.asarray(jnp.argmax(logits, -1))
            h["cache"] = cache
        else:
            # one chunk step in flight per job: nothing is donated, so
            # each step writes a fresh batch cache, and a host that ran
            # ahead would hold one more cache per queued step
            with (tr.span("serve.prefill.await_prev") if tr.enabled
                  else NULL_SPAN):
                jax.block_until_ready(h["cache"])
            with (tr.span("serve.prefill.dispatch") if tr.enabled
                  else NULL_SPAN):
                fn = self._chunk_fn(clen, B)
                args = (self.params,
                        jnp.asarray(h["toks"][:, start:start + clen]),
                        self._placed(h["cache"], self._batch_layouts[B]),
                        start, h["lens"])
                self._trace_collectives(("chunk", clen, B), fn, args)
                if tr.enabled:
                    tr.counter("attn", "attn.chunk_kv_blocks",
                               self.clock.now(),
                               self._chunk_kv_blocks(start + clen))
                logits, h["cache"] = fn(*args)
            last = h["np_lens"] - 1
            fin = (last >= start) & (last < start + clen)
            if fin.any():
                with (tr.span("serve.prefill.first_tokens") if tr.enabled
                      else NULL_SPAN):
                    h["first"][fin] = np.asarray(jnp.argmax(logits, -1))[fin]
        if idx == len(job.chunks) - 1:
            if chunked:
                h["cache"] = {"pos": h["lens"].astype(jnp.int32),
                              "groups": h["cache"]["groups"]}
            self._finish_prefill(job)
        return 0.0            # wall backend: the loop reads the clock

    def _prefill_handle(self, job: PrefillJob, chunked: bool) -> dict:
        """A job's host state at its first chunk: the padded prompt ids,
        the lengths, and for chunk mode a zeroed batch cache, seeded
        from the page pool where a cached prefix is skipped."""
        reqs = job.batch.requests
        B = len(reqs)
        total = job.chunks[-1][0] + job.chunks[-1][1]
        toks = np.zeros((B, total), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, r in enumerate(reqs):
            L = min(r.prompt_len, total)
            toks[i, :L] = r.tokens[:L]
            lens[i] = L
        h = {"toks": toks, "lens": jnp.asarray(lens), "np_lens": lens,
             "cache": self._batch_cache(B) if chunked else None,
             "first": np.zeros((B,), np.int64)}
        if job.chunks[0][0] > 0:
            # seed the batch cache's prefix region from the shared
            # page pool before the first (post-prefix) chunk runs
            self._seed_prefix(h, reqs)
        return h

    def _seed_prefix(self, h, reqs: Sequence[Request]) -> None:
        """Copy each row's cached-prefix K/V out of the shared page pool
        into the batch prefill cache, so chunked prefill can resume past
        it.  One gather per cache leaf for the whole batch; the gather is
        the exact inverse of ``_insert_slots_paged``'s scatter, so seeded
        values are bit-identical to a cold recompute.  A session-resumed
        row's hit is NOT page-aligned (the pinned partial tail extends
        it): the gather then includes the tail page and the per-row mask
        cuts at the exact token."""
        page, maxp = self.kv.page_size, self.kv.pages_per_seq
        B = len(reqs)
        idx = np.full((B, maxp), self.trash_page, np.int32)
        plen = np.zeros((B,), np.int32)
        for i, r in enumerate(reqs):
            npg = -(-r.prefix_hit_tokens // page)   # incl. a partial tail
            if npg:
                idx[i, :npg] = self.kv.alloc.table(r.rid)[:npg]
                plen[i] = r.prefix_hit_tokens
        if not plen.any():
            return
        idxj = jnp.asarray(idx)
        S = self.kv.cap
        mask = jnp.arange(S)[None, :] < jnp.asarray(plen)[:, None]  # (B,S)

        def seed(cache_leaf, pool_leaf):
            g = pool_leaf[:, idxj]               # (reps, B, maxp, page, ...)
            g = g.reshape(g.shape[:2] + (maxp * page,) + g.shape[4:])
            g = g[:, :, :S]
            m = mask.reshape((1, B, S) + (1,) * (g.ndim - 3))
            return jnp.where(m, g, cache_leaf)

        new_groups = []
        for gi, (pattern, reps) in enumerate(self.cfg.block_groups()):
            slots_out = []
            for j, btype in enumerate(pattern):
                cslot = h["cache"]["groups"][gi][j]
                if btype in (BLOCK_ATTN, BLOCK_MOE):
                    pslot = self.pool_cache["groups"][gi][j]
                    slots_out.append({k: seed(cslot[k], pslot[k])
                                      for k in cslot})
                else:       # unreachable under the prefix_cacheable gate
                    slots_out.append(cslot)
            new_groups.append(tuple(slots_out))
        h["cache"] = {"pos": h["cache"]["pos"], "groups": tuple(new_groups)}

    def _finish_prefill(self, job: PrefillJob) -> None:
        """First tokens out; batched slot insertion for continuing rows."""
        h = job.handle
        kv = self.kv
        slots, rows, firsts, tables, shared = [], [], [], [], []
        free = iter(i for i, r in enumerate(self.slot_req) if r is None)
        for i, r in enumerate(job.batch.requests):
            tok = int(h["first"][i])
            self.outputs[r.rid].append(tok)
            if not paging.continues_decode(self.cfg, r):
                if kv is not None:
                    # never inserted into the pool: its pages hold no
                    # transcript KV, so they are freed, never retained
                    kv.free(r)
                continue
            slot = next(free)
            self.slot_req[slot] = r
            self._slot_of[r.rid] = slot
            slots.append(slot)
            rows.append(i)
            firsts.append(tok)
            if kv is not None:
                t = kv.alloc.table(r.rid)        # reserved at admission
                self._bt.insert(slot, r.rid, t)
                tables.append(t)
                # shared prefix pages already hold this KV — never
                # re-scattered (they may be read by other live requests)
                shared.append(r.prefix_hit_tokens // kv.page_size
                              if kv.retention is not None else 0)
        tr = self.tracer
        if slots:
            with (tr.span("serve.prefill.insert_pages" if kv is not None
                          else "serve.prefill.insert_slots") if tr.enabled
                  else NULL_SPAN):
                if kv is not None:
                    self._insert_slots_paged(h["cache"], slots, rows,
                                             firsts, tables, shared)
                else:
                    self._insert_slots(h["cache"], slots, rows, firsts)
        if kv is not None:
            # index full prompt pages AFTER their KV is physically in the
            # pool — a concurrent later batch may hit them immediately
            for r in (job.batch.requests[i] for i in rows):
                kv.register_prompt(r)
        job.handle = None

    def _insert_slots(self, batch_cache, slots: List[int], rows: List[int],
                      firsts: List[int]) -> None:
        """Scatter batch rows into pool slots: ONE gather/scatter per
        cache leaf for the whole batch (vs. a per-request device
        round-trip pre-refactor)."""
        sl = jnp.asarray(slots, jnp.int32)
        rw = jnp.asarray(rows, jnp.int32)
        pos = self.pool_cache["pos"].at[sl].set(batch_cache["pos"][rw])
        groups = jax.tree.map(
            lambda pl, bc: pl.at[:, sl].set(bc[:, rw]),
            self.pool_cache["groups"], batch_cache["groups"])
        self.pool_cache = {"pos": pos, "groups": groups}
        self.next_tok = self.next_tok.at[sl].set(
            jnp.asarray(firsts, jnp.int32))

    def _insert_slots_paged(self, batch_cache, slots: List[int],
                            rows: List[int], firsts: List[int],
                            tables: List[List[int]],
                            shared: Optional[List[int]] = None) -> None:
        """Scatter prefilled caches into the page pool, in place
        (``insert_pages_jit``, the pool donated): attention K/V rows are
        chopped into page-sized spans and written to each request's
        allocated pages (one scatter per leaf for the whole batch);
        per-slot leaves (recurrent state, vision KV, positions) go to the
        rows' slots.  The first ``shared[i]`` pages of a table are a
        cached prefix that ALREADY lives in the pool — skipped, so shared
        pages are written exactly once, by their original owner."""
        B = batch_cache["pos"].shape[0]
        maxp = self.kv.pages_per_seq
        # past the pool's last page (the trash page) and its last slot:
        # the insert writes nothing there
        dst = np.full((B, maxp), self.trash_page + 1, np.int32)
        slot_of_row = np.full((B,), self.max_slots, np.int32)
        for k, (slot, row, t) in enumerate(zip(slots, rows, tables)):
            skip_pages = shared[k] if shared else 0
            dst[row, skip_pages:len(t)] = t[skip_pages:]
            slot_of_row[row] = slot
        self.pool_cache = self._insert(
            self._placed(self.pool_cache, self._pool_layout), batch_cache,
            jnp.asarray(dst.reshape(-1)), jnp.asarray(slot_of_row))
        self._upload_tables()
        self.next_tok = self.next_tok.at[jnp.asarray(slots, jnp.int32)].set(
            jnp.asarray(firsts, jnp.int32))

    def _decode_in_place(self, params, tok, cache):
        """One paged decode step on ``cache``, whose page pool it
        donates.  The donated leaves are deleted by the call, so the
        step's pool takes their place in ``cache`` itself: a holder of
        the pool tree never reads a deleted buffer.  The positions and
        the rest of the step's state come back in the returned cache."""
        args = (params, tok) + split_pool(self.cfg, cache)
        self._trace_collectives(("decode",), self._decode_step, args)
        logits, rest, pool = self._decode_step(*args)
        cache["groups"] = join_pool(cache, pool)["groups"]
        return logits, join_pool(rest, pool)

    def decode_iter(self, pool: Sequence[Request],
                    context_tokens: int) -> float:
        kv = self.kv
        if kv is not None and self._bt.dirty:
            # tables changed (extend/preempt/release) — push the tiny
            # (slots, pages_per_seq) int32 host mirror; steady-state
            # decode iterations skip the transfer
            self._upload_tables()
        tr = self.tracer
        with (tr.span("serve.decode.dispatch") if tr.enabled else NULL_SPAN):
            cache = self._placed(self.pool_cache, self._pool_layout)
            k_in = (jax.tree.leaves(split_pool(self.cfg, cache)[1])[0]
                    if tr.enabled and kv is not None else None)
            logits, self.pool_cache = self._decode_fn(
                self.params, self._placed(self.next_tok, self._tok_layout),
                cache)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            if k_in is not None:
                # 1 where the step consumed the K pool it was given:
                # donation engaged, and the pool was updated in place
                tr.counter("kv", "kv.pool_in_place", self.clock.now(),
                           {"in_place": int(k_in.is_deleted())})
            if tr.enabled and kv is not None and self._mesh is not None:
                # the context each live slot attends in this step
                ctx = [kv.decode_tokens(r) for r in self.slot_req
                       if r is not None]
                tr.counter("kv", "kv.decode_context", self.clock.now(),
                           {"sum": sum(ctx), "max": max(ctx, default=0)})
        self.next_tok = nxt
        with (tr.span("serve.decode.readback") if tr.enabled else NULL_SPAN):
            toks = np.asarray(nxt)
        with (tr.span("serve.decode.outputs") if tr.enabled else NULL_SPAN):
            for slot, r in enumerate(self.slot_req):
                if r is not None:
                    self.outputs[r.rid].append(int(toks[slot]))
        return 0.0

    def release(self, req: Request) -> None:
        slot = self._slot_of.pop(req.rid, None)
        if slot is not None:
            self.slot_req[slot] = None
        if self.kv is not None:
            self.kv.release(req, self.generated_tokens, self.clock.now())
            if slot is not None:
                self._bt.clear(slot, req.rid)
            else:
                self._bt.forget(req.rid)

    def generated_tokens(self, req: Request) -> np.ndarray:
        return np.asarray(self.outputs.get(req.rid, ()), np.int32)

    def maintain(self, now: float) -> None:
        if self.kv is not None:
            self.kv.tick(now)


class ServingEngine:
    """Facade: schedule + serve a request set on the JAX backend.

    Thin wiring only — the run loop is core/serving_loop.ServingLoop in
    ``disagg`` topology (prefill chunks interleave with slot decode)."""

    def __init__(self, cfg: ModelConfig, params, scheduler, *,
                 max_slots: int = 8, cache_len: Optional[int] = None,
                 moe_impl: str = "local", time_scale: float = 1.0,
                 chunk_tokens: Optional[int] = None, paged: bool = False,
                 page_size: int = 128,
                 kv_pool_tokens: Optional[int] = None,
                 prefix_cache: bool = False,
                 session_ttl: Optional[float] = None,
                 host_pool_tokens: Optional[int] = None,
                 spill_bw: float = 16e9,
                 spill_dtype: str = "",
                 slice_tokens: Optional[int] = None,
                 recorder=None, tracer=None,
                 fault_plan=None, recovery=None,
                 restore_timeout: float = 30.0):
        self.cfg = cfg
        self.params = params
        self.sched = scheduler
        self.backend = JaxEngineBackend(
            cfg, params, max_slots=max_slots, cache_len=cache_len,
            moe_impl=moe_impl, time_scale=time_scale,
            chunk_tokens=chunk_tokens, paged=paged, page_size=page_size,
            kv_pool_tokens=kv_pool_tokens, prefix_cache=prefix_cache,
            session_ttl=session_ttl, host_pool_tokens=host_pool_tokens,
            spill_bw=spill_bw, spill_dtype=spill_dtype)
        faults = None
        if fault_plan is not None and fault_plan.any_armed:
            faults = FaultInjector(fault_plan)
        self.faults = faults
        if tracer is None:
            # records exactly while a JAX profiler session captures
            tracer = ProfilerTracer()
        self.backend.tracer = tracer
        self.loop = ServingLoop(scheduler, self.backend, LoopConfig(
            mode="disagg", decode_slot_cap=max_slots,
            slice_tokens=slice_tokens, restore_timeout=restore_timeout),
            recorder=recorder, tracer=tracer,
            faults=faults, recovery=recovery)
        self.result: Optional[ServeResult] = None

    @property
    def outputs(self) -> Dict[int, List[int]]:
        return self.backend.outputs

    @property
    def n_prefill_shapes(self) -> int:
        return self.backend.n_prefill_shapes

    @property
    def interleaved_decode_steps(self) -> int:
        return self.result.interleaved_decode_steps if self.result else 0

    def submit(self, requests: List[Request]) -> None:
        self.requests = list(requests)

    def run(self, max_wall_s: float = 600.0) -> List[Request]:
        self.result = self.loop.run(self.requests, time_limit=math.inf,
                                    max_wall_s=max_wall_s)
        return [r for r in self.requests
                if r.finished >= 0 and not r.dropped]
