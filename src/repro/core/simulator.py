"""Analytic cost-model backend: paper-scale end-to-end serving runs.

The container is CPU-only, so the paper's 4×A100 experiments (Fig. 5/6)
are reproduced on an analytic cost model; the same scheduler objects also
drive the *real* JAX engine (core/engine.py) at tiny-model scale, which
is how the cost model's scheduling behaviour is validated.

All orchestration lives in core/serving_loop.py — this module only
prices the substrate: :class:`CostModelBackend` implements the
``ExecutionBackend`` protocol on a :class:`VirtualClock`, and
:class:`Simulator` is a thin facade wiring (scheduler, cost model,
execution mode) into a :class:`ServingLoop`.

Cost model:
  prefill (compute-bound):  t = FLOPs(padded tokens) / (chips·peak·MFU)
  decode  (memory-bound) :  t = max(weight+KV bytes / (chips·BW·eff),
                                     FLOPs / (chips·peak·MFU))
  KV transfer prefill->decode over NVLink (A100) / ICI (TPU).

OOM semantics: schedulers admitting more live KV tokens than the device
budget trigger an OOM event — the offending batch is evicted and
re-queued after a restart penalty (models vLLM preemption/recompute).
BucketServe's Eq. (5)/(6) memory safety avoids these by construction.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import math

import numpy as np

from repro.models.config import ModelConfig
from . import paging
from .batcher import FormedBatch
from .faults import FaultInjector
from .request import Request
from .serving_loop import (LoopConfig, PrefillJob, ServeResult, ServingLoop,
                           VirtualClock, plan_chunks)

# Back-compat alias: benchmark/analysis code predating the unified loop
# imports the result type under its simulator-era name.
SimResult = ServeResult


# ------------------------------------------------------------- hardware ---
@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float            # per chip, bf16
    hbm_bw: float                # per chip
    link_bw: float               # inter-chip (KV transfer)
    hbm_bytes: int               # per chip
    prefill_chips: int = 2
    decode_chips: int = 2
    mfu: float = 0.55            # achievable fraction of peak in prefill
    bw_eff: float = 0.80         # achievable fraction of HBM bandwidth


A100X4 = HardwareSpec("a100x4", 312e12, 1.555e12, 300e9, 40 * 2 ** 30,
                      prefill_chips=2, decode_chips=2)
V5E_POD = HardwareSpec("v5e", 197e12, 819e9, 50e9, 16 * 2 ** 30,
                       prefill_chips=128, decode_chips=128)


class CostModel:
    def __init__(self, cfg: ModelConfig, hw: HardwareSpec,
                 bytes_per_el: int = 2):
        self.cfg = cfg
        self.hw = hw
        self.b = bytes_per_el
        self.p_active = cfg.active_param_count()
        # honors the int8-KV serving variant (halved cache traffic/budget)
        self.kv_per_tok = max(cfg.cache_bytes_per_token(), 1)
        self.weight_bytes = cfg.param_count() * bytes_per_el

    def _attn_flops(self, s: int) -> float:
        """Quadratic attention FLOPs per sequence of length s (score+value)."""
        win = self.cfg.sliding_window or (
            self.cfg.local_window if self.cfg.arch_type == "hybrid" else 0)
        if self.cfg.attention_free:
            return 2.0 * 2 * self.cfg.n_layers * self.cfg.d_model * s * 64
        eff = min(s, win) if win else s
        n_attn = self.cfg.n_layers
        return 2.0 * 2 * n_attn * self.cfg.n_heads * self.cfg.d_head * s * eff

    def prefill_seconds(self, n: int, pad_to: int) -> float:
        tokens = n * pad_to                      # padded compute (TPU shapes)
        flops = 2.0 * self.p_active * tokens + n * self._attn_flops(pad_to)
        chips = self.hw.prefill_chips
        return flops / (chips * self.hw.peak_flops * self.hw.mfu)

    def prefill_chunk_seconds(self, n: int, start: int, length: int) -> float:
        """One chunked-prefill step: linear FLOPs for the chunk's tokens
        plus the *incremental* quadratic attention cost of extending each
        sequence from ``start`` to ``start+length`` context."""
        flops = 2.0 * self.p_active * n * length + n * (
            self._attn_flops(start + length) - self._attn_flops(start))
        chips = self.hw.prefill_chips
        return flops / (chips * self.hw.peak_flops * self.hw.mfu)

    def decode_iter_seconds(self, context_tokens: int, pool: int) -> float:
        """One iteration over the decode pool (one token each).
        `context_tokens`: KV tokens actually READ this iteration — exact
        live tokens for continuous/paged systems, padded-batch tokens for
        batch-granularity systems (the paper's Fig. 3b waste)."""
        if pool == 0:
            return 0.0
        chips = self.hw.decode_chips
        mem = (self.weight_bytes / chips +
               context_tokens * self.kv_per_tok / chips) / \
            (self.hw.hbm_bw * self.hw.bw_eff)
        comp = 2.0 * self.p_active * pool / (chips * self.hw.peak_flops
                                             * self.hw.mfu)
        return max(mem, comp)

    def transfer_seconds(self, prompt_tokens: int) -> float:
        return prompt_tokens * self.kv_per_tok / self.hw.link_bw

    def kv_budget_tokens(self, chips: int, reserve: float = 0.10,
                         act_reserve: float = 0.05) -> float:
        total = self.hw.hbm_bytes * chips
        remain = total - self.weight_bytes - act_reserve * total
        return max(0.0, (1 - reserve) * remain) / self.kv_per_tok


# -------------------------------------------------------------- backend ---
class CostModelBackend:
    """ExecutionBackend over the analytic cost model (virtual time).

    ``prefill_chunk``/``decode_iter`` price work instead of running it —
    the ServingLoop advances request state itself.  ``chunk_tokens``
    enables chunked prefill in the cost model too (incremental quadratic
    attention per chunk); default is whole-prompt prefill, matching the
    paper's setup.

    ``paged=True`` replaces the token KV budget with a page budget:
    the backend holds the same :class:`~repro.core.paging.PagedKv` as
    the real engine (``self.kv``), which sizes, admits, extends,
    releases and retains.  With ``prefix_cache`` or ``session_ttl`` set,
    token ids are materialized with the engine's rng rule and chunk
    plans skip the cached prefix, so the priced prefill work starts at
    the resume offset.  Without a ``kv_pool_tokens`` budget the pool is
    ``kv_budget`` (already cache-dtype tokens) in pages.
    """

    prefill_needs_slots = False
    supports_decode = True
    # armed by the ServingLoop when the scheduler is slack-aware: a
    # CLOCK-FREE key (Request -> seconds) preferring the victim with
    # the most remaining deadline slack (DESIGN.md §8)
    slack_of = None

    def __init__(self, cost: CostModel, *, kv_budget: float,
                 chunk_tokens: Optional[int] = None, paged: bool = False,
                 page_size: int = 128,
                 kv_pool_tokens: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 prefix_cache: bool = False,
                 session_ttl: Optional[float] = None,
                 host_pool_tokens: Optional[int] = None,
                 spill_bw: float = 16e9,
                 spill_dtype: str = ""):
        self.cost = cost
        self.clock = VirtualClock()
        self.chunk_tokens = chunk_tokens
        self.flops_per_token = 2.0 * cost.p_active
        assert paged or not (prefix_cache or session_ttl is not None
                             or host_pool_tokens), \
            "KV retention and its host tier ride on the paged KV pool"
        # the host-side page book; None exactly when the pool is not paged
        self.kv: Optional[paging.PagedKv] = None
        self._kv_budget = kv_budget
        if paged:
            # block accounting REPLACES the token-budget OOM check
            self._kv_budget = math.inf
            cfg = cost.cfg
            self.kv = paging.PagedKv(
                cfg, page_size, cache_len or cfg.max_seq_len,
                pool_tokens=kv_pool_tokens,
                default_pages=int(kv_budget) // page_size,
                prefix_cache=prefix_cache, session_ttl=session_ttl,
                host_pool_tokens=host_pool_tokens, spill_bw=spill_bw,
                spill_dtype=spill_dtype)

    def begin(self, requests: Sequence[Request]) -> None:
        self.clock = VirtualClock()
        if self.kv is not None:
            self.kv.reset()
            if self.kv.retention is not None:
                # the radix index keys on ACTUAL token ids, materialized
                # by the engine's rule (later session turns are left for
                # the loop to compose)
                for r in requests:
                    r.materialize_tokens(self.cost.cfg.vocab_size)

    def kv_budget_tokens(self) -> float:
        return self._kv_budget

    def maintain(self, now: float) -> None:
        if self.kv is not None:
            self.kv.tick(now)

    def free_slots(self) -> int:          # pragma: no cover - not consulted
        return 1 << 30

    # ------------------------------------------------------- paged KV ----
    def admit_blocks(self, requests: Sequence[Request]) -> int:
        if self.kv is None:
            return len(requests)
        return self.kv.admit(requests)

    def decode_preempt(self, pool: Sequence[Request]) -> List[Request]:
        if self.kv is None:
            return []
        return self.kv.extend(pool, self.slack_of)

    def on_slice_yield(self, req: Request, keep: int) -> None:
        # the synthetic id stream (generated_tokens) is prefix-stable:
        # truncating req.generated back to ``keep`` IS the truncation
        pass

    def on_preempt_reset(self, req: Request) -> None:
        pass

    # ------------------------------------------- fault/drain teardown -----
    def abort_prefill(self, req: Request) -> None:
        """A mid-prefill request leaves before its KV enters service
        (prefill-job abandon, checkpointed drain): free its pages
        OUTRIGHT — never through ``release``, which would register a
        garbage partial transcript with the retention layer."""
        if self.kv is not None:
            self.kv.free(req)

    def evict_request(self, req: Request) -> None:
        """Tear down a pooled request's KV without retention
        registration — the decode-pool kill / drain analogue of a
        preemption victim's teardown (which ``extend_for_decode`` does
        inside the backend)."""
        if self.kv is not None:
            self.kv.free(req)

    def chunk_plan(self, batch: FormedBatch) -> List[Tuple[int, int]]:
        # same gate as the real engine (cfg.chunkable_prefill) so the two
        # backends schedule identically for ring-cache/VLM configs
        c = self.chunk_tokens if self.cost.cfg.chunkable_prefill else None
        skip = self.kv.prefix_skip(batch) if self.kv is not None else 0
        return plan_chunks(batch.pad_to, c, skip=skip)

    def prefill_chunk(self, job: PrefillJob, idx: int) -> float:
        start, length = job.chunks[idx]
        if idx == len(job.chunks) - 1 and self.kv is not None:
            # the end of prefill: index the prompt pages
            for r in job.batch.requests:
                self.kv.register_prompt(r)
        if len(job.chunks) == 1 and start == 0:
            return self.cost.prefill_seconds(job.batch.size, length)
        # a span starting past 0 (later chunk OR resumed-after-prefix
        # prefill) pays the incremental quadratic attention cost of
        # extending each sequence's context from ``start``
        return self.cost.prefill_chunk_seconds(job.batch.size, start, length)

    def transfer_seconds(self, batch: FormedBatch) -> float:
        return self.cost.transfer_seconds(batch.total_tokens)

    def decode_iter(self, pool: Sequence[Request],
                    context_tokens: int) -> float:
        return self.cost.decode_iter_seconds(context_tokens, len(pool))

    def release(self, req: Request) -> None:
        if self.kv is not None:
            self.kv.release(req, self.generated_tokens, self.clock.now())

    def generated_tokens(self, req: Request) -> np.ndarray:
        """Deterministic SYNTHETIC generated ids — the cost model runs
        no model, but session transcripts must still be concrete token
        paths.  Seeded per rid (disjoint from the prompt
        materialization rule), so regenerating the same request yields
        the same transcript: hit counts stay reproducible and in
        parity with the engine's (whose ids differ but whose
        transcript STRUCTURE is identical)."""
        rng = np.random.default_rng([req.rid, 0xD3C0DE])
        return rng.integers(0, self.cost.cfg.vocab_size,
                            req.generated).astype(np.int32)


# ------------------------------------------------------------ simulator ---
class Simulator:
    """Facade: (scheduler, cost model, mode) -> configured ServingLoop.

    Execution modes (loop topology, see serving_loop.ServingLoop):

    * ``disagg``  — separate prefill/decode executors + KV transfer
      (BucketServe, DistServe).
    * ``coupled`` — ONE executor; each iteration fuses the new prefill
      batch (if any) with one decode step over the live pool — Orca-style
      iteration-level scheduling.  Prefill work inflates every concurrent
      request's TPOT: the phase interference DistServe/BucketServe remove.
    * ``static``  — one executor; a batch runs prefill + ALL decode steps
      to completion before the next batch starts (naive static batching).
    """

    def __init__(self, scheduler, cost: CostModel, *, mode: str = "disagg",
                 decode_slot_cap: int = 256, restart_penalty: float = 0.5,
                 tick: float = 0.005, chunk_tokens: Optional[int] = None,
                 paged: bool = False, page_size: int = 128,
                 kv_pool_tokens: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 prefix_cache: bool = False,
                 session_ttl: Optional[float] = None,
                 host_pool_tokens: Optional[int] = None,
                 spill_bw: float = 16e9,
                 spill_dtype: str = "",
                 slice_tokens: Optional[int] = None,
                 recorder=None, tracer=None,
                 fault_plan=None, recovery=None,
                 restore_timeout: float = 30.0):
        assert mode in ("disagg", "coupled", "static")
        prefix_cache = prefix_cache or session_ttl is not None
        # static mode runs a batch to completion without per-iteration
        # decode_preempt extends, so paged accounting would silently
        # understate the live footprint — refuse the combination
        assert not (paged and mode == "static"), \
            "paged KV accounting needs iteration-level decode " \
            "(disagg/coupled)"
        # fused-iteration modes bypass backend.chunk_plan (prefill is one
        # hardcoded whole-prompt span), so a prefix cache would count
        # hits and discount charges WITHOUT ever skipping prefill —
        # refuse rather than silently misreport
        assert not (prefix_cache and mode != "disagg"), \
            "prefix cache needs chunk-planned prefill (disagg mode)"
        self.sched = scheduler
        self.cost = cost
        self.mode = mode
        chips = cost.hw.decode_chips if mode == "disagg" \
            else cost.hw.decode_chips + cost.hw.prefill_chips
        self.backend = CostModelBackend(
            cost, kv_budget=cost.kv_budget_tokens(chips),
            chunk_tokens=chunk_tokens, paged=paged, page_size=page_size,
            kv_pool_tokens=kv_pool_tokens, cache_len=cache_len,
            prefix_cache=prefix_cache, session_ttl=session_ttl,
            host_pool_tokens=host_pool_tokens, spill_bw=spill_bw,
            spill_dtype=spill_dtype)
        # fault-injection plane (core/faults.py): a FaultPlan is turned
        # into a per-run injector HERE so the facade owns the arming —
        # passing a plan with no armed site is the same as passing None
        faults = None
        if fault_plan is not None and fault_plan.any_armed:
            faults = FaultInjector(fault_plan)
        self.faults = faults
        self.loop = ServingLoop(scheduler, self.backend, LoopConfig(
            mode=mode, decode_slot_cap=decode_slot_cap,
            restart_penalty=restart_penalty, tick=tick,
            slice_tokens=slice_tokens, restore_timeout=restore_timeout),
            recorder=recorder, tracer=tracer,
            faults=faults, recovery=recovery)

    def run(self, requests: List[Request], time_limit: float = 3600.0,
            drain_at: Optional[float] = None,
            resume_clock: Optional[float] = None) -> SimResult:
        return self.loop.run(requests, time_limit=time_limit,
                             drain_at=drain_at, resume_clock=resume_clock)
