"""Jit'd public wrappers around the Pallas kernels.

``configure(use_pallas=...)`` / the ``REPRO_USE_PALLAS`` env var pick
between the kernel path and the pure-jnp reference (models/attention.py
et al.).  Interpret mode is not a choice: ``kernels.interpret_mode``
runs the kernels in the Pallas interpreter on the CPU backend and
compiles them everywhere else.
"""
from __future__ import annotations

import functools
import os

import jax

from . import decode_attn as _decode
from . import flash_prefill as _prefill
from . import interpret_mode
from . import paged_decode_attn as _paged
from . import wkv6 as _wkv6
from . import ref

_STATE = {"use_pallas": os.environ.get("REPRO_USE_PALLAS", "0") == "1"}


def configure(use_pallas: bool | None = None):
    if use_pallas is not None:
        _STATE["use_pallas"] = use_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_prefill(q, k, v, lengths=None, *, causal=True, window=0):
    return _prefill.flash_prefill(q, k, v, lengths, causal=causal,
                                  window=window, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("ring",))
def flash_decode(q, k_cache, v_cache, pos, *, ring=False):
    return _decode.flash_decode(q, k_cache, v_cache, pos, ring=ring,
                                interpret=interpret_mode())


@jax.jit
def wkv6(r, k, v, w, u, s0):
    return _wkv6.wkv6(r, k, v, w, u, s0, interpret=interpret_mode())


def prefill_attention(q, k, v, lengths=None, *, causal=True, window=0):
    """Dispatcher used by the engine: Pallas kernel or jnp reference."""
    if _STATE["use_pallas"]:
        return flash_prefill(q, k, v, lengths, causal=causal, window=window)
    return ref.flash_prefill_ref(q, k, v, lengths, causal=causal,
                                 window=window)


def decode_attention(q, k_cache, v_cache, pos, *, ring=False):
    if _STATE["use_pallas"]:
        return flash_decode(q, k_cache, v_cache, pos, ring=ring)
    return ref.flash_decode_ref(q, k_cache, v_cache, pos, ring=ring)


@functools.partial(jax.jit, static_argnames=("s_len", "ring"))
def paged_flash_decode(q, k_pool, v_pool, block_tables, pos, *, s_len,
                       ring=False):
    return _paged.paged_flash_decode(q, k_pool, v_pool, block_tables, pos,
                                     s_len=s_len, ring=ring,
                                     interpret=interpret_mode())


def paged_decode_attention(q, k_pool, v_pool, block_tables, pos, *, s_len,
                           ring=False):
    """Dispatcher: Pallas paged kernel (scalar-prefetched block tables)
    or the gather-then-attend jnp reference."""
    if _STATE["use_pallas"]:
        return paged_flash_decode(q, k_pool, v_pool, block_tables, pos,
                                  s_len=s_len, ring=ring)
    return ref.paged_flash_decode_ref(q, k_pool, v_pool, block_tables, pos,
                                      s_len=s_len, ring=ring)
