"""Operations and bytes the dense GQA decoder's work needs, from its
sizes.  ``m`` is a configuration file's ``model`` block.

A token costs two operations per weight it multiplies (every layer's
projections and MLP, and the output head; the embedding is a lookup),
plus attention over its context: for each layer and query head, a dot
product with each key and a weighted sum of each value, ``4 * d_head``
operations per context token.  A decode step reads every weight once
and each live request's keys and values once; on a mesh, each chip
holds and reads its share.
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple


def matmul_params(m: dict) -> int:
    """Weights a token multiplies: layers' projections and MLP, head."""
    d, ff = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return m["n_layers"] * layer + m["vocab_size"] * d


def weight_bytes(m: dict, bytes_per: int = 2) -> int:
    """Bytes a decode step reads of the weights: every matrix, the norms,
    the head (the embedding rows it looks up are left out)."""
    d = m["d_model"]
    norms = m["n_layers"] * 2 * d + d
    if m["qk_norm"]:
        norms += m["n_layers"] * 2 * m["d_head"]
    return (matmul_params(m) + norms) * bytes_per


def kv_bytes_per_token(m: dict, bytes_per: int = 2) -> int:
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"] * bytes_per


def attn_flops(m: dict, context: int) -> int:
    """One query token attending ``context`` tokens, every layer."""
    return 4 * m["n_layers"] * m["n_heads"] * m["d_head"] * context


def decode_step(m: dict, contexts: Sequence[int],
                chips: int = 1) -> Tuple[float, float]:
    """(operations, bytes) per chip of one decode step whose live
    requests attend ``contexts`` tokens each (their prompt and the
    tokens generated so far, the new one included)."""
    flops = len(contexts) * 2 * matmul_params(m) + sum(
        attn_flops(m, c) for c in contexts)
    nbytes = weight_bytes(m) + kv_bytes_per_token(m) * sum(contexts)
    return flops / chips, nbytes / chips


def prefill_flops(m: dict, spans: Iterable[Tuple[int, int]]) -> float:
    """Operations of prompt tokens run through prefill: ``spans`` holds,
    per request, (first position, real tokens); each token attends every
    position up to its own (causal)."""
    total = 0.0
    for start, n in spans:
        total += n * 2 * matmul_params(m)
        # sum over p in [start, start + n) of (p + 1)
        total += attn_flops(m, 1) * (n * start + n * (n + 1) / 2)
    return total


def bound_s(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take: the larger of operations at
    peak rate and bytes at peak bandwidth, and which of the two it is."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
