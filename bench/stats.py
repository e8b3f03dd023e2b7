"""Percentiles and window arithmetic for the client-side series."""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple


def nearest_rank(xs: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the ceil(q/100 * n)-th smallest sample.
    Raises on an empty series: a percentile of nothing is no number."""
    s = sorted(xs)
    if not s:
        raise ValueError("percentile of an empty series")
    rank = max(-(-int(q * len(s)) // 100), 1)   # ceil without float error
    return s[min(rank, len(s)) - 1]


def tokens_in_window(stamps: Dict[int, List[float]], lo: float,
                     hi: float) -> int:
    """Tokens whose stamp falls in [lo, hi)."""
    return sum(1 for ts in stamps.values() for t in ts if lo <= t < hi)


def gaps_in_window(stamps: Dict[int, List[float]], lo: float,
                   hi: float) -> List[float]:
    """Every gap between consecutive tokens of one request whose two
    stamps both fall in [lo, hi)."""
    out = []
    for ts in stamps.values():
        for a, b in zip(ts, ts[1:]):
            if lo <= a and b < hi:
                out.append(b - a)
    return out


def ttft_series(due: Dict[int, float], first: Dict[int, float], lo: float,
                hi: float) -> Tuple[List[float], List[int]]:
    """TTFT of every request due in [lo, hi), from its due time; and the
    rids of those with no first token (they rank above every TTFT)."""
    served, missing = [], []
    for rid, d in due.items():
        if not lo <= d < hi:
            continue
        if rid in first:
            served.append(first[rid] - d)
        else:
            missing.append(rid)
    return served, missing


def quartile_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median, with Python's ``statistics.quantiles``."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
