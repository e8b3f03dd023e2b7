"""The program's own host spans, and the device's idle time split by
what the host was doing.

A wall-clock ``ServingEngine`` records ``serve.*`` spans
(``core/telemetry.py``) while a profiler session captures, stamped on
the loop clock; the reader finds them on
``run.client.engine.loop.tracer`` and places each on the trace's
timeline by the window's two ends, ``ns = trace.lo + (t - run.lo) x
1e9``.  A program that records none (its tracer has no events) gives
nothing to read, and each metric then reports nothing.

The idle time is the one ``device.idle_frac`` reads: the gaps between
the union of each device's operations inside the window.  Each idle
instant is labelled by the host's program spans around it: ``compile``
inside a ``serve.compile`` span, ``wait`` inside ``serve.loop.wait``
(no work ready) and no compile, ``host`` inside any other program span,
``none`` inside none.  The four shares sum to the idle share.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import reduce as R

PREFIX = "serve."
COMPILE = "serve.compile"
WAIT = "serve.loop.wait"


def program_spans(run) -> Optional[List[Tuple[str, float, float]]]:
    """The program's spans as (name, start ns, end ns) on the trace's
    timeline; None without a device trace or program spans."""
    if run.trace is None:
        return None
    engine = getattr(run.client, "engine", None)
    tracer = getattr(getattr(engine, "loop", None), "tracer", None)
    events = getattr(tracer, "events", None) or ()
    lo_ns, lo = run.trace.lo, run.lo
    out = [(e["name"], lo_ns + (e["ts"] * 1e-6 - lo) * 1e9,
            lo_ns + ((e["ts"] + e["dur"]) * 1e-6 - lo) * 1e9)
           for e in events
           if e["ph"] == "X" and e["name"].startswith(PREFIX)]
    return out or None


def covered(S: np.ndarray, t: np.ndarray) -> np.ndarray:
    """For each point of ``t``, the length of the disjoint sorted
    intervals ``S`` (n, 2) that lies below it."""
    t = np.asarray(t, float)
    if not len(S):
        return np.zeros(len(t))
    cum = np.concatenate([[0.0], np.cumsum(S[:, 1] - S[:, 0])])
    k = np.searchsorted(S[:, 0], t, side="right")   # intervals begun by t
    j = np.maximum(k - 1, 0)
    part = np.clip(np.minimum(t, S[j, 1]) - S[j, 0], 0.0, None)
    return np.where(k > 0, cum[j] + part, 0.0)


def overlap(a: np.ndarray, S: np.ndarray) -> float:
    """Length of the intervals ``a`` (n, 2) that lies inside the
    disjoint sorted intervals ``S``."""
    if not len(a):
        return 0.0
    return float((covered(S, a[:, 1]) - covered(S, a[:, 0])).sum())


def _intervals(spans, keep) -> np.ndarray:
    iv = np.asarray([(s, e) for n, s, e in spans if keep(n)], float)
    return R.union(iv.reshape(-1, 2))


def idle_split(trace: R.Trace, spans) -> Dict[str, float]:
    """Shares of the window, averaged over devices, in which the device
    was idle while the host was compiling, waiting for work, doing other
    program work, or in no program span."""
    C = _intervals(spans, lambda n: n == COMPILE)
    WC = _intervals(spans, lambda n: n in (COMPILE, WAIT))
    A = _intervals(spans, lambda n: True)
    out = {"compile": 0.0, "wait": 0.0, "host": 0.0, "none": 0.0}
    for ev in trace.ops:
        g = R.gaps(R.union(R.clip(ev.intervals(), trace.lo, trace.hi)),
                   trace.lo, trace.hi)
        idle = float((g[:, 1] - g[:, 0]).sum())
        c, wc, a = overlap(g, C), overlap(g, WC), overlap(g, A)
        for k, v in (("compile", c), ("wait", wc - c), ("host", a - wc),
                     ("none", idle - a)):
            out[k] += v
    width = (trace.hi - trace.lo) * len(trace.ops)
    return {k: v / width for k, v in out.items()}


def read_share(run, key: str) -> Optional[float]:
    """One share of ``idle_split`` for a run, or None where the run has
    no device trace or the program no spans."""
    spans = program_spans(run)
    if spans is None:
        return None
    return idle_split(run.trace, spans)[key]
