"""From what a run recorded to numbers: the run's record, and the
reduction of the profiler's device trace.

The trace (``jax.profiler``, read with ``ProfileData``) holds, on one
plane per device, the operations that ran there ("XLA Ops") and the
programs they belong to ("XLA Modules"), and on the host plane the
harness's own spans: ``bench.anchor``, marked as the profiler started,
and ``bench.prefill_chunk`` / ``bench.decode_iter`` around each wrapped
protocol call.  The window's place on the trace's timeline is reckoned
from the anchor, whose moment the harness also read on its own clock.
Device busy time is the union of the operations' intervals inside the
window.  Device time is attributed to a protocol call by the span the
host was in when the program started: a decode call reads its token back
before it returns, so its step runs inside its span, and a chunk step
starts on an idle device right after it is dispatched (the engine awaits
the previous chunk first).  Names are used only where they are unique:
the prefill and chunk programs are both local closures named ``fn``, and
the decode step and the batch cache builder are both
``functools.partial`` objects, which JAX names ``_unknown``.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# operations that hold other operations on the same line of the trace
CONTAINER = re.compile(r"%?(while|conditional|call)[.\s=]")
ANCHOR = "bench.anchor"
# programs that only a prefill call launches: a chunk step dispatched at
# the end of one call can start on the device after the next call began
PREFILL_PROGRAMS = ("fn",)
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|allreduce|allgather",
                        re.I)


def peaks_for(kind: str) -> dict:
    """Peak rates of a device kind, from ``peaks.json``; an unknown kind
    is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def module_name(name: str) -> str:
    """``jit_decode_step(1234)`` / ``jit_fn.3`` -> ``decode_step`` / ``fn``."""
    name = re.sub(r"\(.*\)$", "", name.strip())
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def union(iv: np.ndarray) -> np.ndarray:
    """Merge intervals (n, 2) into disjoint sorted intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=float)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]] if len(iv) else iv.reshape(0, 2)


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Idle intervals between disjoint busy intervals, inside [lo, hi]."""
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def label_at(spans: List[Tuple[str, float, float]], t: float) -> str:
    """Name of the innermost harness span around ``t``, or ``host``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "host"


@dataclasses.dataclass
class Events:
    """One line of one plane: starts and ends (ns) and names."""
    start: np.ndarray
    end: np.ndarray
    names: List[str]

    @staticmethod
    def of(evs) -> "Events":
        s, e, n = [], [], []
        for ev in evs:
            s.append(ev[0])
            e.append(ev[1])
            n.append(ev[2])
        return Events(np.asarray(s, float), np.asarray(e, float), n)

    def intervals(self) -> np.ndarray:
        return np.stack([self.start, self.end], 1) if len(self.start) \
            else np.zeros((0, 2))


@dataclasses.dataclass
class Trace:
    lo: float                             # the window, ns
    hi: float
    spans: List[Tuple[str, float, float]]  # harness spans on the host
    ops: List[Events]                     # per device
    modules: List[Events]                 # per device

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, dev: int) -> float:
        b = union(clip(self.ops[dev].intervals(), self.lo, self.hi))
        return float((b[:, 1] - b[:, 0]).sum()) * 1e-9

    def mean_busy_s(self) -> float:
        return float(np.mean([self.busy_s(d) for d in range(len(self.ops))]))

    def module_time(self, pred, dev: int = 0) -> Tuple[float, int]:
        """(seconds, count) of programs on ``dev`` whose reduced name
        satisfies ``pred`` and that start inside the window."""
        ev = self.modules[dev]
        tot, n = 0.0, 0
        for s, e, name in zip(ev.start, ev.end, ev.names):
            if self.lo <= s < self.hi and pred(module_name(name), s):
                tot += e - s
                n += 1
        return tot * 1e-9, n

    def in_span(self, kind: str, t: float) -> bool:
        return any(n == kind and s <= t < e for n, s, e in self.spans)

    def span_programs(self, kind: str, dev: int = 0) -> List[List[Tuple]]:
        """For each harness span of ``kind`` that starts inside the
        window, the programs (name, seconds) that started on ``dev``
        while the host was in it."""
        ev = self.modules[dev]
        order = np.argsort(ev.start, kind="stable")
        starts = ev.start[order]
        out = []
        for name, s, e in self.spans:
            if name != kind or not self.lo <= s < self.hi:
                continue
            i, j = np.searchsorted(starts, [s, e])
            out.append([(ev.names[k], (ev.end[k] - ev.start[k]) * 1e-9)
                        for k in order[i:j]])
        return out

    def step_times(self, kind: str, dev: int = 0,
                   exclude=()) -> List[float]:
        """Per span of ``kind`` in the window, the device time of every
        program it launched (for a decode call: the step, the argmax and
        any small op), so that fusing or splitting programs leaves the
        work's time as it was; programs whose reduced name is in
        ``exclude`` belong to another kind of call."""
        out = []
        for progs in self.span_programs(kind, dev):
            own = [t for n, t in progs if module_name(n) not in exclude]
            if own:
                out.append(sum(own))
        return out

    def op_time(self, pattern, dev: int) -> float:
        ev = self.ops[dev]
        tot = 0.0
        for s, e, name in zip(ev.start, ev.end, ev.names):
            if self.lo <= s < self.hi and pattern.search(name):
                tot += min(e, self.hi) - s
        return tot * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """The device operations that took most time, seconds per
        device (mean over devices).  Loops and calls are left out: their
        bodies' operations are listed themselves."""
        tot: Dict[str, float] = {}
        for ev in self.ops:
            for s, e, name in zip(ev.start, ev.end, ev.names):
                if self.lo <= s < self.hi and not CONTAINER.match(name):
                    tot[name] = tot.get(name, 0.0) + (min(e, self.hi) - s)
        nd = max(len(self.ops), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9 / nd] for n, v in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The longest idle gaps on device 0, named by the harness span
        the host was in at the gap's middle."""
        b = union(clip(self.ops[0].intervals(), self.lo, self.hi))
        g = gaps(b, self.lo, self.hi)
        g = g[np.argsort(g[:, 0] - g[:, 1], kind="stable")][:k]
        return [[label_at(self.spans, (s + e) / 2), (e - s) * 1e-9]
                for s, e in g]


def _device_planes(pd):
    out = []
    for pl in pd.planes:
        m = re.match(r"/device:(TPU|GPU):(\d+)$", pl.name)
        if m:
            out.append((int(m.group(2)), pl))
    return [pl for _, pl in sorted(out, key=lambda x: x[0])]


def read_trace(trace_dir: str, lo_s: float, hi_s: float) -> Trace:
    """The trace under ``trace_dir``, its window ``lo_s`` to ``hi_s``
    seconds after the anchor; a window that opened before the profiler
    started is cut to the anchor."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(sorted(paths)[-1])
    spans, anchor = [], None
    for pl in pd.planes:
        if not pl.name.startswith("/host:"):
            continue
        for line in pl.lines:
            for ev in line.events:
                if ev.name == ANCHOR:
                    anchor = float(ev.start_ns)
                elif ev.name.startswith("bench."):
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.end_ns)))
    if anchor is None:
        raise ValueError(f"the trace holds no {ANCHOR} span")
    ops, modules = [], []
    for pl in _device_planes(pd):
        lines = {ln.name: ln for ln in pl.lines}
        take = lambda ln: Events.of(
            (float(e.start_ns), float(e.end_ns), e.name) for e in ln.events)
        ops.append(take(lines["XLA Ops"]) if "XLA Ops" in lines
                   else Events.of([]))
        modules.append(take(lines["XLA Modules"]) if "XLA Modules" in lines
                       else Events.of([]))
    return Trace(anchor + max(lo_s, 0.0) * 1e9, anchor + hi_s * 1e9, spans,
                 ops, modules)


@dataclasses.dataclass
class Run:
    """What one run recorded, for the metric readers
    (``bench/metrics/<name>.py``, each ``read(run) -> float | None``)."""
    cfg: dict                 # the configuration file's "model" sizes
    cfgfile: dict
    mix: dict
    chips: int
    client: object            # harness.Client: stamps, due times, calls
    lo: float                 # window, loop seconds
    hi: float
    setup_s: float
    compiles: tuple           # (compiles, cache loads) inside the window
    device: dict
    trace: Optional[Trace] = None

    def calls(self, kind: str):
        """The wrapped calls of ``kind`` that began inside the window."""
        return [c for c in self.client.calls
                if c.kind == kind and self.lo <= c.t0 < self.hi]

    @property
    def stamps(self) -> Dict[int, List[float]]:
        return self.client.stamps

    @property
    def online(self) -> bool:
        return self.mix["mode"] == "open"

    def ttft(self):
        """(TTFTs of the requests due in the window, rids without one)."""
        import stats
        first = {rid: s[0] for rid, s in self.stamps.items() if s}
        return stats.ttft_series(self.client.due, first, self.lo, self.hi)

    def peaks(self) -> dict:
        return peaks_for(self.device["kind"])

    def summary(self) -> dict:
        import stats
        due, stamps = self.client.due, self.stamps
        lo, hi = self.lo, self.hi
        served, missing = self.ttft()
        dropped = {r.rid for r in self.client.requests if r.dropped}
        if self.online:
            attempted = len(served) + len(missing)
            failed = len(set(missing) | {rid for rid in dropped
                                         if lo <= due[rid] < hi})
        else:
            attempted = sum(1 for s in stamps.values()
                            if any(lo <= t < hi for t in s))
            failed = len(dropped)
        done = sum(1 for rid, s in stamps.items()
                   if len(s) == self.client.max_new.get(rid)
                   and lo <= s[-1] < hi)
        late = [self.client.admitted[rid] - d for rid, d in due.items()
                if lo <= d < hi and rid in self.client.admitted]
        first = {rid: s[0] for rid, s in stamps.items()}
        waiting = lambda t: sum(1 for rid, d in due.items() if d <= t
                                and first.get(rid, float("inf")) > t)
        ttft = {"n": len(served), "no_first_token": len(missing)}
        if served:
            ttft.update(p50=stats.nearest_rank(served, 50),
                        p90=stats.nearest_rank(served, 90))
        info = {"arrived_in_window": len(served) + len(missing),
                "ttft_s": ttft,
                "waiting_at_open": waiting(lo), "waiting_at_close": waiting(hi),
                "completed_in_window": done,
                "tokens_in_window": stats.tokens_in_window(stamps, lo, hi),
                "generator_late_s": {
                    "p50": stats.nearest_rank(late, 50) if late else None,
                    "max": max(late) if late else None}}
        out = {"attempted": attempted, "failed": failed, "info": info,
               "device": self.device}
        if self.trace is not None:
            out["busy_s"] = self.trace.mean_busy_s()
            out["trace_window_s"] = self.trace.window_s
            out["breakdown"] = {"device_ops": self.trace.top_ops(),
                                "idle_gaps": self.trace.idle_gaps()}
            progs: Dict[str, List] = {}
            for call in self.trace.span_programs("bench.decode_iter"):
                for name, t in call:
                    c = progs.setdefault(module_name(name), [0, 0.0])
                    c[0] += 1
                    c[1] += t
            info["decode_call_programs"] = sorted(
                ([n, c, t] for n, (c, t) in progs.items()),
                key=lambda x: -x[2])[:10]
        return out
