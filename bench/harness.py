"""One run of one cell: set up, measure a window, check, report.

The system under test is the program's own serving path, built as
``launch/serve.py`` builds it: ``ServingEngine`` -> ``ServingLoop`` ->
``JaxEngineBackend`` on the paged KV pool with chunked prefill, a
``BucketServeScheduler`` under the paged memory model, and a
``MemoryBudget`` from the device's ``bytes_limit``.  The benchmark
makes the weights (``weights.py``) and the requests (``traffic.py``).

Client-side stamps: the backend's protocol calls ``prefill_chunk`` and
``decode_iter`` are wrapped on the instance.  After each call, every
token that newly appeared in the engine's outputs is stamped on
``time.perf_counter``, aligned with the loop clock's zero; a token is
stamped the first time its index appears (a preempted request that
regenerates tokens does not deliver them twice).  Both calls read their
tokens back to the host before they return, so a stamp follows the
token's arrival there.  The arrival of a request is its due time on the
schedule; the scheduler's ``on_arrival`` is wrapped only to see how late
the loop admitted it.

Set-up is everything before the window opens: process start, weights,
the pool, a warm-up that serves a fixed set of requests through the same
engine so that every batch size of both prefill programs and the decode
program is compiled or loaded, and a pre-roll of the cell's own traffic.
The window is fixed on the loop clock: it opens exactly ``preroll_s``
after the loop starts, whatever the loop is doing then, and lasts
``seconds``.  The loop's idle waits (``clock.advance``) are wrapped as
well, so the harness sees the clock pass its marks between calls.  Then
the run serves on, arrivals still coming, until every request due in
the window has its first token, or the mix's drain cap ends it.
Compile and compile-cache events are stamped by ``jax.monitoring``
listeners and counted by their time.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",)
WARMUP_RID = 1 << 40
# the profiler starts this long before the window opens
TRACE_LEAD_S = 3.0


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Stop(Exception):
    """Ends the loop once the window is closed and drained."""


class CompileCounter:
    """Compiles and compile-cache loads, from JAX's own events, each
    stamped on ``time.perf_counter`` when JAX reports it."""

    def __init__(self):
        import jax
        self.compiles: List[float] = []
        self.loads: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.compiles.append(time.perf_counter())

    def _event(self, event, **_):
        if event in CACHE_EVENTS:
            self.loads.append(time.perf_counter())

    def between(self, a: float, b: float):
        """(compiles, cache loads) stamped in [a, b)."""
        return (sum(a <= t < b for t in self.compiles),
                sum(a <= t < b for t in self.loads))


@dataclasses.dataclass
class Call:
    kind: str                   # "prefill" | "decode"
    t0: float                   # loop seconds at entry
    rows: int = 0               # prefill: batch rows
    pad_to: int = 0             # prefill: padded prompt length
    real_tokens: int = 0        # prefill: real prompt tokens in this chunk
    positions: tuple = ()       # prefill: (start, n) of real tokens per row
    rids: tuple = ()            # prefill: the batch's requests
    chunked: bool = False       # prefill: the chunk program, not one span
    first_chunk: bool = False   # prefill: first chunk of its batch
    prompt_tokens: int = 0      # prefill, first chunk: real prompt tokens
    live: int = 0               # decode: live slots
    contexts: tuple = ()        # decode: tokens each live slot attends
    pages_used: int = 0         # decode: pool pages in use
    pages_total: int = 0


class Client:
    """The wrappers around one engine, and what they record."""

    def __init__(self, engine, window_opens: float, seconds: float,
                 drain_cap: float, on_start=None, lead: float = 0.0,
                 on_close=None):
        self.engine = engine
        be = engine.backend
        self.t_zero = None
        self.stamps: Dict[int, List[float]] = {}
        self.due: Dict[int, float] = {}
        self.max_new: Dict[int, int] = {}
        self.requests: list = []
        self.admitted: Dict[int, float] = {}
        self.calls: List[Call] = []
        self.opens_at = window_opens
        self.seconds = seconds
        self.drain_cap = drain_cap
        self.lo = self.hi = None
        # ``on_start`` (the profiler) runs at the first hook ``lead``
        # seconds or less before the window opens, ``on_close`` at the
        # first hook after it closes; the drain cap counts from its end
        self.on_start, self.lead, self.on_close = on_start, lead, on_close
        self.started = self.closed = False
        self.drain_from = None
        self._orig = {k: getattr(be, k) for k in
                      ("begin", "prefill_chunk", "decode_iter")}
        self._orig_advance = be.clock.advance
        self._orig_arrival = engine.sched.on_arrival
        be.begin = self._begin
        be.prefill_chunk = self._prefill_chunk
        be.decode_iter = self._decode_iter
        be.clock.advance = self._advance
        engine.sched.on_arrival = self._on_arrival
        self.measuring = False

    def now(self) -> float:
        return time.perf_counter() - self.t_zero

    # ---------------------------------------------------------- wrappers
    def _begin(self, requests):
        self._orig["begin"](requests)
        clock = self.engine.backend.clock
        self.t_zero = time.perf_counter() - clock.now()

    def _on_arrival(self, req, now, *a, **kw):
        if req.rid not in self.admitted:
            self.admitted[req.rid] = self.now()
        return self._orig_arrival(req, now, *a, **kw)

    def _advance(self, to):
        self._gate()
        self._orig_advance(to)

    def _gate(self):
        """Before a call or an idle wait: start the profiler ahead of the
        window, stop it once the window has closed, or end the run."""
        if not self.measuring:
            return
        t = self.now()
        if not self.started and t >= self.lo - self.lead:
            self.started = True
            if self.on_start:
                self.on_start()
        if t < self.hi:
            return
        if not self.closed:
            self.closed = True
            if self.on_close:
                self.on_close()
            self.drain_from = self.now()
        waiting = [rid for rid, d in self.due.items()
                   if self.lo <= d < self.hi and rid not in self.stamps]
        if not waiting or self.now() >= self.drain_from + self.drain_cap:
            raise _Stop

    def _stamp(self, reqs):
        t = self.now()
        outs = self.engine.backend.outputs
        for r in reqs:
            n = len(outs.get(r.rid, ())) - len(self.stamps.get(r.rid, ()))
            if n > 0:
                self.stamps.setdefault(r.rid, []).extend([t] * n)

    def _prefill_chunk(self, job, idx):
        self._gate()
        reqs = list(job.batch.requests)
        start, clen = job.chunks[idx]
        pos = tuple((start, int(min(max(r.prompt_len - start, 0), clen)))
                    for r in reqs)
        call = Call("prefill", self.now(), rows=len(reqs),
                    pad_to=job.batch.pad_to,
                    real_tokens=sum(n for _, n in pos), positions=pos,
                    chunked=len(job.chunks) > 1 or job.chunks[0][0] > 0,
                    first_chunk=idx == 0, rids=tuple(r.rid for r in reqs),
                    prompt_tokens=sum(r.prompt_len for r in reqs))
        out = self._orig["prefill_chunk"](job, idx)
        self._stamp(reqs)
        self.calls.append(call)
        return out

    def _decode_iter(self, pool, context_tokens):
        self._gate()
        reqs = list(pool)
        be = self.engine.backend
        alloc = getattr(be, "alloc", None)
        call = Call("decode", self.now(), live=len(reqs),
                    contexts=tuple(r.prompt_len + r.generated
                                   for r in reqs))
        if alloc is not None:
            call.pages_total = alloc.n_pages
            call.pages_used = alloc.n_pages - alloc.free_pages()
        out = self._orig["decode_iter"](pool, context_tokens)
        self._stamp(reqs)
        self.calls.append(call)
        return out

    # ---------------------------------------------------------- serving
    def serve(self, requests, measuring: bool):
        """Serve ``requests`` through the engine's loop; with
        ``measuring``, until the window has closed and drained."""
        self.measuring = measuring
        if measuring:
            self.lo = self.opens_at
            self.hi = self.lo + self.seconds
        try:
            self.engine.loop.run(requests, time_limit=math.inf)
        except _Stop:
            pass
        self.measuring = False


def make_requests(arrivals):
    from repro.core.request import Request
    return [Request(rid=a.rid, prompt_len=len(a.prompt),
                    max_new_tokens=a.max_new, arrival=a.due,
                    tokens=a.prompt, cls=a.cls) for a in arrivals]


def warmup_sets(slots: int, chunk: int, vocab: int):
    """(rows, chunked, arrivals): request sets, all due at once, that
    make the loop run every batch size, from 1 to ``slots``, of the
    one-span prefill program and of the chunk program: prompts of one
    chunk and of three, three tokens out."""
    import traffic
    rng = np.random.default_rng(0)
    rid = WARMUP_RID
    sets = []
    for rows in range(1, slots + 1):
        for plen in (chunk - 28, 3 * chunk - 84):
            batch = [traffic.Arrival(rid + i, 0.0, rng.integers(
                0, vocab, plen, dtype=np.int32), 3, "warmup")
                for i in range(rows)]
            rid += rows
            sets.append((rows, plen > chunk, batch))
    return sets


def model_config(cfgfile: dict):
    """The program's ModelConfig for the configuration file: the
    registry's entry with every field the file states set as stated."""
    from repro.configs import get_config
    from repro.models.config import ModelConfig
    base = get_config(cfgfile["arch"])
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    stated = {k: v for k, v in cfgfile["model"].items() if k in fields}
    changed = {k: (getattr(base, k), v) for k, v in stated.items()
               if getattr(base, k) != v}
    if changed:
        print(f"config: set as the file states (registry, file): {changed}",
              file=sys.stderr)
    return dataclasses.replace(base, **stated)


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak():
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def free_device_memory():
    """Drop every array this process holds on its devices."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def run_cell(cfgfile: dict, mix: dict, seed: int,
             seconds: float, trace: bool, metrics: List[dict], *,
             t_proc: float, model=None, rate: Optional[float] = None,
             control: bool = False, tamper=None, check: bool = True) -> dict:
    """One run; returns the result line as a dict.  ``model`` replaces
    the configuration's ModelConfig (smaller sizes, for tests); ``rate``
    replaces the mix's arrival rate (for a rate sweep); ``control``
    adds the float8 control's verdict (``out["control"]``);
    ``tamper(engine)``, if given, runs after the warm-up (tests plant
    faults with it); without ``check`` the run skips the reference (a
    rate sweep)."""
    import jax

    import check as C
    import reduce as R
    import traffic as T
    import weights as W
    from repro.core import BucketServeScheduler, MemoryBudget, SchedulerConfig
    from repro.core.engine import ServingEngine
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import context as shctx
    from repro.sharding import partition

    serve.use_compile_cache()
    counter = CompileCounter()
    phases = {}
    t = time.perf_counter()
    cfg = model if model is not None else model_config(cfgfile)
    m = dict(cfgfile["model"])
    if model is not None:
        m.update({k: getattr(cfg, k) for k in m if hasattr(cfg, k)})
    if rate is not None:
        mix = {**mix, "rate_rps": rate}
    chips = int(cfgfile["chips"])
    mesh = None
    if chips > 1:
        mesh = make_host_mesh(cfgfile["mesh"]["data"], cfgfile["mesh"]["model"])
        shctx.set_mesh(mesh)

    make = functools.partial(W.make_params, m)
    root = W.root_key(seed)
    if mesh is None:
        params = jax.jit(make)(root)
    else:
        specs = partition.param_specs(cfg, jax.eval_shape(make, root), mesh)
        params = jax.jit(make, out_shardings=partition.to_shardings(
            mesh, specs))(root)
    jax.block_until_ready(params)
    phases["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    slots = int(cfgfile["slots"])
    budget = MemoryBudget(
        hbm_bytes_per_device=serve.device_hbm_bytes(model is not None),
        n_devices=chips,
        weight_bytes=sum(x.nbytes for x in jax.tree.leaves(params)))
    sched = BucketServeScheduler(cfg, budget, SchedulerConfig(
        max_batch=slots, trigger="waste", memory_model="paged",
        page_size=int(cfgfile["page"])))
    engine = ServingEngine(cfg, params, sched, max_slots=slots,
                           cache_len=cfg.max_seq_len, moe_impl="local",
                           chunk_tokens=int(cfgfile["chunk"]), paged=True,
                           page_size=int(cfgfile["page"]),
                           kv_pool_tokens=int(cfgfile["pool_tokens"]))
    jax.block_until_ready(engine.backend.pool_cache)
    phases["pool_s"] = time.perf_counter() - t

    state = {"trace_dir": None}

    def start_profiler():
        """Starts the profiler, and marks on its timeline the moment
        ``anchor_abs`` from which the window's place there is reckoned."""
        t0 = time.perf_counter()
        state["trace_dir"] = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-Python-call events
        jax.profiler.start_trace(state["trace_dir"], profiler_options=opts)
        with jax.profiler.TraceAnnotation(R.ANCHOR):
            state["anchor_abs"] = time.perf_counter()
        phases["profiler_start_s"] = state["anchor_abs"] - t0

    def stop_profiler():
        """Stops the profiler as the window closes; writing the trace
        out stalls the host for a minute or more, before the drain."""
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        phases["trace_stop_s"] = time.perf_counter() - t0

    client = Client(engine, float(mix.get("preroll_s", 0)), seconds,
                    float(mix.get("drain_cap_s", 60)),
                    start_profiler if trace else None, TRACE_LEAD_S,
                    stop_profiler if trace else None)
    if trace:
        _annotate(client, jax)

    # warm-up: every batch size of both prefill programs, and decode
    t = time.perf_counter()
    seen = set()
    for rows, chunked, batch in warmup_sets(slots, int(cfgfile["chunk"]),
                                            cfg.vocab_size):
        if (rows, chunked) not in seen:
            client.serve(make_requests(batch), measuring=False)
        seen |= {(c.rows, c.chunked) for c in client.calls
                 if c.kind == "prefill"}
    want = {(b, c) for b in range(1, slots + 1) for c in (False, True)}
    if want - seen:
        print(f"warm-up: batch shapes not run: {sorted(want - seen)}",
              file=sys.stderr)
    n_prefill, n_decode = engine.backend.compiled_shapes()
    phases["warmup_s"] = time.perf_counter() - t
    client.calls.clear()
    client.stamps.clear()
    if tamper is not None:
        tamper(engine)

    # pre-roll, window, drain: one run of the loop over the cell's traffic
    arrivals = T.generate(mix, seed, cfg.max_seq_len, cfg.vocab_size)
    client.due = {a.rid: a.due for a in arrivals}
    client.max_new = {a.rid: a.max_new for a in arrivals}
    reqs = make_requests(arrivals)
    client.requests = reqs
    t = time.perf_counter()
    client.serve(reqs, measuring=True)
    if not client.closed:
        raise SystemExit(f"the loop ended {client.now() - client.lo:.1f} s "
                         f"from the opening of a {seconds} s window")
    lo_abs, hi_abs = client.t_zero + client.lo, client.t_zero + client.hi
    phases["preroll_s"] = lo_abs - t
    setup_s = lo_abs - t_proc
    mem = memory_peak()

    run = R.Run(cfg=m, cfgfile=cfgfile, mix=mix, chips=chips, client=client,
                lo=client.lo, hi=client.hi, setup_s=setup_s,
                compiles=counter.between(lo_abs, hi_abs),
                device=device_info())
    if trace:
        t = time.perf_counter()
        tr = R.read_trace(state["trace_dir"], lo_abs - state["anchor_abs"],
                          hi_abs - state["anchor_abs"])
        phases["trace_read_s"] = time.perf_counter() - t
        run.trace = tr if tr.ops else None       # no device planes: no chip
        shutil.rmtree(state["trace_dir"], ignore_errors=True)

    values = {}
    for spec in metrics:
        v = load_reader(spec["name"])(run)
        if v is not None:
            values[spec["name"]] = {"value": v, "unit": spec["unit"]}

    if not check:
        summary = run.summary()
        del engine, client, params, sched, run
        free_device_memory()
        return {"result": {"attempted": summary["attempted"],
                           "failed": summary["failed"], "metrics": values,
                           "device": summary["device"]},
                "info": {"setup_s": setup_s, **summary["info"]}}

    # the check: a sample of what the window produced, against the plain
    # reference, once the program's state is freed
    reqs_by_rid = {r.rid: r for r in reqs}
    sample = C.pick_sample(client, reqs_by_rid, seed,
                           cfgfile["check"]["max_requests"],
                           cfgfile["check"]["min_tokens"])
    prompts = [np.asarray(reqs_by_rid[rid].tokens[:reqs_by_rid[rid].prompt_len])
               for rid in sample]
    served = [np.asarray(engine.backend.outputs[rid], np.int32)
              for rid in sample]
    summary = run.summary()
    del engine, client, params, sched, run
    free_device_memory()
    t = time.perf_counter()
    verdict = C.compare(m, seed, prompts, served, cfgfile["limits"],
                        control=control, mesh=C.reference_mesh(chips))
    check_s = time.perf_counter() - t

    device = dict(summary["device"], memory_peak_bytes=mem)
    if "busy_s" in summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["trace_window_s"]
    result = {"correct": verdict["correct"],
              "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": values,
              "device": device}
    if "breakdown" in summary:
        result["breakdown"] = summary["breakdown"]
    info = {"phases_s": phases, "setup_s": setup_s,
            "check_s": check_s, "compiled": [n_prefill, n_decode],
            "sample": [[int(len(p)), int(len(s))] for p, s in
                       zip(prompts, served)], **summary["info"],
            **verdict["info"]}
    out = {"result": result, "check": verdict["numbers"], "info": info}
    if "control" in verdict:
        out["control"] = verdict["control"]
    return out


def _annotate(client: Client, jax):
    """Trace runs: each wrapped protocol call inside a named span on the
    profiler's own timeline."""
    for name in ("prefill_chunk", "decode_iter"):
        inner = getattr(client.engine.backend, name)

        def wrapped(*a, _inner=inner, _name=f"bench.{name}"):
            with jax.profiler.TraceAnnotation(_name):
                return _inner(*a)
        setattr(client.engine.backend, name, wrapped)
