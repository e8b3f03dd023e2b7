"""Traffic generator: one general generator, driven by a mix's data file.

A mix is ``bench/traffic/<name>.json``.  Its keys:

* ``mode``: ``"open"`` (arrivals on a schedule, independent of the
  server: TTFT counts from the due time) or ``"offline"`` (the whole
  queue is present at time 0, as in batch document summarisation).
* ``rate_rps``: mean arrival rate of the open loop.
* ``n_requests``: requests in the schedule, enough to cover pre-roll,
  window and drain at the mix's rate (or to keep an offline queue deeper
  than a run can serve).
* ``classes``: ``[{"name", "share", "prompt", "output"}]``; ``prompt``
  and ``output`` are ``{"dist": "lognormal", "median" | "mean", "sigma"}``.
* ``output_clip``: ``[lo, hi]`` output tokens.  A prompt is clipped to
  ``context - its output``, so an output is never cut short.
* ``block``: requests per stratification block (below).
* ``bursts``: ``null`` or ``{"factor", "every_s", "duration_s"}``:
  arrival gaps inside a burst window shrink by ``factor``.
* ``prefix``: ``null`` or ``{"groups", "tokens", "zipf"}``: every prompt
  starts with one of ``groups`` shared prefixes of ``tokens`` ids, drawn
  with Zipf reuse.
* ``sessions``: ``null``.  Multi-turn sessions are not generated yet.

Every seed serves the same work in another order.  The sizes are not
drawn at random: each class's prompt and output lengths are the
quantiles of its distribution at evenly spaced points, and the arrival
gaps the quantiles of the exponential.  The seed only orders them
(stratified, so every ``block`` of arrivals holds each class in its
share, lengths from every part of the distribution, and the same total
of gaps) and draws the token ids.  So two seeds differ in which request
meets which queue, not in how much work a window holds.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Arrival:
    rid: int
    due: float                  # seconds after the loop's clock starts
    prompt: np.ndarray          # int32 token ids
    max_new: int
    cls: str


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """The distribution's quantiles at (i + 0.5) / n, ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    sigma = float(dist["sigma"])
    if "median" in dist:
        mu = math.log(dist["median"])
    else:                               # mean = exp(mu + sigma^2 / 2)
        mu = math.log(dist["mean"]) - sigma ** 2 / 2
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.exp(mu + sigma * z)


def _stratified(values: np.ndarray, blocks: int, rng) -> np.ndarray:
    """Deal sorted ``values`` into ``blocks`` equal blocks so that each
    block takes one value from every stratum of consecutive values;
    the order inside each block is shuffled.  Returns the values
    block after block."""
    per = len(values) // blocks
    strata = np.sort(values)[:per * blocks].reshape(per, blocks)
    out = np.stack([s[rng.permutation(blocks)] for s in strata], axis=1)
    for row in out:
        rng.shuffle(row)
    return out.reshape(-1)


def generate(mix: dict, seed: int, context: int, vocab: int) -> List[Arrival]:
    """The mix's schedule for ``seed``: the same lengths and gaps for
    every seed, ordered and filled with token ids from the seed."""
    if mix.get("sessions"):
        raise ValueError("multi-turn sessions are not generated yet")
    n = int(mix["n_requests"])
    block = int(mix.get("block", 8))
    if n % block:
        raise ValueError(f"n_requests {n} is not a multiple of block {block}")
    blocks = n // block
    rng = np.random.default_rng([seed, 0x7AFF1C])

    # classes: each block holds every class in its share
    labels = []
    for c in mix["classes"]:
        k = round(float(c["share"]) * block)
        labels += [c["name"]] * k
    if len(labels) != block:
        raise ValueError(f"class shares do not fill a block of {block}")
    order = np.concatenate([rng.permutation(labels) for _ in range(blocks)])

    lo, hi = mix["output_clip"]
    plens = np.zeros(n, np.int64)
    olens = np.zeros(n, np.int64)
    for c in mix["classes"]:
        idx = np.flatnonzero(order == c["name"])
        p = _stratified(_quantiles(c["prompt"], len(idx)), blocks, rng)
        o = _stratified(_quantiles(c["output"], len(idx)), blocks, rng)
        # block b's members of this class take block b's values
        plens[idx] = np.rint(p).astype(np.int64)
        olens[idx] = np.clip(np.rint(o), lo, hi).astype(np.int64)
    olens = np.minimum(olens, context - 4)
    plens = np.clip(plens, 4, context - olens)

    if mix["mode"] == "offline":
        due = np.zeros(n)
    else:
        rate = float(mix["rate_rps"])
        u = (np.arange(n) + 0.5) / n
        # one gap from every stratum in each block: blocks of arrivals
        # span nearly the same time
        gaps = _stratified(-np.log1p(-u) / rate, blocks, rng)
        due = np.cumsum(gaps)
        bursts = mix.get("bursts")
        if bursts:
            # burst windows start at exponential gaps; inside one, the
            # next arrival comes ``factor`` times sooner
            starts = np.cumsum(rng.exponential(float(bursts["every_s"]),
                                               len(gaps)))
            t, scaled = 0.0, []
            for g in gaps:
                k = np.searchsorted(starts, t, side="right") - 1
                inside = k >= 0 and t < starts[k] + float(bursts["duration_s"])
                t += g / float(bursts["factor"]) if inside else g
                scaled.append(t)
            due = np.asarray(scaled)

    prefix = mix.get("prefix")
    shared = []
    if prefix:
        shared = [rng.integers(0, vocab, int(prefix["tokens"]), dtype=np.int32)
                  for _ in range(int(prefix["groups"]))]
        picks = (rng.zipf(float(prefix["zipf"]), n) - 1) % len(shared)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(plens[i]), dtype=np.int32)
        if shared:
            pre = shared[int(picks[i])][:max(int(plens[i]) - 1, 0)]
            toks[:len(pre)] = pre
        out.append(Arrival(rid=i, due=float(due[i]), prompt=toks,
                           max_new=int(olens[i]), cls=str(order[i])))
    return out
