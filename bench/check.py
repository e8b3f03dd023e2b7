"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests it finished is
drawn from the seed: the longest, the one with the shortest prompt, one
from each prefill batch shape the window ran (rows, and one span or
chunks) where one finished, then more until the sample holds
``min_tokens`` served tokens or ``max_requests`` requests.  The reference runs once over each prompt
with its served tokens.  The number compared is the widest gap by which
a served token's logit lies below the reference's best logit at its
position (greedy decoding serves the argmax, so a sound program's gaps
are rounding, and a wrong token shows as a gap of the size of the
logits' spread).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import reference


def pick_sample(client, reqs_by_rid: Dict, seed: int, max_requests: int,
                min_tokens: int) -> List[int]:
    outs = client.engine.backend.outputs
    lo, hi = client.lo, client.hi
    done = sorted(
        rid for rid, r in reqs_by_rid.items()
        if len(outs.get(rid, ())) == r.max_new_tokens
        and any(lo <= t < hi for t in client.stamps.get(rid, ())))
    if not done:
        return []
    rng = np.random.default_rng([seed, 0xC4EC])
    size = {rid: reqs_by_rid[rid].prompt_len + reqs_by_rid[rid].max_new_tokens
            for rid in done}
    plen = {rid: reqs_by_rid[rid].prompt_len for rid in done}
    pick = [max(done, key=lambda rid: (size[rid], rid))]
    shortest = min(done, key=lambda rid: (plen[rid], rid))
    if shortest not in pick:
        pick.append(shortest)
    shapes = {}
    for c in client.calls:
        if c.kind == "prefill" and lo <= c.t0 < hi:
            for rid in c.rids:
                if rid in size:
                    shapes.setdefault((c.rows, c.chunked), set()).add(rid)
    for key in sorted(shapes):
        if not shapes[key] & set(pick):
            pick.append(int(rng.choice(sorted(shapes[key]))))
    rest = [rid for rid in rng.permutation(done) if rid not in pick]
    for rid in rest:
        if len(pick) >= max_requests or \
                sum(reqs_by_rid[r].max_new_tokens for r in pick) >= min_tokens:
            break
        pick.append(int(rid))
    return pick[:max(max_requests, 1)]


def reference_mesh(chips: int):
    """The reference's own mesh over the cell's chips, or None on one."""
    if chips < 2:
        return None
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:chips]), ("model",))


def verdict(gaps, limit) -> dict:
    """The number compared, the widest gap over ``gaps``, and whether it
    keeps to its limit."""
    value = float(np.concatenate(gaps).max())
    return {"value": value, "limit": limit,
            "correct": limit is not None and value <= limit}


def compare(m: dict, seed: int, prompts, served, limits: dict, *,
            control: bool = False, mesh=None) -> dict:
    """The verdict on the served tokens; with ``control``, also the
    verdict on the tokens the float8 control puts first, through the
    same comparison and limit."""
    limit = limits.get("logit_gap_max")
    if not prompts:
        return {"correct": False, "info": {"checked_tokens": 0},
                "numbers": {"logit_gap_max": {"value": None,
                                              "limit": limit}}}
    gaps = reference.logit_gaps(m, seed, prompts, served, control=control,
                                mesh=mesh)
    allg = np.concatenate([g["served"] for g in gaps])
    got = verdict([allg], limit)
    info = {"checked_tokens": int(allg.size),
            "tokens_not_argmax": int((allg > 0).sum()),
            "gap_max_per_request": [float(g["served"].max()) for g in gaps]}
    out = {"correct": bool(got["correct"]), "info": info,
           "numbers": {"logit_gap_max": {"value": got["value"],
                                         "limit": limit}}}
    if control:
        ctl = verdict([g["control"] for g in gaps], limit)
        info["control_gap_max"] = ctl["value"]
        info["control_not_argmax"] = int(sum(
            (g["control"] > 0).sum() for g in gaps))
        out["control"] = {"correct": bool(ctl["correct"]),
                          "logit_gap_max": {"value": ctl["value"],
                                            "limit": limit}}
    return out
