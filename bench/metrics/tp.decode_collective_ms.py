"""Device time of the collectives in a decode step, in milliseconds per
step, averaged over the devices: per wrapped ``decode_iter`` span in the
window, the programs that started on the device inside it, save the
prefill and chunk programs (as ``step.decode_ms`` takes them), and
inside those programs the collective operations (``reduce.COLLECTIVE``).
An asynchronous collective, a ``-start`` and its ``-done``, counts once
and in full, from the start's beginning to the done's end; overlapping
intervals are merged, so no time counts twice.  None on one chip."""
import numpy as np

import reduce


def collective_intervals(names, starts, ends) -> np.ndarray:
    """Intervals (n, 2) of the collective operations among ``names``,
    each asynchronous pair joined into one."""
    out, used = [], set()
    order = np.argsort(starts, kind="stable")
    for pos, i in enumerate(order):
        name = names[i]
        if i in used or not reduce.COLLECTIVE.search(name):
            continue
        end = ends[i]
        if "-start" in name:
            done = name.replace("-start", "-done")
            j = next((j for j in order[pos + 1:]
                      if names[j] == done and j not in used), None)
            if j is not None:
                used.add(j)
                end = ends[j]
        out.append((starts[i], end))
    return reduce.union(np.asarray(out, float).reshape(-1, 2))


def device_ms(tr, dev: int):
    """Collective milliseconds per decode step on one device, or None
    where the window holds no decode step."""
    mods, ops = tr.modules[dev], tr.ops[dev]
    coll = np.asarray([k for k, n in enumerate(ops.names)
                       if reduce.COLLECTIVE.search(n)], int)
    coll = coll[np.argsort(ops.start[coll], kind="stable")]
    cstart = ops.start[coll]
    morder = np.argsort(mods.start, kind="stable")
    mstart = mods.start[morder]
    total, steps = 0.0, 0
    for name, s, e in tr.spans:
        if name != "bench.decode_iter" or not tr.lo <= s < tr.hi:
            continue
        i, j = np.searchsorted(mstart, [s, e])
        progs = [k for k in morder[i:j] if reduce.module_name(
            mods.names[k]) not in reduce.PREFILL_PROGRAMS]
        if not progs:
            continue
        steps += 1
        for k in progs:
            a, b = np.searchsorted(cstart, [mods.start[k], mods.end[k]])
            inside = coll[a:b]
            iv = collective_intervals([ops.names[x] for x in inside],
                                      ops.start[inside], ops.end[inside])
            total += float((iv[:, 1] - iv[:, 0]).sum())
    return total * 1e-6 / steps if steps else None


def read(run):
    tr = run.trace
    if tr is None or run.chips < 2:
        return None
    got = [device_ms(tr, d) for d in range(len(tr.ops))]
    got = [g for g in got if g is not None]
    return sum(got) / len(got) if got else None
