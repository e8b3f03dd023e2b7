"""The decode step's share of its roofline, in percent: the least time
the chip could take for the work decode needs (the weights, each chip's
share, and every live request's real context of keys and values; not
the padded gather of the whole block table), over the decode step's
device time (``step.decode_ms``).  The bound is the larger of
operations at peak and bytes at peak bandwidth (``counts.bound_s``);
at these batch sizes it is the bytes."""
import counts
import reduce


def read(run):
    if run.trace is None:
        return None
    t = run.trace.step_times("bench.decode_iter",
                             exclude=reduce.PREFILL_PROGRAMS)
    steps = run.calls("decode")
    if not t or not steps:
        return None
    peaks = run.peaks()
    bound = sum(counts.bound_s(*counts.decode_step(run.cfg, c.contexts,
                                                   run.chips), peaks)[0]
                for c in steps) / len(steps)
    return 100.0 * bound * len(t) / sum(t)
