"""Device time of a decode step, in milliseconds, averaged over the
window's decode calls: per wrapped ``decode_iter`` span, every program
that started on the device inside it (``reduce.step_times``), save the
prefill and chunk programs, which a prefill call dispatched just
before."""
import reduce


def read(run):
    if run.trace is None:
        return None
    t = run.trace.step_times("bench.decode_iter",
                             exclude=reduce.PREFILL_PROGRAMS)
    return sum(t) / len(t) * 1e3 if t else None
