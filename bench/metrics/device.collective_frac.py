"""Device time in collective operations (all-reduce, all-gather, ...)
over the traced window, averaged over the devices; only where the cell
spans several chips."""
import reduce


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    tr = run.trace
    return sum(tr.op_time(reduce.COLLECTIVE, d)
               for d in range(len(tr.ops))) / len(tr.ops) / tr.window_s
