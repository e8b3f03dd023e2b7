"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the cell's devices."""


def read(run):
    if run.trace is None:
        return None
    return 1.0 - run.trace.mean_busy_s() / run.trace.window_s
