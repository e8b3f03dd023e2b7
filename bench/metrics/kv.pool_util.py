"""Pages of the paged KV pool in use over all its pages, from the
allocator at each decode step of the window, averaged."""


def read(run):
    steps = [c for c in run.calls("decode") if c.pages_total]
    if not steps:
        return None
    return sum(c.pages_used / c.pages_total for c in steps) / len(steps)
