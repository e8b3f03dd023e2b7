"""Prefill device time per thousand real prompt tokens, shared by the
online and offline readers: the device time of every program that
started inside a wrapped ``prefill_chunk`` call (the prefill or chunk
step, the batch cache builder, the eager scatters into the pool), and
of the ``fn`` programs (the prefill and chunk steps by name) wherever
they started, over the real prompt tokens the window's prefill calls
processed."""


def read(run):
    if run.trace is None:
        return None
    tr = run.trace
    t, _ = tr.module_time(
        lambda name, s: name == "fn" or tr.in_span("bench.prefill_chunk", s))
    tokens = sum(c.real_tokens for c in run.calls("prefill"))
    return t * 1e3 / (tokens / 1e3) if tokens else None
