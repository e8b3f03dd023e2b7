"""Prefill device time per thousand prompt tokens, in cells whose queue
is all present at the start (it moves throughput there)."""
import prefill


def read(run):
    return None if run.online else prefill.read(run)
