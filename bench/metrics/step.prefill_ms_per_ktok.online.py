"""Prefill device time per thousand prompt tokens, in cells whose
arrivals are on a schedule (it moves the TTFT tail there)."""
import prefill


def read(run):
    return prefill.read(run) if run.online else None
