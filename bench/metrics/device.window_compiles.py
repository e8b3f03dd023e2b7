"""Compiles and persistent-cache loads inside the window, counted from
JAX's monitoring events.  Each is a stall the warm-up did not take."""


def read(run):
    return sum(run.compiles)
