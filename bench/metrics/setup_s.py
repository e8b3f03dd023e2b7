"""Seconds from process start until the window opens: weights, pool,
warm-up (compiles or cache loads) and the pre-roll of the cell's
traffic."""


def read(run):
    return run.setup_s
