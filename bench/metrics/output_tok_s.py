"""Output tokens delivered in the window, per second of the window:
every token the client stamped inside it, over the window's length."""
import stats


def read(run):
    return stats.tokens_in_window(run.stamps, run.lo, run.hi) / (run.hi - run.lo)
