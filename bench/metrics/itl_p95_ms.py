"""95th percentile of every gap between two consecutive tokens of one
request, both stamped inside the window, in milliseconds."""
import stats


def read(run):
    g = stats.gaps_in_window(run.stamps, run.lo, run.hi)
    return stats.nearest_rank(g, 95) * 1e3 if g else None
