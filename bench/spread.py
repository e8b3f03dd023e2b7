#!/usr/bin/env python3
"""Spread of each metric over runs, as the bounds are set from it.

    python3 bench/spread.py A0.out A1.out ... -- B0.out B1.out ...

Each file holds one run's standard output; its last line is the result.
For each metric of each set: the median and the spread, the distance
between the first and third quartile (``statistics.quantiles``, n=4) as
a share of the median; then the wider of the two sets' spreads.
"""
from __future__ import annotations

import json
import statistics
import sys

import stats


def values(paths):
    out = {}
    for p in paths:
        with open(p) as f:
            line = json.loads(f.read().strip().splitlines()[-1])
        for k, v in line["metrics"].items():
            out.setdefault(k, []).append(v["value"])
    return out


def main(argv) -> int:
    sets = [s.split() for s in " ".join(argv).split(" -- ")]
    per = [values(s) for s in sets]
    for name in sorted(per[0]):
        cols = [v[name] for v in per if name in v]
        spreads = [stats.quartile_spread(c) for c in cols if len(c) > 1]
        print(f"{name}: medians {[statistics.median(c) for c in cols]} "
              f"spreads {spreads} widest {max(spreads, default=None)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
