#!/usr/bin/env python3
"""Medians and spreads of the runs a chip call left in chiprun_out/.

    python3 benchmark/chip/spread.py <tag>...

For each tag the result lines of chiprun_out/<tag>-*.out: per metric the
values in seed order, the median, and the spread as the bounds are set
from it (inter-quartile distance over the median, statistics.quantiles).
"""

import glob
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT

from benchmark import stats  # noqa: E402


def main():
    for tag in sys.argv[1:]:
        by = {}
        for path in sorted(glob.glob(os.path.join(
                ROOT, "chiprun_out", tag + "-*.out"))):
            with open(path) as f:
                last = f.read().strip().splitlines()[-1]
            res = json.loads(last)
            for k, m in res["metrics"].items():
                by.setdefault(k, []).append(m["value"])
            by.setdefault("correct", []).append(res["correct"])
        for k, v in by.items():
            if k == "correct" or len(v) < 2:
                print(tag, k, v)
                continue
            print(f"{tag} {k}: median {statistics.median(v):.6g} spread "
                  f"{100 * stats.spread(v):.2f}% values "
                  + " ".join(f"{x:.6g}" for x in v))
    return 0


if __name__ == "__main__":
    sys.exit(main())
