"""The arithmetic of the end-to-end metrics, apart from any I/O.

A rate is all the work over all the time of the window; a tail is the
tail of every line that was due in it, and a line that never arrived
lies beyond every percentile.
"""

from __future__ import annotations

import math

import numpy as np


def rate(seen_us, t0_us, t1_us):
    """Records whose bytes reached the sink inside the window, a
    second."""
    inside = np.count_nonzero((seen_us >= t0_us) & (seen_us < t1_us))
    return inside / ((t1_us - t0_us) / 1e6)


def percentile(values, q):
    """The ``q``-th percentile (nearest rank) of ``values``, among
    which ``inf`` stands for "never"; inf if the rank falls on one."""
    n = len(values)
    if n == 0:
        return math.inf
    k = min(n - 1, max(0, math.ceil(q / 100.0 * n) - 1))
    return float(np.partition(np.asarray(values, np.float64), k)[k])


def match(expected, got):
    """Pair two multisets of integers.  Both arrive sorted.  Returns,
    for each entry of ``expected``, its index in ``got`` (-1: missing),
    and how many entries of ``got`` nothing expected."""
    first = np.searchsorted(expected, expected, "left")
    rank = np.arange(len(expected)) - first
    at = np.searchsorted(got, expected, "left") + rank
    hit = at < np.searchsorted(got, expected, "right")
    at = np.where(hit, at, -1)
    return at, len(got) - int(hit.sum())


def spread(values):
    """Inter-quartile distance as a share of the median: the measure
    the bounds are set from."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
