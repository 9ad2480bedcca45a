"""Percentile, rate and multiset arithmetic of the end-to-end metrics."""

import math

import numpy as np
import pytest

from benchmark import stats


def test_percentile_is_nearest_rank_over_every_value():
    v = np.arange(1, 101, dtype=np.float64)
    assert stats.percentile(v, 50) == 50.0
    assert stats.percentile(v, 99) == 99.0
    assert stats.percentile(v[::-1], 99) == 99.0
    assert stats.percentile([7.0], 99) == 7.0
    assert stats.percentile([], 50) == math.inf


def test_a_line_that_never_arrives_lies_beyond_every_percentile():
    v = np.array([1.0] * 98 + [math.inf] * 2)
    assert stats.percentile(v, 50) == 1.0
    assert stats.percentile(v, 98) == 1.0
    assert stats.percentile(v, 99) == math.inf


def test_a_stall_in_the_window_shows_in_the_tail_not_the_median():
    # 10,000 lines, 10 ms each; a 300 ms stall delays 200 of them
    lat = np.full(10_000, 10.0)
    lat[5000:5200] = np.linspace(310.0, 10.0, 200)
    assert stats.percentile(lat, 50) == 10.0
    assert stats.percentile(lat, 99) > 150.0


def test_rate_is_all_the_work_over_all_the_window():
    seen = np.array([5, 10, 19, 20, 25, 29, 30, 31], np.int64) * 1_000_000
    # [10 s, 30 s): 10, 19, 20, 25, 29 are inside
    assert stats.rate(seen, 10_000_000, 30_000_000) == 5 / 20.0
    # a stall: nothing arrives for half of the window, the rate halves
    steady = np.arange(0, 20_000_000, 1000, dtype=np.int64)
    stalled = steady[(steady < 5_000_000) | (steady >= 15_000_000)]
    assert stats.rate(stalled, 0, 20_000_000) == pytest.approx(
        stats.rate(steady, 0, 20_000_000) / 2)


def test_match_pairs_multisets():
    want = np.array([1, 2, 2, 3, 5, 5], np.int64)
    have = np.array([2, 2, 2, 3, 4, 5], np.int64)
    at, extra = stats.match(want, have)
    assert (at >= 0).tolist() == [False, True, True, True, True, False]
    assert have[at[at >= 0]].tolist() == [2, 2, 3, 5]
    assert extra == 2          # a third 2, and the 4
    at, extra = stats.match(want, np.zeros(0, np.int64))
    assert (at < 0).all() and extra == 0


def test_spread_is_the_interquartile_distance_over_the_median():
    import statistics

    v = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q = statistics.quantiles(v, n=4)
    assert stats.spread(v) == (q[2] - q[0]) / statistics.median(v)
