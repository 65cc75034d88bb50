"""Percentile, tail and spread arithmetic."""

import numpy as np
import pytest

from perf import stats


@pytest.mark.parametrize("q", [0, 25, 50, 95, 99, 100])
def test_percentile_matches_numpy(q):
    xs = list(np.random.RandomState(0).exponential(1.0, 137))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_and_of_one():
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0


def test_tail_counts_its_samples():
    t = stats.tail([1.0, 2.0, 3.0, 4.0, 5.0])
    assert t == {"n": 5, "p50": 3.0, "p95": pytest.approx(4.8)}


def test_spread_is_the_drivers_quartile_distance():
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
