"""The yardstick's arithmetic, checked by hand."""
import statistics

import numpy as np
import pytest

from perfbench import stats


@pytest.mark.parametrize("q", [5, 50, 90, 95, 99])
def test_percentile_is_numpy_linear(q):
    xs = list(np.random.default_rng(q).lognormal(3.0, 0.7, size=237))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 95) is None
    assert stats.tail_mean([], 0.1) is None


@pytest.mark.parametrize("n,q,beyond,ok", [
    (200, 95, 10, True),     # ten samples beyond the 95th of 200
    (199, 95, 9, False),     # one request short
    (1000, 99, 10, True),
    (999, 99, 9, False),
    (20, 50, 10, True),
    (113, 95, 5, False),     # a 15 s window of the serve cell
])
def test_ten_samples_beyond(n, q, beyond, ok):
    assert stats.samples_beyond(n, q) == beyond
    assert stats.supported(n, q) is ok


@pytest.mark.parametrize("n,want", [(5, None), (20, 50), (40, 75), (100, 90),
                                    (200, 95), (999, 95), (1000, 99)])
def test_highest_supported_percentile(n, want):
    assert stats.highest_supported(n) == want


def test_tail_mean_is_the_mean_of_the_slowest_share():
    xs = list(range(1, 101))
    assert stats.tail_mean(xs, 0.1) == pytest.approx(95.5)   # 91..100
    assert stats.tail_mean(xs, 0.05) == pytest.approx(98.0)  # 96..100
    assert stats.tail_mean([3.0], 0.1) == 3.0                 # at least one
    # smooth where a percentile steps: move one sample across the rank
    a = [10.0] * 90 + [20.0] * 10
    b = [10.0] * 91 + [20.0] * 9
    assert abs(stats.tail_mean(a, 0.1) - stats.tail_mean(b, 0.1)) == 1.0
    assert stats.percentile(a, 90.5) - stats.percentile(b, 90.5) > 4.0


def test_spread_is_the_quartile_distance_over_the_median():
    xs = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 100.25)
    assert stats.spread([5.0] * 6) == 0.0
