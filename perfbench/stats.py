"""The arithmetic of the yardstick: percentiles, tails, spreads.

Plain Python on lists of floats, so that a test can check every rule by
hand. Nothing here reads a clock or a device.
"""
import json
import math
import os
import statistics

BEYOND = 10  # samples a percentile needs beyond it (choosing-metrics, 1)


def percentile(values, q):
    """The q-th percentile (0 < q < 100) by linear interpolation between
    order statistics, as ``numpy.percentile`` defaults to. None when the
    list is empty."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the q-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def supported(n, q, beyond=BEYOND):
    """Whether ``n`` samples carry the q-th percentile: at least ``beyond``
    of them lie beyond it."""
    return samples_beyond(n, q) >= beyond


def highest_supported(n, candidates=(99, 95, 90, 75, 50), beyond=BEYOND):
    """The highest of ``candidates`` that ``n`` samples carry, or None."""
    for q in sorted(candidates, reverse=True):
        if supported(n, q, beyond):
            return q
    return None


def tail_mean(values, share=0.1):
    """Mean of the slowest ``share`` of the samples (at least one): smooth
    where a percentile of a mixture of buckets steps."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(1, int(math.ceil(len(xs) * share)))
    return sum(xs[-k:]) / k


def spread(values):
    """Distance between the first and third quartile over the median, with
    the quartiles of ``statistics.quantiles(values, n=4)``: the contract's
    measure of run-to-run noise."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def repo_root():
    """The checkout that holds this package."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
