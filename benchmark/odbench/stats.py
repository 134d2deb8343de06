"""Percentiles by the benchmark's rule, and the spread the bounds rest on."""

from __future__ import annotations

import math
import statistics

# a tail is reported only with this many samples beyond it
MIN_BEYOND = 10


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q < 100) of ``values``. A failed
    request enters as ``math.inf`` and so counts as missing every limit."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the nearest-rank ``q``-th."""
    return n - max(1, math.ceil(q / 100.0 * n))


def supported(n: int, q: float) -> bool:
    """Whether ``n`` samples carry the ``q``-th percentile: at least
    ``MIN_BEYOND`` samples beyond it."""
    return n > 0 and samples_beyond(n, q) >= MIN_BEYOND


def highest_supported(n: int, candidates=(99.0, 95.0, 90.0, 75.0, 50.0)):
    """The highest of ``candidates`` that ``n`` samples support, or None."""
    for q in candidates:
        if supported(n, q):
            return q
    return None


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)``: the contract's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
