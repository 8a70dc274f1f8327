"""Percentile and spread arithmetic, in one place."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by the nearest-rank rule: the
    smallest value with at least q% of the sample at or below it. No
    interpolation, so a tail is a value that was really observed."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — the contract's spread (``statistics.quantiles(n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
