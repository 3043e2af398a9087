"""Percentiles and spreads, the one arithmetic every metric shares."""
from __future__ import annotations

import math
import statistics


def percentile(samples, p: float) -> float:
    """Nearest-rank percentile: the ceil(p/100 * n)-th smallest sample.
    A missing sample (a request that never finished) is ``inf`` and sorts
    last, so it counts against the tail."""
    xs = sorted(samples)
    if not xs:
        return math.nan
    rank = max(1, math.ceil(p / 100 * len(xs)))
    return xs[min(rank, len(xs)) - 1]


def spread(values) -> float:
    """Interquartile distance over the median, as a share."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
