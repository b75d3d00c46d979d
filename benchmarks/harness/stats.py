"""Metric arithmetic: percentiles, rates, spreads. Plain Python, no JAX."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest sample with at least p% of the
    samples at or below it. None for no samples. p in (0, 100]."""
    xs = sorted(values)
    if not xs:
        return None
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    rank = math.ceil(p / 100.0 * len(xs))
    return xs[max(rank, 1) - 1]


def rate_per_s(count: float, seconds: float) -> float:
    """Work over the whole window: `count` units in `seconds`."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return count / seconds


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the bound's rule reads it (statistics.quantiles, n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def token_gaps(arrivals: Sequence[float]) -> list:
    """Gaps between one request's consecutive token arrival times."""
    return [b - a for a, b in zip(arrivals, arrivals[1:])]
