"""The arithmetic of the end-to-end metrics and of a bound: percentile,
rate, and the spread the contract defines.  No program import."""

from __future__ import annotations

import math
import statistics


def percentile(values, pct: float) -> float:
    """The pct-th percentile by linear interpolation between closest
    ranks (numpy's default), over ALL values given."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * pct / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(completed: int, window_s: float) -> float:
    """Completed per second over the WHOLE window."""
    if window_s <= 0:
        raise ValueError("window of no length")
    return completed / window_s


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by statistics.quantiles(values, n=4): the contract's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
