"""Arithmetic of the end-to-end metrics.

Every rate is the work completed inside the whole window over the
window's length, and every tail is taken over all requests sent in the
window; none of them is a median of chunks.
"""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile of all ``values`` (0 < q <= 100):
    the smallest value with at least ``q``% of the values at or below
    it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(math.ceil(q / 100.0 * len(vals)), 1)
    return float(vals[rank - 1])


def rate(done_times, window_start: float, window_end: float) -> float:
    """Completions stamped inside ``[window_start, window_end]`` per
    second of the window."""
    n = sum(1 for t in done_times if window_start <= t <= window_end)
    return n / (window_end - window_start)


def spread(values) -> float:
    """Distance between the first and third quartile over the median
    (``statistics.quantiles``, the rule the benchmark's bounds use)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
