"""Percentiles and spreads, kept with the benchmark so no PR can change them."""

from __future__ import annotations

import math
import statistics


def nearest_rank(values, q: float) -> float:
    """The q-th percentile (0 < q <= 100) by nearest rank: the smallest value
    with at least q% of the sample at or below it."""
    s = sorted(values)
    if not s:
        raise ValueError("no samples")
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def percentile_failures_worst(ok, failed, q: float) -> float:
    """Nearest-rank percentile of the succeeded latencies ``ok`` with every
    failed or refused request counted as the worst (at least the slowest
    success, or its own time to fail if that was longer)."""
    worst = max(ok)
    return nearest_rank(list(ok) + [max(worst, f) for f in failed], q)


def median(values) -> float:
    return statistics.median(values)


def iqr_share(values) -> float:
    """Distance between the first and third quartile over the median, as the
    driver takes it (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
