"""Order statistics used by the end-to-end metrics."""
from __future__ import annotations

import math
import statistics


def nearest_rank(values, q: float) -> float:
    """The q-quantile by the nearest-rank rule: the smallest sample with at
    least a share q of the samples at or below it.  Never interpolates, so
    the value reported is one that a request really saw."""
    if not values:
        raise ValueError("no samples")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"q={q} outside (0, 1]")
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def quartile_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (Python's `statistics.quantiles`, the rule the bounds use)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
