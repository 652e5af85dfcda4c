"""Order statistics the metrics share."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence

# a turn that failed, or never resolved, misses every latency limit
MISSED_MS = 1e9


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (the smallest value with at least
    ``q`` percent of the sample at or below it); None for no values."""
    if not values:
        return None
    xs = sorted(values)
    return float(xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)])


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
