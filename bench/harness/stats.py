"""Percentiles and rates over every sample, never over chunks."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the samples at or below it."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    ordered = sorted(values)
    rank = max(math.ceil(q / 100.0 * len(ordered)), 1)
    return float(ordered[rank - 1])


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds
