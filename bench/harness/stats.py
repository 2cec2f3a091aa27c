"""Order statistics as the benchmark reports them."""

from __future__ import annotations

import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0 < q <= 100) by nearest rank: a value that was
    observed, never one interpolated between two.  ``inf`` entries (requests
    that failed) sort last, so they count as late."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(1, math.ceil(q / 100.0 * len(ordered))) - 1]


def median(values: Sequence[float]) -> float:
    return nearest_rank(values, 50.0)
