"""Percentiles over raw samples, for the metric readers."""

from __future__ import annotations

import math
from typing import Optional, Sequence

__all__ = ["percentile"]


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-quantile (``q`` in (0, 1]): the smallest sample
    with at least a fraction ``q`` of the samples at or below it.  None
    for no samples."""
    if not values:
        return None
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]
