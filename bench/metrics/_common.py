"""Helpers shared by the metric readers (not a metric itself)."""

from __future__ import annotations

import statistics
from typing import List, Optional


def median(values: List[float]) -> Optional[float]:
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear interpolation between the closest ranks (numpy's default)."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
