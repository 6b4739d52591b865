"""Percentiles in which a failed request misses: it is ranked as +inf, not dropped."""

from __future__ import annotations

import math
from typing import Iterable, Optional

INF = float("inf")


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """q in [0, 100], linear interpolation between closest ranks (numpy's
    default). A +inf neighbour makes the result +inf; an empty sample None."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if v[hi] == INF and (hi == lo or pos > lo):
        return INF
    if v[lo] == INF:
        return INF
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def with_failures(good: Iterable[float], n_failed: int):
    """The sample over which a latency percentile is taken."""
    return list(good) + [INF] * n_failed


def mean(values: Iterable[float]) -> Optional[float]:
    v = list(values)
    return sum(v) / len(v) if v else None


def finite(x: Optional[float]) -> Optional[float]:
    """A metric value fit to print, or None where there is nothing to report."""
    return x if x is not None and math.isfinite(x) else None
