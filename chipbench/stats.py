"""Percentile, lateness and spread arithmetic (the yardstick's own)."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule); None for no values."""
    if not values:
        return None
    xs = sorted(float(v) for v in values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * (q / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the driver's
    measure of how far runs of one cell disagree."""
    med = median(values)
    if med is None or med == 0:
        return None
    return (percentile(values, 75.0) - percentile(values, 25.0)) / abs(med)


def lateness(due: Sequence[float], sent: Sequence[float]) -> dict:
    """How late the generator ran: ``sent - due`` per request, never
    negative (a request is not sent before it is due)."""
    late = [max(0.0, s - d) for d, s in zip(due, sent)]
    return {"n": len(late),
            "p50_ms": 1e3 * (median(late) or 0.0),
            "p99_ms": 1e3 * (percentile(late, 99.0) or 0.0),
            "max_ms": 1e3 * (max(late) if late else 0.0)}
