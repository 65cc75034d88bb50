"""Percentile and spread arithmetic of the benchmark (its own copy, so
that no later change of the program moves the yardstick)."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default rule). None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tail(values: Sequence[float]) -> Dict[str, Optional[float]]:
    """Median, 95th percentile and the sample count behind them."""
    return {"n": len(values), "p50": percentile(values, 50.0),
            "p95": percentile(values, 95.0)}


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver reads it (statistics.quantiles, n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
