"""Medians and tail percentiles of the timings a run collects."""

from __future__ import annotations

import math
from typing import Optional, Sequence

# the percentiles a timing may be reported at, lowest first
LADDER = (50.0, 90.0, 95.0, 99.0, 99.9)


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule), on any non-empty sequence."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def samples_beyond(n: int, q: float) -> float:
    """How many of ``n`` samples lie beyond the ``q``-th percentile
    (100 - 99.9 is not 0.1 in binary, hence the rounding)."""
    return round(n * (100.0 - q) / 100.0, 9)


def highest_supported_percentile(n: int, beyond: int = 10
                                 ) -> Optional[float]:
    """The highest percentile of LADDER that still has ``beyond`` of the
    ``n`` samples above it (choosing-metrics §1); None when not even
    the median has."""
    best = None
    for q in LADDER:
        if samples_beyond(n, q) >= beyond:
            best = q
    return best
