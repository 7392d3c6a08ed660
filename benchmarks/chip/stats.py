"""Percentiles and windowing of host-clock timestamps."""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100), linear between closest ranks
    (numpy's default); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(t: float, start: float, end: float) -> bool:
    """Windows are half open: [start, end)."""
    return start <= t < end


def count_in_window(stamps: Iterable[float], start: float, end: float) -> int:
    return sum(1 for t in stamps if in_window(t, start, end))


def gaps_ending_in(times: Sequence[float], start: float, end: float):
    """Gaps between consecutive timestamps of one request whose later
    stamp falls in [start, end)."""
    return [b - a for a, b in zip(times, times[1:]) if in_window(b, start, end)]

