"""Metric arithmetic shared by the readers: percentiles of stamps, interval
unions and shares of an interval."""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (linear between order statistics) of every
    value; None when there is none."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """Intervals cut to [lo, hi], empty ones dropped."""
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering exactly the input's union."""
    merged: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def covered(intervals: Iterable[Interval]) -> float:
    """Length of the union of the intervals."""
    return sum(b - a for a, b in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval of ``busy`` covers."""
    out = []
    t = lo
    for a, b in union(clip(busy, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def share(intervals: Iterable[Interval], lo: float, hi: float
          ) -> Optional[float]:
    """Per cent of [lo, hi] that the union of the intervals covers."""
    if hi <= lo:
        return None
    return 100.0 * covered(clip(intervals, lo, hi)) / (hi - lo)
