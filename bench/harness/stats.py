"""Metric arithmetic: percentiles over all requests, unions and
intersections of time intervals. Plain functions over plain numbers."""

from __future__ import annotations

import math
from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest observed value with at least
    ``p`` percent of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering exactly what the input covers;
    nested and overlapping intervals count once."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two unions (each sorted and disjoint)."""
    out: List[Interval] = []
    i = j = 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return intersect(union(intervals), [(lo, hi)])


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (a union) leaves uncovered."""
    out: List[Interval] = []
    t = lo
    for a, b in busy:
        if b <= lo or a >= hi:
            continue
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out

