"""Interval arithmetic on (start, end) pairs, any one unit.

``union`` and ``intersection_len`` follow ``_union_ms``/``_intersection_ms``
of ``oktopk_tpu/obs/anatomy.py`` at commit 669e046 (sort, merge, sweep);
``subtract`` and ``gaps`` are the benchmark's own.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted cover of the given intervals."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> float:
    """Total length of the union."""
    return sum(e - s for s, e in union(intervals))


def intersection_len(a: Iterable[Interval], b: Iterable[Interval]) -> float:
    """Length covered by both unions."""
    ua, ub = union(a), union(b)
    i = j = 0
    total = 0.0
    while i < len(ua) and j < len(ub):
        lo, hi = max(ua[i][0], ub[j][0]), min(ua[i][1], ub[j][1])
        if hi > lo:
            total += hi - lo
        if ua[i][1] < ub[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out
