"""Arithmetic on the completion stamps of a measured window.

A stamp is the host clock read right after a step's result was ready. A
step's time is the gap between its stamp and the one before it: every step
of the window gives one reading, and the percentiles are taken over all of
them, so that one slow step shows as itself. The host clock is off by some
half a millisecond; the steps timed here take 65 ms or more, so a single
reading is off by under 1 %, and a median or a 95th percentile of some
hundreds of them by far less.
"""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple


def step_times(stamps: Sequence[float]) -> List[float]:
    """Seconds of every step but the first, which starts on an empty
    queue (its gap would run from the window's start, not from a
    completion)."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def rate(t0: float, stamps: Sequence[float], per_step: float) -> float:
    """Units completed a second over the whole window: all the steps, all
    the time from ``t0`` to the last stamp."""
    if not stamps or stamps[-1] <= t0:
        raise ValueError("empty window")
    return len(stamps) * per_step / (stamps[-1] - t0)


def summary(t0: float, stamps: Sequence[float]) -> Tuple[int, float, int]:
    """(steps, window seconds, step-time readings)."""
    return (len(stamps), stamps[-1] - t0 if stamps else 0.0,
            len(step_times(stamps)))
