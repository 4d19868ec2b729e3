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

import itertools
import math
import statistics
from typing import List, Optional, Sequence, Tuple


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


def last_period(elapsed: float, stamped: int, dispatched: int, period: int,
                seconds: float) -> bool:
    """Asked when ``dispatched`` steps, a whole number of periods, have
    been sent and ``stamped`` of them have completed in ``elapsed``
    seconds: would one more period, at the mean pace so far, end past
    ``seconds``? Then this period is the window's last. The queue is one
    step deep, so the question is put one completion before the period's
    end, while its last step can still be the last one sent."""
    if stamped < 1 or dispatched % period:
        raise ValueError("asked off a period's end")
    periods = dispatched // period
    so_far = elapsed * dispatched / stamped
    return so_far * (periods + 1) / periods > seconds


def line_at(xs: Sequence[float], ys: Sequence[float],
            x0: float) -> Optional[float]:
    """``a + b x0`` of the Theil-Sen line through the points: b the median
    slope over all pairs, a the median of ``y - b x``. A stamp that the
    host takes late makes one gap long and the next short by as much; a
    median line does not follow the pair where least squares would. None
    under four points (a traced window of a step over a second) or where
    all ``xs`` are one value."""
    slopes = [(y2 - y1) / (x2 - x1) for (x1, y1), (x2, y2)
              in itertools.combinations(zip(xs, ys), 2) if x2 != x1]
    if len(xs) < 4 or not slopes:
        return None
    b = statistics.median(slopes)
    return statistics.median(y - b * x for x, y in zip(xs, ys)) + b * x0


def summary(t0: float, stamps: Sequence[float]) -> Tuple[int, float, int]:
    """(steps, window seconds, step-time readings)."""
    return (len(stamps), stamps[-1] - t0 if stamps else 0.0,
            len(step_times(stamps)))
