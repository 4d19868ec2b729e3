"""Median step time, completion to completion, over every step of the
window but the first (benchlib/window.py)."""
from benchlib import window


def read(ctx):
    times = window.step_times(ctx.window.stamps)
    return 1e3 * window.percentile(times, 50) if times else None
