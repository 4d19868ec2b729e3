"""95th percentile of the same readings as ``step_ms_p50``, one a step: a
slow step (a threshold recompute, a stall of the host) shows as itself."""
from benchlib import window


def read(ctx):
    times = window.step_times(ctx.window.stamps)
    return 1e3 * window.percentile(times, 95) if times else None
