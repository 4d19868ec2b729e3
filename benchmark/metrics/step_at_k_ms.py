"""Collective layer: what a predicted-threshold step takes when exactly
k = density x n values are delivered. The step follows its delivered count
(a constant and ~60 ms a million), and the count wanders over the
controller's band by the seed, so a window's step times say where in the
band its counts lay; the line through (delivered, step time) of the
window's predicted steps, read at k, does not (PERF.md, Findings, PR 37:
270.2-270.9 ms over twelve seeds whose medians read 275.2-280.5). Every
step of the window but its first (which starts on an empty queue) and
those on one of the cadences that the traffic file names
(``window.whole_periods_of``: exact recomputes, repartitions). Nothing to
read where the traffic names no cadence or the counts do not vary."""
from benchlib import window


def read(ctx):
    names = ctx.traffic.get("window", {}).get("whole_periods_of")
    if not names:
        return None
    every = [int(getattr(ctx.algo_cfg, n)) for n in names]
    win = ctx.window
    times = window.step_times(win.stamps)
    points = [(win.delivered[i], 1e3 * t) for i, t in enumerate(times, 1)
              if all((win.first_step + i) % e for e in every)]
    if not points:
        return None
    return window.line_at(*zip(*points), ctx.algo_cfg.density * ctx.n)
