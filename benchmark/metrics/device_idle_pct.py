"""Device layer: the share of the traced window in which no operation
runs on the busiest chip: 1 - the union of the device operations' intervals
over the window, both terms from the trace, nothing cut off.

Listed for the cells whose input is small (tokens). Under the profiler a
host-to-device copy is slowed (a 38 MB batch of images: 12 ms untraced,
200-300 ms traced; PERF.md section 5), so in an image cell the traced
window is mostly the device waiting for its input, and this number would
be the profiler's, not the job's; there the result line's ``busy_s`` over
``window_s`` and the ``breakdown``'s ``idle_gaps`` say so."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share()
