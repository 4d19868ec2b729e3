"""Entry layer: median host time of ``next(data)`` plus the enqueue of
``Trainer.train_step`` (no wait for the device), from the harness's own
spans of the traced window."""
import statistics


def read(ctx):
    spans = ctx.window.spans
    data = [e - s for n, s, e in spans if n == "data"]
    disp = [e - s for n, s, e in spans if n == "dispatch"]
    if not data or len(data) != len(disp):
        return None
    return 1e3 * statistics.median(a + b for a, b in zip(data, disp))
