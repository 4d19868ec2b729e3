"""Device layer: median time the first chip runs nothing between two
consecutive executions of the step program (``XLA Modules``), from the
traced window. Listed for the token cells only: under the profiler an
image cell's input copy makes this gap (``device_idle_pct`` says why)."""
import statistics

from benchlib import progspans


def read(ctx):
    v = progspans.view(ctx)
    if v is None or not v.gap_s:
        return None
    return 1e3 * statistics.median(v.gap_s)
