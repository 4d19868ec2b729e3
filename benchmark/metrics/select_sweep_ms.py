"""Collective layer: device time a step under ``anat/.../select/sweep``,
kernels excluded: the XLA wrapper of the n-scale selection sweep (padding,
reshapes, the sums of its per-block counts)."""
from benchlib import progspans


def read(ctx):
    return progspans.sub_ms(ctx, "select_sweep")
