"""Collective layer: device time a step under ``anat/.../select/global``,
kernels excluded: phase (b)'s global winner selection round the kernels."""
from benchlib import progspans


def read(ctx):
    return progspans.sub_ms(ctx, "select_global")
