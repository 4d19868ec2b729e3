"""Collective layer: device time a step under ``anat/.../stage/finalize``,
kernels excluded: staging's cap-scale finalize: overflow census, prefix
sums, branch dispatch, materialising gathers."""
from benchlib import progspans


def read(ctx):
    return progspans.sub_ms(ctx, "stage_finalize")
