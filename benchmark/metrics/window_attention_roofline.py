"""Model layer: the least time the chip could take for a step's banded
scores over the time it took (the operations under
``anat/fwd_bwd/window_scores``). The least time is the larger of the band's
operations over the matrix peak and its bytes over the memory bandwidth,
counted from shapes alone, one forward and the backward
(``benchlib/kernels_swa.py``): what a block of queries computes outside the
band and every recomputation are in the time and not in the count."""
from benchlib import kernels_lm, kernels_swa


def read(ctx):
    seconds = kernels_lm.sub_seconds(ctx, ("window_scores",))
    if not seconds:
        return None
    least, _ = kernels_swa.window_scores_roofline_seconds(
        ctx.config, ctx.global_batch, ctx.device_kind)
    return 100.0 * least / (seconds / ctx.trace.steps)
