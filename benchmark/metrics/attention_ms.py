"""Model layer: device time a step of the operations under
``anat/fwd_bwd/attention`` (norm, the four MLA projections, rotary, scores,
softmax, weighted sum; forward, recomputed and backward), kernels
included."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("attention",))
