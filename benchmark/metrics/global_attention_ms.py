"""Model layer: device time a step of the global layers' attention, the
operations under ``anat/fwd_bwd/attention`` in a model that has windowed
layers beside them (norm, the four projections, the causal scores over the
whole sequence, softmax, weighted sum; forward, recomputed and backward)."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("attention",))
