"""Model layer: device time a step of the windowed layers' attention: the
operations under ``anat/fwd_bwd/window_attention`` (norm, the four
projections, rotary) and under ``anat/fwd_bwd/window_scores`` (the blocked
scores, softmax and weighted sum, which lie inside it); forward, recomputed
and backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("window_attention", "window_scores"))
