"""Model layer: device time a step of the sliding-window layers' scores
alone, the operations under ``anat/fwd_bwd/window_scores`` (scores, softmax
and weighted sum inside the window), in a model whose layers differ in head
count by kind: forward, recomputed and backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("window_scores",))
