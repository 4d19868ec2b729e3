"""Model layer: device time a step of the full-attention layers' scores
alone, the operations under ``anat/fwd_bwd/full_scores`` (scores, softmax
and weighted sum of a causal layer whose model also has windowed ones; they
lie inside ``attention``): forward, recomputed and backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("full_scores",))
