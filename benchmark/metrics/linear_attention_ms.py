"""Model layer: device time a step of the linear-attention layers' mixers:
the operations under ``anat/fwd_bwd/linear_attention`` (norm, the fused
projections, the causal convolution, gates, the gated norm, ``out_proj``)
and under ``anat/fwd_bwd/delta_rule`` (the chunked recurrence, which lies
inside it); forward, recomputed and backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("linear_attention", "delta_rule"))
