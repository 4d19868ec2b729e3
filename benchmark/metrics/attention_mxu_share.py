"""Model layer: the operations the attention block needs (projections, and
scores and weighted sum over the causal half; one forward and the backward:
``benchlib/kernels_lm.py``) over the chip's published matrix peak times the
measured time of ``attention_ms``'s operations: the share of the peak that
is useful work. The softmax's elementwise work and every recomputation are
in the time and not in the operations."""
from benchlib import kernels_lm


def read(ctx):
    seconds = kernels_lm.sub_seconds(ctx, ("attention",))
    if seconds is None:
        return None
    return kernels_lm.mxu_share(
        ctx, kernels_lm.attention_flops_a_step(ctx.config, ctx.global_batch),
        seconds)
