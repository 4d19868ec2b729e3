"""Model layer: the operations the SwiGLUs' products need (three matrices
of hidden x intermediate a layer application, one forward and the backward:
``benchlib/kernels_loop.py``) over the chip's published matrix peak times
the measured time of ``mlp_ms``'s operations: the share of the peak that is
useful work. The norms, SiLU and the SwiGLU's own recomputation are in the
time and not in the operations."""
from benchlib import kernels_lm, kernels_loop


def read(ctx):
    seconds = kernels_lm.sub_seconds(ctx, ("mlp",))
    if seconds is None:
        return None
    return kernels_lm.mxu_share(
        ctx, kernels_loop.mlp_flops_a_step(ctx.config, ctx.global_batch),
        seconds)
