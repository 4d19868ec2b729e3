"""Model layer: the operations the exits' head products need (one product
of tokens x hidden x vocabulary an exit, one forward and the backward:
``benchlib/kernels_loop.py``) over the chip's published matrix peak times
the measured time of the operations under ``anat/fwd_bwd/head``: the share
of the peak that is useful work. The norm, the softmax's elementwise work
and each block's recomputation are in the time and not in the
operations."""
from benchlib import kernels_lm, kernels_loop


def read(ctx):
    seconds = kernels_lm.sub_seconds(ctx, ("head",))
    if seconds is None:
        return None
    return kernels_lm.mxu_share(
        ctx, kernels_loop.head_flops_a_step(ctx.config, ctx.global_batch),
        seconds)
