"""Model layer: the operations the short-convolution operators' two
products need (``W_in`` [D, 3 D] and ``W_out`` [D, D] of every ``conv``
layer, one forward and the backward: ``benchlib/kernels_conv.py``) over the
chip's published matrix peak times the measured time of ``short_conv_ms``'s
operations (the whole operator): the share of the peak that is useful work.
The gated convolution's memory-bound passes and the layer's recomputation
of the whole operator are in the time and not in the operations."""
from benchlib import kernels_conv, kernels_lm


def read(ctx):
    seconds = kernels_lm.sub_seconds(ctx, ("short_conv", "gated_conv"))
    if seconds is None or "layer_types_run" not in ctx.config:
        return None
    return kernels_lm.mxu_share(
        ctx, kernels_conv.products_flops_a_step(ctx.config, ctx.global_batch),
        seconds)
