"""Model layer: the grouped-product kernels alone (``ragged-dot``, what
XLA:TPU compiles ``lax.ragged_dot`` to; compute-bound: three products of
hidden x moe_intermediate a row against 35 MB of float32 weights an
expert): the operations of the rows the program counted, one forward and
the backward, over the chip's published matrix peak times those kernels'
measured time. The kernels' recomputed passes and the rows of padding they
run over are in the time and not in the operations."""
from benchlib import kernels_lm


def read(ctx):
    rows = kernels_lm.counter_mean(ctx, "expert_rows")
    if not rows or ctx.trace is None:
        return None
    seconds = ctx.trace.seconds(kernels_lm.is_ragged_dot)
    return kernels_lm.mxu_share(
        ctx, kernels_lm.expert_flops_a_step(ctx.config, rows),
        seconds if seconds > 0 else None)
