"""Model layer: the operations that the rows the program counted at its
held experts need (``benchlib/kernels_lm.py``: one forward and the
backward) over the chip's published matrix peak times the measured time of
``experts_ms``'s operations: the share of the peak that is useful work. Low
where recomputation, the buffer's padding, the sort, the gather and the
scatter-add take the time; it cannot pass 100 %."""
from benchlib import kernels_lm


def read(ctx):
    rows = kernels_lm.counter_mean(ctx, "expert_rows")
    if not rows:
        return None
    return kernels_lm.mxu_share(
        ctx, kernels_lm.expert_flops_a_step(ctx.config, rows),
        kernels_lm.sub_seconds(ctx, ("router", "experts"),
                               (kernels_lm.RAGGED_DOT,)))
