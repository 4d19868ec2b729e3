"""Model layer: the least time the chip could take for a step's gated delta
rule over the time it took (``delta_rule_ms``'s operations). The least time
is the larger of the token recurrence's operations over the matrix peak and
its bytes over the memory bandwidth, counted from shapes alone, one forward
and the backward (``benchlib/kernels_gdn.py``): the chunked form's own
work and every recomputation are in the time and not in the count."""
from benchlib import kernels_gdn, kernels_lm


def read(ctx):
    seconds = kernels_lm.sub_seconds(ctx, ("delta_rule",))
    if not seconds:
        return None
    tokens = ctx.global_batch * int(ctx.config["seq_len"])
    least, _ = kernels_gdn.delta_rule_roofline_seconds(
        ctx.config, tokens, ctx.device_kind)
    return 100.0 * least / (seconds / ctx.trace.steps)
