"""Kernel layer: share of the traced steps in which the wide staging
kernel (capacity 1024 a block) has a device event: the overflow branch
that a step's tail time comes from. A count, not a time."""

KERNEL = "oktopk_stage_w1024"


def read(ctx):
    return 100.0 * ctx.trace.steps_with(lambda o: o.mentions(KERNEL))
