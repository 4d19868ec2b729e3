"""Step layer: device time a step of the instructions under
``anat/fwd_bwd`` (forward, backward, local clipping)."""


def read(ctx):
    s = ctx.trace.seconds(lambda o: o.phase == "fwd_bwd")
    return 1e3 * s / ctx.trace.steps if s > 0 else None
