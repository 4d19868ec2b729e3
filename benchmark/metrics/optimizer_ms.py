"""Step layer: device time a step of the instructions under
``anat/optimizer`` (the SGD update)."""


def read(ctx):
    s = ctx.trace.seconds(lambda o: o.phase == "optimizer")
    return 1e3 * s / ctx.trace.steps if s > 0 else None
