"""Collective layer: device time a step under ``anat/.../select`` and
``anat/.../stage``: the selection kernels and the capacity-scale XLA
gathers of staging alike."""


def read(ctx):
    s = ctx.trace.seconds(lambda o: o.phase in ("select", "stage"))
    return 1e3 * s / ctx.trace.steps if s > 0 else None
