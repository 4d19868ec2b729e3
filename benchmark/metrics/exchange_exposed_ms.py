"""Collective layer: time a step of the all-to-all, all-gather and
all-reduce device operations during which no other operation runs on that
chip."""


def read(ctx):
    if ctx.workers < 2:
        return None
    return 1e3 * ctx.trace.exposed_collective_s() / ctx.trace.steps
