"""Global samples completed in the window over its length: every step and
all the time from the first dispatch to the last completion. An LSTM sample
is one sequence of the configuration's length."""
from benchlib import window


def read(ctx):
    return window.rate(ctx.window.t0, ctx.window.stamps, ctx.global_batch)
