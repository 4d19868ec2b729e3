"""Highest ``peak_bytes_in_use`` over the cell's chips after the window,
read before the plain reference runs."""


def read(ctx):
    return ctx.memory_peak_bytes / 2 ** 20
