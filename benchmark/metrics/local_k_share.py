"""Collective layer: mean realised local selection count over k (density
x n), over the traced window's steps: how close the threshold controller
holds the count to its target."""
from benchlib import progspans


def read(ctx):
    v = progspans.view(ctx)
    col = v and v.column("local_k")
    if not col:
        return None
    return sum(col) / len(col) / (ctx.algo_cfg.density * ctx.n)
