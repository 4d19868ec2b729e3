"""Kernel layer: mean count a step of blocks over the fast staging width
(staging's census plus the phase-(b) select's), over the traced window's
steps: what the overflow branches are chosen by."""
from benchlib import progspans


def read(ctx):
    v = progspans.view(ctx)
    a = v and v.column("stage_overflow_blocks")
    b = v and v.column("select_overflow_blocks")
    if not a or not b:
        return None
    return (sum(a) + sum(b)) / len(a)
