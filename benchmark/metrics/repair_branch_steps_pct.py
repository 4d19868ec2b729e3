"""Kernel layer: share of the traced window's steps, joined by the
program's ``step_num``, whose staging or phase-(b) selection took the repair
or the wide branch: from the step's own ``counters``, not from an event."""
from benchlib import progspans


def read(ctx):
    v = progspans.view(ctx)
    a = v and v.column("stage_branch")
    b = v and v.column("select_branch")
    if not a or not b or "repair" not in v.branches:
        return None
    repair = v.branches.index("repair")
    hit = sum(max(x, y) >= repair for x, y in zip(a, b))
    return 100.0 * hit / len(a)
