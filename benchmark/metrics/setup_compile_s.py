"""Entry layer: seconds of jaxpr tracing, lowering and back-end compiling
(persistent-cache loads included) that the program's own listener counted
in host steps before the traced window's first."""
from benchlib import progspans


def read(ctx):
    v = progspans.view(ctx)
    if v is None or not v.compile_by_step:
        return None
    return sum(sum(kinds.values()) for step, kinds in
               v.compile_by_step.items() if step < v.steps[0])
