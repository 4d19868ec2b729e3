"""Model layer: device time a step that ``anat/fwd_bwd/router`` and
``anat/fwd_bwd/experts`` own. The grouped products' kernels carry no scope
and come by the owner map's rules (their operands are the experts'), not by
their name, so a kernel of another name stays counted."""
from benchlib import owners


def read(ctx):
    return owners.owned_ms(ctx, "fwd_bwd", ("router", "experts"))
