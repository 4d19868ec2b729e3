"""Step layer: device time a step whose instruction no rule of the
program's owner map (``oktopk_tpu/obs/anatomy.owners``) could give a phase:
what the per-layer metrics cannot account for."""
from benchlib import owners


def read(ctx):
    t = owners.table(ctx)
    return None if t is None else t["unowned_ms"]
