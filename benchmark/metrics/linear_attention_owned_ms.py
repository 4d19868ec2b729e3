"""Model layer: device time a step that ``anat/fwd_bwd/linear_attention``
and ``anat/fwd_bwd/delta_rule`` own: ``linear_attention_ms`` and the
layout copies, slices and transposes of the segment loop and the chunk
loop, which carry no scope."""
from benchlib import owners


def read(ctx):
    return owners.owned_ms(ctx, "fwd_bwd", ("linear_attention", "delta_rule"))
