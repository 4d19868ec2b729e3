"""Model layer: device time a step that ``anat/fwd_bwd/attention`` owns:
``attention_ms`` and the asynchronous copies and slices that XLA:TPU makes
of the score blocks inside the query-block loop, which carry no scope."""
from benchlib import owners


def read(ctx):
    return owners.owned_ms(ctx, "fwd_bwd", ("attention",))
