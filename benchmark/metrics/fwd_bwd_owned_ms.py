"""Step layer: device time a step that ``anat/fwd_bwd`` owns, the model
step whole: its own instructions (``fwd_bwd_ms`` reads those alone), what
inherits from them, and its loops' and branches' own time."""
from benchlib import owners


def read(ctx):
    return owners.owned_ms(ctx, "fwd_bwd")
