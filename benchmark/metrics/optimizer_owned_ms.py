"""Step layer: device time a step that ``anat/optimizer`` owns
(``optimizer_ms`` and what inherits from it)."""
from benchlib import owners


def read(ctx):
    return owners.owned_ms(ctx, "optimizer")
