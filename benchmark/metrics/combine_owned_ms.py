"""Collective layer: device time a step that ``anat/.../combine`` owns
(the scatter of the received values and the residual's update), its own
instructions and what inherits from them."""
from benchlib import owners


def read(ctx):
    return owners.owned_ms(ctx, "combine")
