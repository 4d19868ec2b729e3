"""Step layer: device time a step of the instructions that carry no
``anat/`` phase of their own (the compiler made or renamed them) and got
one from their surroundings by the owner map's rules 2-5 (a ``*-done``'s
``*-start``, the nearest operand, the nearest user, the body's caller)."""
from benchlib import owners


def read(ctx):
    return owners.total_ms(ctx, "inherited_ms")
