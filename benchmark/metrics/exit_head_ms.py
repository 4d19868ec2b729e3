"""Model layer: device time a step of a looped model's exits, the
operations under ``anat/fwd_bwd/head`` (every pass's final norm, head
product and cross-entropy, a block of rows at a time) and under
``anat/fwd_bwd/exit_gate`` (the gate's product, the exits' distribution,
the mixing and the entropy); forward, recomputed and backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("head", "exit_gate"))
