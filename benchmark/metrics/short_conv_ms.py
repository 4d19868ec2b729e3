"""Model layer: device time a step of the short-convolution operators, the
operations under ``anat/fwd_bwd/short_conv`` (``W_in``'s product, ``W_out``'s
product and the residual add of every ``conv`` layer; the norm before it is
outside) and under ``anat/fwd_bwd/gated_conv`` inside it (the two gates and
the taps, which ``gated_conv_ms`` reads alone): forward, recomputed and
backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("short_conv", "gated_conv"))
