"""Model layer: device time a step of the gated convolution alone, the
operations under ``anat/fwd_bwd/gated_conv`` (``B * z``, the depthwise
causal taps and ``C *`` of every ``conv`` layer, elementwise; they lie
inside ``short_conv``, and ``short_conv_ms`` counts them too): forward,
recomputed and backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("gated_conv",))
