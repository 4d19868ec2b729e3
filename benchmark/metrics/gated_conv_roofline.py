"""Model layer: the least time the chip could take for a step's gated
convolutions over the time they took (``gated_conv_ms``'s operations). The
least time is the larger of the taps' operations over the matrix peak and
the bytes of B, C, z and the result over the memory bandwidth (the bytes
bound it), counted from shapes alone, one forward and the backward
(``benchlib/kernels_conv.py``): what the layer's recomputation runs again,
and every pass over [T, D] that fusion did not save, are in the time and
not in the count."""
from benchlib import kernels_conv


def read(ctx):
    return kernels_conv.roofline_share(
        ctx, kernels_conv.gated_conv_roofline_seconds, "gated_conv")
