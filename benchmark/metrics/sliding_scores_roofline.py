"""Model layer: the least time the chip could take for a step's banded
scores of the sliding-window layers over the time it took (the operations
under ``anat/fwd_bwd/window_scores``). The least time is the larger of the
BAND's operations over the matrix peak and its bytes over the memory
bandwidth, each layer at its own head count, counted from shapes alone, one
forward and the backward (``benchlib/kernels_mixed_gqa.py``): what a tile
computes outside the band and every recomputation are in the time and not
in the count."""
from benchlib import kernels_mixed_gqa


def read(ctx):
    return kernels_mixed_gqa.roofline_share(ctx, kernels_mixed_gqa.SLIDING,
                                            "window_scores")
