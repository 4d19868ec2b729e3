"""Model layer: the least time the chip could take for a step's causal
scores of the ``full_attention`` layers of a model whose heads are
narrower than a lane row (64 dims) over the time they took (the operations
under ``anat/fwd_bwd/full_scores``). The least time is the larger of the
TRIANGLE's operations over the matrix peak and its bytes over the memory
bandwidth at the configuration's grouped heads, counted from shapes alone,
one forward and the backward (``benchlib/kernels_conv.py``): what a tile
computes past the diagonal and every recomputation are in the time and not
in the count."""
from benchlib import kernels_conv


def read(ctx):
    return kernels_conv.roofline_share(
        ctx, kernels_conv.scores_roofline_seconds, "full_scores")
