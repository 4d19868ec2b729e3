"""Model layer: the least time the chip could take for a step's causal
scores of every layer application of a looped model over the time they took
(the operations under ``anat/fwd_bwd/full_scores``). The least time is the
larger of the TRIANGLE's operations over the matrix peak and its bytes over
the memory bandwidth at the configuration's ungrouped heads, counted from
shapes alone, one forward and the backward, times passes x layers
(``benchlib/kernels_loop.py``): what a tile computes past the diagonal and
every recomputation are in the time and not in the count."""
from benchlib import kernels_loop


def read(ctx):
    return kernels_loop.scores_roofline_share(ctx, "full_scores")
