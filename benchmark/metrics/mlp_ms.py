"""Model layer: device time a step of the dense MLPs, the operations under
``anat/fwd_bwd/mlp`` (a block's norms round the SwiGLU, its three products
and the residual add, every layer application): forward, recomputed and
backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("mlp",))
