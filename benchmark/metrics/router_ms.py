"""Model layer: device time a step of the routers, the operations under
``anat/fwd_bwd/router`` (the float32 ``highest`` logits, their scores, the
top-k, the renormalised weights and the held experts' mask of every sparse
layer); forward, recomputed and backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("router",))
