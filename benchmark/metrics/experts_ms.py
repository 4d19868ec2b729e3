"""Model layer: device time a step of the routed experts: the operations
under ``anat/fwd_bwd/router`` (gate product, softmax, top-k, which tokens go
to a held expert) and ``anat/fwd_bwd/experts`` (sort, gather, weighted
scatter-add; forward, recomputed and backward) and the grouped products'
own kernels (``ragged-dot``: XLA:TPU's, found by name, since they carry no
scope). The shared experts are not in it."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("router", "experts"),
                             (kernels_lm.RAGGED_DOT,))
