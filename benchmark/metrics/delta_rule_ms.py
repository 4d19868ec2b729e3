"""Model layer: device time a step of the gated delta rule alone, the
operations under ``anat/fwd_bwd/delta_rule``: the chunk-local triangular
systems and the scan over chunks that carries the state; forward, each
segment's recomputation and backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("delta_rule",))
