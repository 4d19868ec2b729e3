"""Model layer: device time a step of ALL the attention of a model whose
layers differ in kind and head count: the operations under
``anat/fwd_bwd/attention`` and ``window_attention`` (norm, projections,
rotary, output projection of a full and of a sliding layer) and under the
three scopes that lie inside them, ``full_scores``, ``window_scores`` (the
scores, softmax and weighted sum) and ``attn_gate`` (the per-head output
gate's projection, sigmoid and product); forward, recomputed and
backward."""
from benchlib import kernels_lm


def read(ctx):
    return kernels_lm.sub_ms(ctx, ("attention", "window_attention",
                                   "full_scores", "window_scores",
                                   "attn_gate"))
