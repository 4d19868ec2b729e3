"""Functional compression kernels (replaces reference compression.py).

The reference's compressors (TopKCompressor / GaussianCompressor and eight
subclasses, reference VGG/compression.py) are stateful classes with class-attr
residual dicts. Here every operation is a pure function over explicit arrays;
residual state lives in ``collectives.state.SparseState`` and is threaded
through jit, so it is checkpointable (fixing the reference gap noted in
SURVEY.md §5.4: residuals were never saved).
"""

from oktopk_tpu.ops.topk import (  # noqa: F401
    exact_topk,
    ratio2threshold,
    k2threshold,
)
from oktopk_tpu.ops.select import (  # noqa: F401
    SENTINEL,
    count_by_threshold,
    scatter_sparse,
    select_by_threshold,
    select_mask,
    select_nonzero,
    pack_by_region,
)
from oktopk_tpu.ops.gaussian import gaussian_threshold  # noqa: F401
from oktopk_tpu.ops.fused_select import (  # noqa: F401
    fused_pack_finalize,
    fused_select_pallas,
    fused_select_reference,
    fused_select_stage,
)
from oktopk_tpu.ops.residual import (  # noqa: F401
    add_residual,
    update_residual_at_winners,
    update_residual_at_selection,
)
