"""Exact top-k selection and threshold computation.

Replaces the reference's ``torch.topk``-based paths:
- ``TopKCompressor.ratio2threshold`` (reference VGG/compression.py:86-106):
  exact k-th-largest |grad| after residual add.
- ``k2globalthreshold`` (reference VGG/compression.py:407-415): exact k-th
  largest of a gathered value buffer.

On TPU, ``lax.top_k`` maps to an XLA sort/partition; for the very large flat
gradients a Pallas bucketed-count kernel can replace it (ops/pallas_topk.py).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def exact_topk(x: jnp.ndarray, k: int):
    """(values, indices) of the k largest |x|, values keep their sign.

    Reference TopKCompressor.compress (VGG/compression.py:63-84).
    """
    absx = jnp.abs(x)
    _, idx = lax.top_k(absx, k)
    return x[idx], idx


def k2threshold(x_abs: jnp.ndarray, k: int):
    """The k-th largest value of ``x_abs`` (selection threshold).

    Reference k2globalthreshold (VGG/compression.py:407-415).
    """
    vals = lax.top_k(x_abs, k)[0]
    return vals[k - 1]


def k2threshold_method(x_abs: jnp.ndarray, k: int, method: str = "sort",
                       bisect_iters: int = 30):
    """Dispatch between the exact sort-based threshold ("sort": the
    reference-faithful one, static k) and the sort-free bisection
    ("bisect", ops/pallas_topk.py) — selected by
    ``OkTopkConfig.threshold_method``."""
    if method == "bisect":
        from oktopk_tpu.ops.pallas_topk import k2threshold_bisect
        return k2threshold_bisect(x_abs, k, iters=bisect_iters)
    return k2threshold(x_abs, k)


def ratio2threshold(x: jnp.ndarray, density: float):
    """Exact threshold such that |x| >= t selects ~density*n elements.

    Reference TopKCompressor.ratio2threshold (VGG/compression.py:86-106) —
    the every-32-iterations exact recompute of the local threshold.
    """
    k = max(1, int(density * x.size))
    return k2threshold(jnp.abs(x), k)
