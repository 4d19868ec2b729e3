"""The jax spellings the package is written against, in one place.

The installation is jax 0.9.0: ``jax.shard_map``, varying-manual-axes types
(``jax.typeof(x).vma``, ``lax.pcast``) and ``lax.axis_size`` all exist, so
these are one-line names for them — kept because ~50 call sites use them,
not because any of them branches on the jax version.
"""

from __future__ import annotations

import jax
from jax import lax

shard_map = jax.shard_map
axis_size = lax.axis_size


def typeof_vma(x) -> frozenset:
    """The varying-manual-axes set of ``x``'s type (empty outside
    shard_map, and for concrete values)."""
    return frozenset(jax.typeof(x).vma)


def pvary(x, axes):
    """Mark ``x`` as varying over ``axes`` (no-op for an empty set)."""
    axes = tuple(axes)
    return lax.pcast(x, axes, to="varying") if axes else x


def shape_dtype_struct(shape, dtype, vma=None) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
