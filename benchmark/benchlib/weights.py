"""Weights from the seed, made by the benchmark on the device in one call.

The program and the plain reference are both handed these, so the
reference takes nothing that the program made. The rule is by a leaf's
name, as the flax parameter tree spells it: a ``kernel`` is normal with
variance 1/fan_in (fan_in = all axes but the last), an ``embedding``
normal with variance 1/width, a ``scale`` ones, a ``bias`` zeros; and
``experts``, a stack of kernels with the expert axis first, is normal with
variance 1/shape[-2], each expert's own fan_in.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaf(path, shape, dtype, key):
    name = str(getattr(path[-1], "key", path[-1]))
    if name == "kernel":
        std = 1.0 / math.sqrt(max(1, math.prod(shape[:-1])))
        return std * jax.random.normal(key, shape, dtype)
    if name == "experts":
        return jax.random.normal(key, shape, dtype) / math.sqrt(shape[-2])
    if name == "embedding":
        return jax.random.normal(key, shape, dtype) / math.sqrt(shape[-1])
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    raise ValueError(f"no seeding rule for parameter leaf {name!r}")


def make_params(shapes, seed: int, sharding=None):
    """A tree like ``shapes`` (leaves with ``shape`` and ``dtype``)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = [_leaf(p, tuple(s.shape), s.dtype, jax.random.fold_in(key, i))
                  for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=sharding)
    return fn(jax.random.PRNGKey(seed32(seed)))


def seed32(seed: int) -> int:
    """``--seed`` may pass 2**31; fold it under it, keeping seeds apart."""
    return int(seed) % 2147483629
