"""Dense allreduce baseline + the shared warmup wrapper.

Reference: the ``dense`` compressor branch (VGG/allreducer.py:175-180,532-547)
and the dense-allreduce warmup that every sparse algorithm starts with
(512 iters for VGG, VGG/allreducer.py:573; 128 for LSTM; disabled for BERT).
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax

from oktopk_tpu.collectives.state import SparseState, bump
from oktopk_tpu.collectives.wire import dense_wire_bytes
from oktopk_tpu.comm.primitives import pvary_like
from oktopk_tpu.config import OkTopkConfig
from oktopk_tpu.obs.anatomy import phase_scope


def dense_allreduce(grad, state: SparseState, cfg: OkTopkConfig,
                    axis_name: str = "data"):
    """psum-mean over the data axis (ring allreduce moves ~2n per worker).

    ``grad`` is the bucket's flat vector or the bucket a leaf at a time: a
    tuple of its gradient leaves, each in its own shape, ``cfg.n`` elements
    in all (optim/distributed.py hands a dense bucket over so where nothing
    reads the flat vector). The mean is element-wise, so the mean of a
    concatenation is the concatenation of the means, and either form is ONE
    ``pmean`` under one ``exchange`` scope. Over a tuple JAX binds a
    ``psum`` a leaf and lowers an ``all_reduce`` a leaf; what goes on the
    wire together is XLA's all-reduce combiner's choice in both forms
    (XLA:CPU merges all of a step's buckets into one all-reduce, flat or
    not; over a mesh axis of size one nothing is emitted at all). The
    accounting is the same line for both."""
    with phase_scope("exchange", cfg.bucket_index):
        out = lax.pmean(grad, axis_name)
    out, state = pvary_like(
        (out, bump(state, volume=2.0 * cfg.n,
                   wire_bytes=dense_wire_bytes(2.0 * cfg.n),
                   local_count=cfg.n, global_count=cfg.n)),
        jax.tree.leaves(grad)[0])
    return out, state


def with_warmup(algo_fn):
    """Run dense allreduce for the first ``cfg.warmup_steps`` steps, then the
    sparse algorithm (reference VGG/allreducer.py:573-574). Both branches are
    traced with identical shapes, as ``lax.cond`` requires."""

    def wrapped(grad, state, cfg: OkTopkConfig, axis_name: str = "data"):
        if cfg.warmup_steps <= 0:
            return algo_fn(grad, state, cfg, axis_name)
        return lax.cond(
            state.step < cfg.warmup_steps,
            partial(dense_allreduce, cfg=cfg, axis_name=axis_name),
            partial(algo_fn, cfg=cfg, axis_name=axis_name),
            grad, state,
        )

    wrapped.__name__ = f"warmup({getattr(algo_fn, '__name__', 'algo')})"
    return wrapped
