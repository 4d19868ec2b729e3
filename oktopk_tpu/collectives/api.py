"""Host-level entry points: run a sparse allreduce over a device mesh.

The per-shard algorithm functions (this package) correspond to the body the
reference runs on every MPI rank; this module is the analogue of wiring them
into the process world — except the "world" is a ``jax.sharding.Mesh`` and the
wiring is ``shard_map`` + jit. Also provides the EPS-vs-dense equivalence
harness mirroring the reference's PROFILING_NORM measurement
(VGG/allreducer.py:584-606,1072-1080: EPS = ‖dense−sparse‖₂/‖dense‖₂).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from oktopk_tpu.collectives.hierarchical import HierarchicalConfig
from oktopk_tpu.collectives.registry import get_algorithm
from oktopk_tpu.collectives.state import SparseState, init_state
from oktopk_tpu.comm import compat
from oktopk_tpu.config import OkTopkConfig


def batched_init_state(cfg, dtype=jnp.float32) -> SparseState:
    """Per-worker state stacked on a leading device axis [P, ...] so it can be
    sharded over the data axis (each worker owns its residual/thresholds,
    as each rank does in the reference).

    A :class:`HierarchicalConfig` is accepted too: the state is the OUTER
    level's (residual/thresholds live among pod leaders only) replicated
    across all ``num_pods * pod_size`` worker rows — each pod's members
    carry identical copies, mirroring the leader-replication the
    emulated exchange performs."""
    base = cfg.outer_cfg if isinstance(cfg, HierarchicalConfig) else cfg
    s = init_state(base, dtype)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.num_workers,) + x.shape), s)


def _hierarchical_setup(name: str, cfg, mesh, warmup: bool):
    """Shared validation/normalisation for the hierarchical build paths:
    returns ``(cfg, spec)`` with pallas resolved on the outer config and
    the shard spec covering (inter, intra) on the leading grad axis."""
    from oktopk_tpu.ops.compaction import resolve_use_pallas
    if name != "hierarchical":
        raise ValueError(
            f"config is a HierarchicalConfig but algorithm is {name!r}; "
            "pass name='hierarchical' (outer algorithm goes in cfg.outer)")
    if not isinstance(cfg, HierarchicalConfig):
        raise TypeError(
            f"build step for {name!r} needs a HierarchicalConfig "
            "(collectives.hierarchical.make_hierarchical_config), got "
            f"{type(cfg).__name__}")
    for ax, want in ((cfg.inter_axis, cfg.num_pods),
                     (cfg.intra_axis, cfg.pod_size)):
        have = dict(zip(mesh.axis_names, mesh.devices.shape)).get(ax)
        if have != want:
            raise ValueError(
                f"mesh axis {ax!r} has size {have}, config wants {want} "
                f"(mesh axes {dict(zip(mesh.axis_names, mesh.devices.shape))})")
    cfg = cfg.replace(outer_cfg=resolve_use_pallas(cfg.outer_cfg, mesh),
                      outer_warmup=warmup)
    return cfg, P((cfg.inter_axis, cfg.intra_axis))


def build_allreduce_step(name: str, cfg, mesh: Mesh,
                         axis_name: str = "data", warmup: bool = True,
                         check_vma: bool = True, donate_state: bool = False):
    """jit-compiled ``(grads [P, n], state) -> (results [P, n], state)``.

    ``results`` is the same reduced vector replicated per worker row (every
    rank gets the full result, as after the reference's allgather phase).

    ``cfg`` is an ``OkTopkConfig`` for the flat algorithms, or a
    ``HierarchicalConfig`` with ``name="hierarchical"`` — then ``mesh``
    must be two-level (comm.mesh.hierarchical_mesh) and grads'/state's
    leading [P] axis is sharded over (inter, intra); ``axis_name`` is
    ignored (both axes come from the config).

    ``check_vma=False`` disables shard_map's varying-axes tracking — needed
    when running the Pallas selection kernel through its interpreter on a
    CPU mesh (the interpreter cannot mix VMA-tracked operands). Compiled
    through Mosaic on a TPU mesh the kernels run with the tracking on
    (tests/test_tpu_hw.py, one chip and four).

    ``donate_state=True`` donates the state argument's buffers to the call,
    letting XLA write the new residual (and the oktopk phase-(a) ``reduced``
    scratch) into the old residual's n-length allocation instead of
    materialising a second dense buffer. Opt-in because a donated state is
    consumed: callers that re-use one state across calls — e.g. the
    profiling loops in scripts/profile_step.py — must leave it off, while
    the train-loop pattern ``out, state = step(g, state)`` is safe.
    """
    from oktopk_tpu.ops.compaction import resolve_use_pallas
    if name == "hierarchical" or isinstance(cfg, HierarchicalConfig):
        # two-level path: spec covers (inter, intra) on the leading grad
        # axis; warmup is composed on the OUTER level (registry.py)
        cfg, spec = _hierarchical_setup(name, cfg, mesh, warmup)
        algo, axis_arg = get_algorithm("hierarchical", warmup=False), None
    else:
        cfg = resolve_use_pallas(cfg, mesh)
        algo, axis_arg = get_algorithm(name, warmup=warmup), axis_name
        spec = P(axis_name)

    def shard_fn(g, s):
        g1 = g[0]
        s1 = jax.tree.map(lambda x: x[0], s)
        out, s2 = algo(g1, s1, cfg, axis_arg)
        return out[None], jax.tree.map(lambda x: x[None], s2)

    mapped = compat.shard_map(shard_fn, mesh=mesh,
                              in_specs=(spec, spec), out_specs=(spec, spec),
                              check_vma=check_vma)
    if donate_state:
        return jax.jit(mapped, donate_argnums=(1,))
    return jax.jit(mapped)


def build_quality_allreduce_step(name: str, cfg, mesh: Mesh,
                                 quality, axis_name: str = "data",
                                 warmup: bool = True,
                                 check_vma: bool = True):
    """``build_allreduce_step`` plus the in-jit signal-fidelity tap:
    ``(grads [P, n], state, qbuf) -> (results, state, qbuf)``.

    ``quality`` is an ``obs.quality.QualityConfig``; ``qbuf`` a batched
    ``obs.metrics_buffer.QualityBuffer`` ([P, ...] leaves, e.g. from
    broadcasting ``init_buffer`` like :func:`batched_init_state` does).
    The tap is the EXACT code path the trainer threads through
    ``optim.build_sparse_grad_step`` — same ``measure_bucket``, same
    ring commit — so the dense-vs-sparse oracle tests
    (tests/test_quality.py) validate what training runs journal, not a
    reimplementation."""
    from oktopk_tpu.obs.quality import commit, measure_bucket
    from oktopk_tpu.ops.compaction import resolve_use_pallas
    from jax import lax
    hier = name == "hierarchical" or isinstance(cfg, HierarchicalConfig)
    if hier:
        cfg, spec = _hierarchical_setup(name, cfg, mesh, warmup)
        algo, axis_arg = get_algorithm("hierarchical", warmup=False), None
    else:
        cfg = resolve_use_pallas(cfg, mesh)
        algo, axis_arg = get_algorithm(name, warmup=warmup), axis_name
        spec = P(axis_name)
    del quality  # static config lives in the buffer's shapes

    def shard_fn(g, s, q):
        g1 = g[0]
        s1 = jax.tree.map(lambda x: x[0], s)
        q1 = jax.tree.map(lambda x: x[0], q)
        out, s2 = algo(g1, s1, cfg, axis_arg)
        if hier:
            # the intra psum is lossless, so the fidelity oracle is the
            # unchanged pre-selection dense gradient: the full-world mean
            # of grad plus the (pod-level) error-feedback residual
            dense = lax.pmean(
                lax.pmean(g1, cfg.intra_axis) + s1.residual, cfg.inter_axis)
        else:
            dense = lax.pmean(g1 + s1.residual, axis_name)
        scalars = measure_bucket(out, dense, s2, q1.prev_sig,
                                 q1.prev_res_norm)
        q2 = commit(q1, s2.step, scalars, jnp.asarray(False))
        return (out[None], jax.tree.map(lambda x: x[None], s2),
                jax.tree.map(lambda x: x[None], q2))

    mapped = compat.shard_map(shard_fn, mesh=mesh,
                              in_specs=(spec, spec, spec),
                              out_specs=(spec, spec, spec),
                              check_vma=check_vma)
    return jax.jit(mapped)


def time_allreduce_step(step_fn, grads, state, iters: int = 3,
                        warmup_iters: int = 1):
    """Per-step wall times of a ``build_allreduce_step`` program.

    The autotuner's trial phase (autotune/trial.py) needs step times it can
    compare across algorithms; each timed call ends in
    ``block_until_ready`` on the result and the new state.

    Returns ``(times_ms, state)`` with ``len(times_ms) == iters``;
    ``warmup_iters`` untimed calls first absorb compilation.
    """
    import time

    for _ in range(warmup_iters):
        out, state = jax.block_until_ready(step_fn(grads, state))
    times_ms = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out, state = jax.block_until_ready(step_fn(grads, state))
        times_ms.append((time.perf_counter() - t0) * 1e3)
    return times_ms, state


@partial(jax.jit, static_argnames=())
def eps_vs_dense(dense_result: jnp.ndarray, sparse_result: jnp.ndarray):
    """EPS = ‖dense − sparse‖₂ / ‖dense‖₂ (reference VGG/allreducer.py:1072-1080)."""
    num = jnp.linalg.norm(dense_result - sparse_result)
    den = jnp.linalg.norm(dense_result) + 1e-12
    return num / den
