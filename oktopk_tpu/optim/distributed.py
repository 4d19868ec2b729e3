"""The distributed training step: grads -> sparse allreduce -> update.

This replaces the reference's entire L3/L4 concurrency machinery
(SURVEY.md §3.1): the per-parameter autograd hooks
(VGG/distributed_optimizer.py:63-94), the background allreducer thread and
its two-queue handshake (VGG/allreducer.py:549, :1640-1643), and the
``synchronize()`` join (:96-105). Under XLA all of that is one traced
program: backward, reverse-layer-order buckets (the analogue of the
reference's bucket merge, VGG/allreducer.py:272-330; with ``num_buckets=1``
the whole model is one bucket like the BERT variant's "myallreduce" flat
tensor, BERT/bert/allreducer.py:200), one collective per bucket, optimizer
update. A sparse collective selects over one address space: its bucket is
flattened (the leaves concatenated into one vector) and the reduced vector
is cut up into the leaves again. The dense all-reduce is element-wise and
needs no such vector: its bucket is reduced a leaf at a time, the leaves
the operands of one ``pmean``, unless an option of the step reads the
vector (``step.leafwise`` says which buckets were). Compute/communication
overlap is XLA's async collective scheduling instead of Python threads.

Local gradient accumulation (``nsteps_update``, reference
VGG/main_trainer.py:82-100) is a ``lax.scan`` over microbatches before the
single allreduce.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Union

import flax.struct
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oktopk_tpu.collectives.dense import dense_allreduce
from oktopk_tpu.collectives.registry import get_algorithm
from oktopk_tpu.collectives.state import (
    BRANCH_COUNTERS,
    MODEL_COUNTERS,
    SparseState,
    init_state,
)
from oktopk_tpu.comm import compat
from oktopk_tpu.config import OkTopkConfig
from oktopk_tpu.obs.anatomy import phase_scope


@flax.struct.dataclass
class DistTrainState:
    """Replicated training state + per-worker sparse state (leading device
    axis on every SparseState leaf). ``local_momentum`` is the per-worker
    flat momentum buffer used only under momentum correction.
    ``health`` is the replicated :class:`resilience.guard.HealthState`
    (attempt/skip counters), present only when the step carries the
    anomaly guard or a fault plan. ``quality`` is the per-worker
    :class:`obs.metrics_buffer.QualityBuffer` fidelity ring (per-bucket
    tuple when bucketed, mirroring ``sparse_state``), present only when
    the step carries the in-jit quality taps; checkpoints saved before
    the field existed restore cleanly (checkpoint.py template merge)."""
    params: Any
    model_state: Any          # e.g. flax batch_stats collection
    opt_state: Any
    sparse_state: SparseState
    local_momentum: Any = None
    health: Any = None
    quality: Any = None


def flat_size(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


def bucket_partition(params, num_buckets: int):
    """Contiguous leaf-index buckets in REVERSE flattened order,
    greedily balanced by element count.

    Reference semantics: the allreducer consumes layer grads in reverse
    layer order as backward produces them and merges them into <=640 MiB
    buckets (VGG/allreducer.py:27,272-330) — bucket 0 holds the LAST
    layers, whose grads are ready first, so its collective can overlap the
    remaining backward (under XLA: independent collectives schedule
    against compute).

    Returns a list of leaf-index lists (ascending within each bucket).
    """
    sizes = [x.size for x in jax.tree.leaves(params)]
    total = sum(sizes)
    L = len(sizes)
    num_buckets = max(1, min(num_buckets, L))
    target = total / num_buckets
    buckets, cur, acc = [], [], 0
    for pos, i in enumerate(reversed(range(L))):   # last layers first
        cur.append(i)
        acc += sizes[i]
        leaves_left = L - pos - 1
        still_needed = num_buckets - len(buckets) - 1
        if len(buckets) < num_buckets - 1 and (
                acc >= target - 1e-9            # fair share reached, or
                or leaves_left == still_needed  # must close to keep every
        ):                                      # later bucket non-empty
            buckets.append(sorted(cur))
            cur, acc = [], 0
    buckets.append(sorted(cur))
    assert len(buckets) == num_buckets and all(buckets), buckets
    return buckets


def bucket_sizes(params, buckets):
    sizes = [x.size for x in jax.tree.leaves(params)]
    return [int(sum(sizes[i] for i in b)) for b in buckets]


def init_dist_state(params, model_state, optimizer, cfg: OkTopkConfig,
                    dtype=jnp.float32,
                    momentum_correction: bool = False,
                    opt_state: Any = None,
                    num_buckets: int = 1,
                    with_health: bool = False,
                    quality=None) -> DistTrainState:
    """``momentum_correction`` must be truthy iff the step builder gets a
    nonzero ``momentum_correction`` factor — the shard_map specs key off the
    presence of ``local_momentum``. Pass ``opt_state`` to carry over existing
    optimizer state (e.g. across an elastic resize) instead of allocating a
    fresh one. With ``num_buckets > 1`` the sparse state (and momentum) is a
    tuple of per-bucket states matching :func:`bucket_partition`.
    ``with_health`` must be truthy iff the step builder gets a guard or a
    fault plan — the shard_map specs key off the presence of ``health``.
    ``quality`` (an ``obs.quality.QualityConfig``) must likewise match the
    step builder's ``quality`` argument: it allocates the per-bucket
    fidelity rings the in-jit taps push into."""
    def batched(n_b):
        s = init_state(cfg.replace(n=n_b), dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_workers,) + x.shape), s)

    def qbatched():
        from oktopk_tpu.obs.metrics_buffer import init_buffer
        b = init_buffer(quality.every, quality.sig_bins, dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_workers,) + x.shape), b)

    if num_buckets > 1:
        nbs = bucket_sizes(params, bucket_partition(params, num_buckets))
        s = tuple(batched(n_b) for n_b in nbs)
        mom = (tuple(jnp.zeros((cfg.num_workers, n_b), dtype)
                     for n_b in nbs) if momentum_correction else None)
        qual = (tuple(qbatched() for _ in nbs)
                if quality is not None else None)
    else:
        s = batched(cfg.n)
        mom = (jnp.zeros((cfg.num_workers, cfg.n), dtype)
               if momentum_correction else None)
        qual = qbatched() if quality is not None else None
    health = None
    if with_health:
        from oktopk_tpu.resilience.guard import init_health
        health = init_health(num_buckets)
    return DistTrainState(params=params, model_state=model_state,
                          opt_state=(optimizer.init(params)
                                     if opt_state is None else opt_state),
                          sparse_state=s, local_momentum=mom,
                          health=health, quality=qual)


def dist_state_specs(axis_name: str, momentum_correction: bool,
                     has_health: bool, has_quality: bool) -> DistTrainState:
    """How the step shards a :class:`DistTrainState`: per-worker leaves over
    ``axis_name``, everything else replicated."""
    return DistTrainState(
        params=P(), model_state=P(), opt_state=P(),
        sparse_state=P(axis_name),
        local_momentum=P(axis_name) if momentum_correction else None,
        health=P() if has_health else None,
        quality=P(axis_name) if has_quality else None)


def place_dist_state(state: DistTrainState, mesh: Mesh,
                     axis_name: str = "data") -> DistTrainState:
    """Put a fresh state where the step will leave it. ``init_dist_state``
    builds every leaf on the default device — all P residual rows on chip
    0 — and a step first called on that layout is compiled for it, then
    compiled again for the sharded state it returned."""
    specs = dist_state_specs(axis_name, state.local_momentum is not None,
                             state.health is not None,
                             state.quality is not None)
    return jax.device_put(state, jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P)))


def build_sparse_grad_step(
    loss_fn: Callable,
    optimizer,
    cfg: OkTopkConfig,
    mesh: Mesh,
    compressor: Union[str, Sequence[str]] = "oktopk",
    axis_name: str = "data",
    nsteps_update: int = 1,
    grad_clip: Optional[float] = None,
    warmup: bool = True,
    profile_norm: bool = False,
    momentum_correction: float = 0.0,
    num_buckets: int = 1,
    bucket_densities: Optional[Sequence[float]] = None,
    guard=None,
    fault_plan=None,
    quality=None,
):
    """Build the jitted distributed train step.

    Args:
      loss_fn: ``(params, model_state, batch, rng) -> (loss, (model_state,
        metrics))`` evaluated on the *local* microbatch shard.
      optimizer: object with ``init(params)`` / ``update(grads, state,
        params)`` (optim.sgd / optim.bert_adam / any optax transform).
      cfg: algorithm config; ``cfg.n`` must equal the flat parameter count.
      nsteps_update: local accumulation microsteps before one allreduce
        (reference VGG/main_trainer.py:85-89).
      grad_clip: optional global-norm clip applied to the *local* grad before
        the allreduce (reference LSTM/main_trainer.py:94-99).
      profile_norm: add an ``eps_vs_dense`` metric — the reference's
        PROFILING_NORM instrumentation (EPS = ‖dense−sparse‖₂/‖dense‖₂,
        VGG/allreducer.py:1072-1080). Costs one extra dense pmean per step.
      momentum_correction: DGC-style local momentum factor applied BEFORE
        compression (reference _DistributedOptimizer's momentum-correction
        option, VGG/distributed_optimizer.py:56,81-88). The optimizer should
        then be momentum-free SGD, since momentum is already folded into the
        compressed gradient stream.
      num_buckets: > 1 runs one sparse collective per reverse-layer-order
        bucket (reference <=640 MiB bucketing, VGG/allreducer.py:27,
        272-330) with per-bucket SparseState — bucket 0 depends only on
        the last layers' grads, so XLA can overlap its collective with the
        remaining backward. Selection becomes per-bucket top-k, exactly
        the reference's per-merged-group compression.
      compressor: one registry name for every bucket, or a sequence of
        ``num_buckets`` names — the per-bucket plan the autotuner
        (autotune/policy.py) produces. All variants trace into ONE jitted
        program; changing the plan means rebuilding the step.
      bucket_densities: optional per-bucket density overrides, parallel to
        the compressor sequence (the autotuner's chosen densities).
      guard: optional ``resilience.guard.GuardConfig`` — adds the in-step
        anomaly guard: per-bucket nonfinite/absurd-value counts are
        psum-agreed across replicas, and on any trip the optimizer update
        AND every bucket's compressor residual/threshold update roll back
        (bit-identical training state; only step counters and volume
        accounting advance). Emits ``step_skipped``/``steps_skipped``/
        ``bucket_anomalies`` metrics. Requires ``state.health``
        (``init_dist_state(with_health=True)``).
      fault_plan: optional ``resilience.faults.FaultPlan`` — bakes the
        plan's deterministic NaN/Inf gradient injection into the traced
        step (wire-payload faults install separately via
        ``collectives.wire.install_wire_fault``). Chaos drills only.
      quality: optional ``obs.quality.QualityConfig`` — adds the in-jit
        signal-fidelity taps: per-bucket compression error vs the
        pre-selection dense gradient, residual norm/growth, realised
        density, threshold drift and winner-index churn, pushed into the
        device-side ring in ``state.quality`` every step (guard-skipped
        steps included, flagged). Purely read-only on the training
        computation — the trajectory is bit-identical taps-on vs
        taps-off — and host-sync-free: the ring is drained only when the
        trainer flushes it (docs/OBSERVABILITY.md "Signal fidelity").
        Requires ``state.quality`` (``init_dist_state(quality=...)``).

    Returns ``step(state: DistTrainState, batch, rng) -> (state, metrics)``.
    ``batch`` leaves are [num_workers * nsteps_update * mb, ...] and get
    sharded over the data axis. ``step.leafwise`` holds a bool a bucket:
    True where the bucket's collective is the dense all-reduce itself and
    neither ``fault_plan``, ``momentum_correction``, ``quality``, ``guard``
    nor ``profile_norm`` reads its flat vector, so that none is built and
    the bucket is reduced a leaf at a time.
    """
    from oktopk_tpu.ops.compaction import resolve_use_pallas
    cfg = resolve_use_pallas(cfg, mesh)
    nb = max(1, num_buckets)
    names = ([compressor] * nb if isinstance(compressor, str)
             else list(compressor))
    if len(names) != nb:
        raise ValueError(
            f"compressor plan has {len(names)} entries for {nb} buckets")
    if bucket_densities is not None and len(bucket_densities) != nb:
        raise ValueError(
            f"bucket_densities has {len(bucket_densities)} entries for "
            f"{nb} buckets")
    algos = [get_algorithm(nm, warmup=warmup) for nm in names]
    has_health = guard is not None or fault_plan is not None
    if has_health:
        from oktopk_tpu.resilience import faults as _faults  # noqa: F401
        from oktopk_tpu.resilience import guard as _guard_mod
    has_quality = quality is not None
    if has_quality:
        from oktopk_tpu.obs import quality as _quality_mod
    # the dense all-reduce is element-wise: the mean of a concatenation is
    # the concatenation of the means. Where it is the bucket's collective
    # (the registry hands it out unwrapped; a warm-up's lax.cond needs one
    # shape for both branches) and nothing below reads the flat vector,
    # the bucket is handed over as its leaves and no vector is built
    reads_flat = bool(profile_norm or momentum_correction or has_health
                      or has_quality)
    leafwise = tuple(fn is dense_allreduce and not reads_flat
                     for fn in algos)

    def shard_fn(state: DistTrainState, batch, rng):
        if has_health and state.health is None:
            raise ValueError(
                "guard/fault_plan need state.health: build the state with "
                "init_dist_state(with_health=True)")
        if has_quality and state.quality is None:
            raise ValueError(
                "quality taps need state.quality: build the state with "
                "init_dist_state(quality=...)")
        rng = jax.random.fold_in(rng, lax.axis_index(axis_name))

        # --- local grads, with optional microbatch accumulation ---
        # MODEL_COUNTERS: what the model says it did, where it says so
        counts_max = jnp.asarray([nm.endswith("_max")
                                  for nm in MODEL_COUNTERS])

        def micro(carry, mb):
            acc_grads, acc_loss, model_state, rng, counts = carry
            rng, sub = jax.random.split(rng)
            (loss, (model_state, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, model_state, mb, sub)
            acc_grads = jax.tree.map(jnp.add, acc_grads, grads)
            if isinstance(aux, dict) and "counters" in aux:
                new = aux["counters"].astype(jnp.int32)
                counts = jnp.where(counts_max, jnp.maximum(counts, new),
                                   counts + new)
            return (acc_grads, acc_loss + loss, model_state, rng,
                    counts), None

        zero_grads = jax.tree.map(jnp.zeros_like, state.params)
        zero_counts = jnp.zeros((len(MODEL_COUNTERS),), jnp.int32)
        with phase_scope("fwd_bwd"):
            if nsteps_update > 1:
                mb_batch = jax.tree.map(
                    lambda x: x.reshape((nsteps_update, -1) + x.shape[1:]),
                    batch)
                (grads, loss, model_state, rng, model_counts), _ = lax.scan(
                    micro, (zero_grads, 0.0, state.model_state, rng,
                            zero_counts), mb_batch)
                grads = jax.tree.map(lambda g: g / nsteps_update, grads)
                loss = loss / nsteps_update
            else:
                (grads, loss, model_state, rng, model_counts), _ = micro(
                    (zero_grads, 0.0, state.model_state, rng, zero_counts),
                    batch)

            if grad_clip is not None:
                gnorm = jnp.sqrt(sum(jnp.sum(g ** 2)
                                     for g in jax.tree.leaves(grads)))
                scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-12))
                grads = jax.tree.map(lambda g: g * scale, grads)

        # --- sparse allreduce of the gradient: one collective per
        # reverse-layer-order bucket. num_buckets == 1 degenerates to the
        # whole model as a single flat vector (the BERT variant's
        # "myallreduce" form, BERT/bert/allreducer.py:200); the outer state
        # layout stays a bare SparseState in that case for checkpoint
        # compatibility. ---
        buckets = bucket_partition(grads, num_buckets)  # static sizes
        sizes = bucket_sizes(grads, buckets)
        leaves, treedef = jax.tree.flatten(grads)
        assert sum(x.size for x in leaves) == cfg.n, (
            f"cfg.n={cfg.n} != flat grad size "
            f"{sum(x.size for x in leaves)}")
        single = num_buckets <= 1
        states_in = ([state.sparse_state] if single
                     else list(state.sparse_state))
        moms_in = (([state.local_momentum] if single
                    else list(state.local_momentum))
                   if momentum_correction else None)
        quals_in = (([state.quality] if single else list(state.quality))
                    if has_quality else None)
        results = [None] * len(leaves)
        sp_olds, sp_news, new_moms, bad_counts = [], [], [], []
        absmaxes, qual_taps, step_counters = [], [], []
        vol = lk = gk = wbytes = jnp.asarray(0.0, jnp.float32)
        eps_num = eps_den = jnp.asarray(0.0, jnp.float32)
        for bi, idxs in enumerate(buckets):
            # copy-free single-leaf bucket: reshape is a view under XLA,
            # while a 1-element concatenate still materialises a second
            # n-length buffer (and the matching slice-back below a third);
            # a leaf-wise bucket is the tuple of its leaves as they are
            if leafwise[bi]:
                flat = tuple(leaves[i] for i in idxs)
            elif len(idxs) == 1:
                flat = leaves[idxs[0]].reshape(-1)
            else:
                flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
            over = {}
            if not single:
                over["n"] = sizes[bi]
                over["bucket_index"] = bi
            if bucket_densities is not None:
                over["density"] = float(bucket_densities[bi])
            cfg_b = cfg.replace(**over) if over else cfg
            sp = jax.tree.map(lambda x: x[0], states_in[bi])
            if fault_plan is not None:
                # chaos drill: deterministic NaN/Inf poisoning of this
                # bucket's local gradient, indexed by the monotonic
                # attempted-step counter (a guard skip must not freeze a
                # one-step fault into a permanent one)
                flat = _faults.inject_grad_faults(
                    fault_plan, flat, state.health.step,
                    lax.axis_index(axis_name), bi)
            if momentum_correction:
                flat = momentum_correction * moms_in[bi][0] + flat
                new_moms.append(flat[None])
            # bucket container scope: the collective's own phase scopes
            # nest inside it, so trace names carry the bucket id even for
            # algorithms annotated without one
            with phase_scope(bucket=bi):
                reduced, sp_new = algos[bi](flat, sp, cfg_b, axis_name)
            if has_quality:
                # fidelity tap (obs/quality.py): reference is the dense
                # gradient the selection approximated — exactly what this
                # worker handed the compressor (faults and momentum fold
                # included) plus its residual, pmean'd. Measured here
                # (pre-guard, observed values); committed into the ring
                # after the guard agrees on the skip flag.
                qb = jax.tree.map(lambda x: x[0], quals_in[bi])
                dense_q = lax.pmean(flat + sp.residual, axis_name)
                qual_taps.append((qb, _quality_mod.measure_bucket(
                    reduced, dense_q, sp_new, qb.prev_sig,
                    qb.prev_res_norm)))
            if guard is not None:
                bad_counts.append(
                    _guard_mod.local_anomaly_count(flat, reduced, guard))
                # peak reduced magnitude: the guard-pressure signal the
                # density-backoff policy watches (how close delivered
                # gradients crowd cfg.abs_limit without tripping it)
                absmaxes.append(jnp.max(jnp.abs(reduced)))
            if leafwise[bi]:
                for i, r in zip(idxs, reduced):
                    results[i] = r
            elif len(idxs) == 1:
                results[idxs[0]] = reduced.reshape(leaves[idxs[0]].shape)
            else:
                off = 0
                for i in idxs:
                    sz = leaves[i].size
                    results[i] = reduced[off:off + sz] \
                        .reshape(leaves[i].shape)
                    off += sz
            sp_olds.append(sp)
            sp_news.append(sp_new)
            vol = vol + sp_new.last_volume
            wbytes = wbytes + sp_new.last_wire_bytes
            lk = lk + sp_new.last_local_count
            gk = gk + sp_new.last_global_count
            step_counters.append(sp_new.last_counters)
            if profile_norm:
                dense = lax.pmean(flat, axis_name)
                eps_num = eps_num + jnp.sum((dense - reduced) ** 2)
                eps_den = eps_den + jnp.sum(dense ** 2)
        grads = jax.tree.unflatten(treedef, results)
        if momentum_correction:
            new_momentum = new_moms[0] if single else tuple(new_moms)
        else:
            new_momentum = state.local_momentum
        grad_norm = jnp.sqrt(sum(jnp.sum(r ** 2) for r in results))
        # nonfinite reduced-gradient elements (the reference warns when
        # the gradient sparsity goes NaN, VGG/dl_trainer.py:608-609; a
        # count in the metrics makes the blow-up step identifiable)
        grad_nonfinite = sum(jnp.sum(~jnp.isfinite(r)) for r in results)
        eps = (jnp.sqrt(eps_num) / (jnp.sqrt(eps_den) + 1e-12)
               if profile_norm else None)

        # --- optimizer update (identical on every worker) ---
        with phase_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = jax.tree.map(jnp.add, state.params, updates)

        metrics = {
            "loss": lax.pmean(loss, axis_name),
            "grad_norm": grad_norm,
            "grad_nonfinite": grad_nonfinite,
            "comm_volume": vol,
            "wire_bytes": wbytes,
            "local_k": lk,
            "global_k": gk,
        }
        if eps is not None:
            metrics["eps_vs_dense"] = eps
        # what the step did, one i32 vector in collectives/state.COUNTERS'
        # order: the worst branch over the buckets, everything else summed,
        # then the largest over the workers (the step is as slow as its
        # slowest worker: one chip in the wide branch holds all of them),
        # the realised counts, and what the model counted
        per_bucket = jnp.stack(step_counters)        # [buckets, branches]
        is_branch = jnp.asarray([nm.endswith("_branch")
                                 for nm in BRANCH_COUNTERS])
        metrics["counters"] = jnp.concatenate([
            lax.pmax(jnp.where(is_branch, jnp.max(per_bucket, axis=0),
                               jnp.sum(per_bucket, axis=0)), axis_name),
            jnp.stack([lk, gk]).astype(jnp.int32),
            jnp.where(counts_max, lax.pmax(model_counts, axis_name),
                      lax.psum(model_counts, axis_name))])

        # --- in-step anomaly guard (resilience/guard.py): agree on a
        # global skip flag, then make the whole step a training no-op —
        # optimizer update discarded, compressor residual/threshold
        # updates rolled back bucket-by-bucket so error feedback is never
        # poisoned. Step counters and wire-volume accounting still
        # advance (the skipped step consumed its batch and its wire). ---
        health = state.health
        if guard is not None:
            flags, any_bad = _guard_mod.agree(bad_counts, axis_name)
            params = _guard_mod.guarded(any_bad, state.params, params)
            opt_state = _guard_mod.guarded(any_bad, state.opt_state,
                                           opt_state)
            model_state = _guard_mod.guarded(any_bad, state.model_state,
                                             model_state)
            if momentum_correction:
                new_momentum = _guard_mod.guarded(
                    any_bad, state.local_momentum, new_momentum)
            sp_news = [
                _guard_mod.guarded(
                    any_bad,
                    old.replace(step=new.step,
                                volume_elems=new.volume_elems,
                                last_volume=new.last_volume,
                                wire_bytes=new.wire_bytes,
                                last_wire_bytes=new.last_wire_bytes,
                                last_local_count=new.last_local_count,
                                last_global_count=new.last_global_count,
                                last_counters=new.last_counters),
                    new)
                for old, new in zip(sp_olds, sp_news)]
            health = _guard_mod.advance(health, any_bad, flags)
            metrics["step_skipped"] = any_bad.astype(jnp.int32)
            metrics["steps_skipped"] = health.steps_skipped
            metrics["bucket_anomalies"] = (flags > 0).astype(jnp.int32)
            # replicated (reduced is post-collective, identical on every
            # worker); NaN when the step carried nonfinites — consumers
            # treat the skip flag as authoritative there
            metrics["reduced_absmax"] = jnp.max(jnp.stack(absmaxes))
        elif has_health:
            # fault plan without a guard: the attempt counter still has
            # to advance or a one-step fault would re-inject forever
            health = _guard_mod.advance(
                health, jnp.asarray(False),
                jnp.zeros_like(health.bucket_trips))

        quality_out = state.quality
        if has_quality:
            # commit the taps AFTER the guard: the ring row always lands
            # (quality accounting advances on skips, exactly like the
            # wire accounting above) with the skip flag recorded, while
            # the step-over-step baselines freeze on skipped steps —
            # next step compares against the last COMMITTED state, which
            # is what the rollback restored
            skip = (any_bad if guard is not None
                    else jnp.asarray(False))
            new_quals = [
                jax.tree.map(
                    lambda x: x[None],
                    _quality_mod.commit(qb, sp_news[bi].step, scalars,
                                        skip))
                for bi, (qb, scalars) in enumerate(qual_taps)]
            quality_out = new_quals[0] if single else tuple(new_quals)

        new_sparse = [jax.tree.map(lambda x: x[None], s) for s in sp_news]
        sparse_out = new_sparse[0] if single else tuple(new_sparse)
        new_state = DistTrainState(
            params=params, model_state=model_state, opt_state=opt_state,
            sparse_state=sparse_out,
            local_momentum=new_momentum,
            health=health,
            quality=quality_out)
        return new_state, metrics

    state_specs = dist_state_specs(axis_name, bool(momentum_correction),
                                   has_health, has_quality)
    mapped = compat.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(state_specs, P(axis_name), P()),
        out_specs=(state_specs, P()),
        check_vma=False)
    step = jax.jit(mapped, donate_argnums=(0,))
    step.leafwise = leafwise
    return step
