"""Ok-Topk: the paper's two-phase O(6k) sparse allreduce, TPU-native.

Reference: the oktopk branch of ``AllReducer.run`` (VGG/allreducer.py:575-1098;
call-stack walkthrough in SURVEY.md §3.2). Phase (a) is a reduce-scatter-like
exchange into per-worker *load-balanced regions*; phase (b) allgathers each
region's globally-selected winners. Thresholds are predicted (multiplicative
adaptation) and only recomputed exactly every ``*_recompute_every`` steps;
regions are repartitioned from local top-k index density every
``repartition_every`` steps.

TPU-first mapping (SURVEY.md §5.8, §7.3):
- the throttled tagged Isend/Irecv rounds (reference :672-794) collapse into
  ONE ``lax.all_to_all`` over fixed-capacity [P, cap] buffers — the rotated
  dst/src schedule, the size Alltoall (:708) and the chunked overlap logic all
  vanish (XLA pipelines the collective with surrounding compute);
- ``torch.split`` by data-dependent boundaries (:667-670) becomes region-id
  masks + one packing scatter (ops/select.pack_by_region) — shapes stay
  static;
- the two ``Allgatherv`` calls (:819,1031) become ``lax.all_gather`` of
  fixed-capacity triples;
- the boundary-averaging ``MPI.Allreduce`` (:638) is a tiny ``psum``;
- iteration-dependent control flow (recompute vs predict) is ``lax.cond`` on
  the step counter carried in SparseState — both branches same shapes.

Communication volume (analytic, tracked in SparseState): phase (a) sends
~2k and receives ~2k (balanced regions), phase (b) sends ~2k/P and receives
~2k(P-1)/P — total < 6k scalars per worker per step, the paper's headline
(reference README.md:2).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from oktopk_tpu.collectives.state import SparseState, bump
from oktopk_tpu.comm import all_gather, all_to_all, axis_rank, psum
from oktopk_tpu.obs.anatomy import (
    SUB_FEEDBACK,
    SUB_FINALIZE,
    SUB_GLOBAL,
    SUB_REPARTITION,
    SUB_SWEEP,
    SUB_THRESHOLD,
    phase_scope,
)
from oktopk_tpu.comm.primitives import pvary_like
from oktopk_tpu.config import OkTopkConfig, scheduled_k
from oktopk_tpu.ops import (
    pack_by_region,
    scatter_sparse,
    select_by_threshold,
    select_mask,
)
from oktopk_tpu.ops.topk import k2threshold_method
from oktopk_tpu.ops.fused_select import (
    fused_pack_finalize,
    fused_select_stage,
)
from oktopk_tpu.ops.residual import add_residual
from oktopk_tpu.collectives.wire import (
    on_wire as _on_wire,
    pair_wire_bytes,
    residual_after_winners,
)


def _target_k(k, n: int, factor: float):
    """The controller setpoint ``factor * k`` as a selection count —
    python int for a static k (the "sort" threshold method needs it
    static), traced otherwise. Full-density operation (k == n) must stay
    exactly dense, so the sub-k setpoint applies only when genuinely
    sparse."""
    if isinstance(k, int):
        return k if k >= n else max(1, int(round(factor * k)))
    kk = jnp.maximum(1, jnp.round(factor * k)).astype(jnp.int32)
    return jnp.where(k >= n, k, kk)


def _newton_adapt(thresh, count, count_probe, k, cfg: OkTopkConfig,
                  band_hi=None, target=None):
    """Threshold feedback toward the [band_lo*k, band_hi*k] count band.

    The reference nudges +-1.2% per step (VGG/allreducer.py:696-699,
    :1054-1057), which cannot re-enter the band within a recompute window
    once drift or a bad prediction pushes counts far out; a fixed
    proportional gain is miscalibrated because the count-threshold slope
    depends on the (changing) tail shape. So: measure the slope with a
    second count at ``thresh * probe_ratio`` — it fuses into the same
    reduction pass over the data, zero extra communication beyond widening
    an existing psum — and take one Newton step on the log-log curve:

        slope = dlog(count)/dlog(t),   t *= (count/k)^(-1/slope)

    Inside the band the threshold is left alone (dead zone, as the
    reference); per-step correction is clamped to ``adapt_max_step``."""
    c = jnp.maximum(count, 1).astype(jnp.float32)
    cp = jnp.maximum(count_probe, 1).astype(jnp.float32)
    slope = (jnp.log(cp) - jnp.log(c)) / jnp.log(cfg.probe_ratio)
    exponent = jnp.clip(-1.0 / jnp.minimum(slope, -0.5),
                        cfg.newton_exp_lo, cfg.newton_exp_hi)
    # corrections aim at the setpoint (<= k); the dead zone stays defined
    # by the reference band around k, so in-band counts are never touched
    corr = (c / (k if target is None else target)) ** exponent
    corr = jnp.clip(corr, 1.0 / cfg.adapt_max_step, cfg.adapt_max_step)
    hi = cfg.band_hi if band_hi is None else band_hi
    in_band = (count >= cfg.band_lo * k) & (count <= hi * k)
    return jnp.where(in_band, thresh, thresh * corr.astype(thresh.dtype))


def _repartition(abs_acc, local_thresh, cfg: OkTopkConfig, axis_name: str):
    """Load-balanced region boundaries from local selection density.

    The reference takes equal-count quantiles of its own top-k indices and
    averages the boundaries across workers with an MPI.Allreduce
    (VGG/allreducer.py:626-654). Here: cumulative hit count -> searchsorted
    quantile cut points -> psum-mean -> monotonic int offsets. Invariant
    preserved: boundaries[0] == 0, boundaries[-1] == n (the reference asserts
    sum(region sizes) == n at :648).
    """
    P, n = cfg.num_workers, cfg.n
    mask = abs_acc >= local_thresh
    csum = jnp.cumsum(mask.astype(jnp.int32))
    total = csum[-1]
    targets = (jnp.arange(1, P) * total).astype(jnp.float32) / P
    interior = jnp.searchsorted(
        csum.astype(jnp.float32), targets, side="left").astype(jnp.float32)
    avg = psum(interior, axis_name) / P
    interior_i = jnp.clip(jnp.round(avg).astype(jnp.int32), 0, n)
    interior_i = jnp.sort(interior_i)
    out = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), interior_i,
        jnp.full((1,), n, jnp.int32)])
    # psum output is replication-invariant; the carried boundaries are
    # per-shard ("varying") under shard_map's VMA tracking — align them.
    return pvary_like(out, abs_acc)


def oktopk(grad: jnp.ndarray, state: SparseState, cfg: OkTopkConfig,
           axis_name: str = "data"):
    P, n = cfg.num_workers, cfg.n
    # With a density_schedule, k is a traced scalar of the step counter:
    # the threshold controller chases the scheduled target while every
    # fixed-capacity buffer stays sized by the max density (config.py).
    k = scheduled_k(cfg, state.step)
    rank = axis_rank(axis_name)
    up = bool(cfg.use_pallas)
    bkt = cfg.bucket_index   # anatomy scope names carry the bucket id
    # Fused selection front-end (ops/fused_select.py): ONE Pallas sweep
    # over (grad, residual) yields acc, the staging rows and the realised
    # and Newton-probe counts, replacing the separate add_residual / abs /
    # mask / count / probe / pack passes below. It runs whenever the Pallas
    # back end is on and the gradient is float32 (the kernels' dtype); the
    # portable path is the CPU implementation and the bit-parity oracle
    # (tests/test_fused_select.py).
    fuse = up and grad.dtype == jnp.float32
    if not fuse:
        with phase_scope("select", bkt, sub=SUB_SWEEP):
            acc = add_residual(grad, state.residual)
            abs_acc = jnp.abs(acc)

    def _abs_acc_branch():
        # fused steps carry no precomputed |acc| buffer; the one branch
        # that needs one (lt_exact, the exact recompute) recomputes it
        # inside its cond — bit-identical values, and the extra sweeps
        # price only the steps that take the branch
        return jnp.abs(add_residual(grad, state.residual)) if fuse \
            else abs_acc

    # The reference's warmup length is a multiple of the recompute cadence
    # (512 % 32 == 0, VGG/allreducer.py:573,577) so its first sparse step
    # always recomputes exactly; we make that explicit so any warmup length
    # is safe (predicted thresholds start at 0 and would select everything).
    first_sparse = state.step == cfg.warmup_steps
    recompute_local = (state.step % cfg.local_recompute_every == 0) | first_sparse
    recompute_global = (state.step % cfg.global_recompute_every == 0) | first_sparse

    # ---- local threshold: exact every local_recompute_every, else predicted
    # (reference VGG/allreducer.py:593 vs :696-699). "Exact" uses the
    # sort-free bisection by default (cfg.threshold_method).
    #
    # Drift tracking: under error feedback at low density the unselected
    # mass — and with it the selection threshold — grows every step; the
    # reference's fixed +-1.2% band nudges cannot follow it at cadence 32.
    # Each exact recompute therefore also measures the realised per-step
    # growth rate over the elapsed window, and predicted steps multiply
    # BOTH thresholds by that rate — "prediction instead of recomputation"
    # (VGG/allreducer.py:593) applied to the drift as well as the level.
    prev_lt = state.local_threshold
    tkl = _target_k(k, n, cfg.local_k_target)

    def lt_exact():
        # exact recompute lands the count at the local setpoint (<= k,
        # inside the reference band) rather than exactly k: phase-(a)
        # volume is 4*count*(P-1)/P, so the setpoint directly buys
        # budget margin at the same nominal density
        lt_new = k2threshold_method(_abs_acc_branch(), tkl,
                                    cfg.threshold_method,
                                    cfg.bisect_iters).astype(grad.dtype)
        # drift measured between consecutive *exact* thresholds (the
        # running predicted one is polluted by the controller's own
        # corrections), as a per-step rate over the elapsed window
        gap = max(1, cfg.local_recompute_every)
        base_lt = state.last_exact_lt
        ratio = jnp.where((lt_new > 0) & (base_lt > 0),
                          lt_new / jnp.maximum(base_lt, 1e-30), 1.0)
        per_step = jnp.clip(ratio ** (1.0 / gap),
                            cfg.drift_clip_lo, cfg.drift_clip_hi)
        # EMA over recompute windows damps oscillation; the first exact
        # recompute has no meaningful baseline -> keep drift
        mixed = ((1.0 - cfg.drift_ema) * state.drift
                 + cfg.drift_ema * per_step)
        drift_new = jnp.where(base_lt > 0, mixed, state.drift)
        return lt_new, drift_new.astype(grad.dtype), lt_new

    def lt_predicted():
        return prev_lt * state.drift, state.drift, state.last_exact_lt

    with phase_scope("select", bkt, sub=SUB_THRESHOLD):
        lt, drift, last_exact_lt = lax.cond(recompute_local, lt_exact,
                                            lt_predicted)

    # ---- phase (a): select, exchange to region owners, scatter-add reduce.
    # Region repartition every repartition_every steps (reference
    # :626-654); the fused kernel is region-blind (regions are assigned in
    # its cap-scale finalize), so on fused steps the boundaries can be
    # computed from the kernel's own acc output in between stage and
    # finalize — repartition's extra |acc| sweep prices only its cadence.
    repart = (state.step % cfg.repartition_every == 0) | first_sparse
    if fuse:
        with phase_scope("select", bkt, sub=SUB_SWEEP):
            st = fused_select_stage(grad, state.residual, lt,
                                    lt * cfg.probe_ratio)
            acc = st.acc
        with phase_scope("stage", bkt, sub=SUB_REPARTITION):
            boundaries = lax.cond(
                repart,
                lambda: _repartition(jnp.abs(acc), lt, cfg, axis_name),
                lambda: state.boundaries)
        with phase_scope("stage", bkt, sub=SUB_FINALIZE):
            s_vals, s_idx, s_counts, branch_a = fused_pack_finalize(
                st, boundaries, P, cfg.cap_pair)
        local_count = st.local_count
        local_probe = st.probe_count
        # only the bf16 wire's residual path reads the sent mask; it fuses
        # into the single consumer pass over acc at the bottom (and is
        # DCE'd entirely under the f32 wire). The kernel's own staging
        # mask clamps the threshold to min-normal f32 (ops/compaction.py
        # _prep) — identical whenever lt is normal, i.e. every step after
        # the first exact recompute.
        mask = jnp.abs(acc) >= lt
    else:
        with phase_scope("stage", bkt, sub=SUB_REPARTITION):
            boundaries = lax.cond(
                repart,
                lambda: _repartition(abs_acc, lt, cfg, axis_name),
                lambda: state.boundaries)
        with phase_scope("select", bkt, sub=SUB_SWEEP):
            mask = abs_acc >= lt
            local_count = jnp.sum(mask)
        with phase_scope("stage", bkt, sub=SUB_FINALIZE):
            s_vals, s_idx, s_counts, branch_a = pack_by_region(
                acc, mask, boundaries, P, cfg.cap_pair, thresh=lt,
                use_pallas=up, with_branch=True)
        # threshold feedback probe (fuses into the same pass over abs_acc)
        with phase_scope("select", bkt, sub=SUB_SWEEP):
            local_probe = jnp.sum(abs_acc >= lt * cfg.probe_ratio)
    with phase_scope("exchange", bkt):
        r_vals = all_to_all(_on_wire(s_vals, cfg, state.step), axis_name) \
            .astype(acc.dtype)                 # [P, cap_pair]
        r_idx = all_to_all(s_idx, axis_name)
    with phase_scope("combine", bkt):
        reduced = scatter_sparse(n, r_vals, r_idx)  # own region only

    # Wire volume: the capped buffers bound what is actually sent (elements
    # beyond cap stay in the residual) — unlike the reference, whose MPI
    # sends are unbounded when counts drift above band between recomputes.
    sent_count = jnp.sum(s_counts)
    recv_count = jnp.sum(r_idx < n)
    own_count = s_counts[rank]
    vol_a = 2.0 * (sent_count - own_count) + 2.0 * (recv_count - own_count)

    # ---- local threshold feedback for the next step
    with phase_scope("select", bkt, sub=SUB_FEEDBACK):
        lt_next = _newton_adapt(lt, local_count, local_probe, k, cfg,
                                target=tkl)

    # ---- phase (b): global winner selection + allgather.
    cap_g = cfg.cap_gather
    k_cand = min(cfg.cap_exact, n)

    def exact_branch():
        # Every global_recompute_every steps the reference gathers all
        # nonzeros of the reduced region and takes an exact global top-k
        # (VGG/allreducer.py:819-846) — unbounded on the wire. TPU form:
        # each region contributes its top cap_exact ~ 4k/P candidates
        # (load-balanced regions hold ~k/P global winners each — the
        # balance the repartition maintains is exactly what makes the
        # paper's volume O(k), not O(kP)) selected by a sort-free
        # per-region threshold; the k-th value of the gathered pool becomes
        # the new global threshold. No O(n log n) sort anywhere.
        with phase_scope("select", bkt, sub=SUB_GLOBAL):
            t_cand = k2threshold_method(jnp.abs(reduced), k_cand,
                                        cfg.threshold_method,
                                        cfg.bisect_iters)
            if up:
                # the kernel's min-normal clamp already excludes zeros
                vals, idx, cand_count, branch_b = select_by_threshold(
                    reduced, t_cand, k_cand, use_pallas=True,
                    with_branch=True)
            else:
                cand_mask = (jnp.abs(reduced) >= t_cand) & (reduced != 0.0)
                vals, idx, cand_count = select_mask(reduced, cand_mask,
                                                    k_cand)
                branch_b = jnp.zeros((2,), jnp.int32)
        with phase_scope("exchange", bkt):
            gv = all_gather(_on_wire(vals, cfg, state.step), axis_name) \
                .astype(acc.dtype)                     # [P, k_cand]
            gi = all_gather(idx, axis_name)
        # Python min when k is static (the "sort" method needs it so);
        # a scheduled k is traced, and the schedule guarantees "bisect"
        # (count-based, traced-k-capable)
        k_pool = (min(k, P * k_cand) if isinstance(k, int)
                  else jnp.minimum(k, P * k_cand))
        with phase_scope("select", bkt, sub=SUB_GLOBAL):
            gt = k2threshold_method(jnp.abs(gv).reshape(-1), k_pool,
                                    cfg.threshold_method,
                                    cfg.bisect_iters).astype(acc.dtype)
            keep = (jnp.abs(gv) >= gt) & (gi < n)
        # values pre-divided by P at cap scale: every gathered index is
        # unique (regions are disjoint and each worker's winners are
        # deduplicated), so scatter(gv / P) == scatter(gv) / P bit-for-bit
        # — and the old dense n-scale division pass disappears
        with phase_scope("combine", bkt):
            result = scatter_sparse(n, jnp.where(keep, gv, 0.0) / P,
                                    jnp.where(keep, gi, n))
        g_count = jnp.sum(keep)
        total_c = psum(cand_count, axis_name)
        vol = 2.0 * cand_count + 2.0 * (total_c - cand_count)
        return pvary_like((result, gt, g_count, vol, branch_b), acc)

    def predicted_branch():
        # Otherwise: threshold-select own region, fixed-capacity allgather,
        # rebuild, adapt the global threshold (reference :894,1031-1057).
        # The reference predicts the next global threshold by multiplicative
        # count feedback alone, which assumes a near-stationary gradient
        # distribution; here gt additionally rides the measured per-step
        # drift rate (see the local-threshold block above) at zero comm
        # cost.
        gt_use = state.global_threshold * drift
        with phase_scope("select", bkt, sub=SUB_GLOBAL):
            gvals, gidx, gcount, branch_b = select_by_threshold(
                reduced, gt_use, cap_g, use_pallas=up, with_branch=True)
        with phase_scope("exchange", bkt):
            gv = all_gather(_on_wire(gvals, cfg, state.step), axis_name) \
                .astype(acc.dtype)                     # [P, cap_g]
            gi = all_gather(gidx, axis_name)
        with phase_scope("combine", bkt):
            result = scatter_sparse(n, gv / P, gi)  # pre-divided
            # (see exact_branch)
        # Newton probe count rides the same psum as the realised count —
        # one 2-vector allreduce (the reference pays a full size-exchange
        # Allgather for less information, VGG/allreducer.py:807)
        probe_c = jnp.sum((jnp.abs(reduced) >= gt_use * cfg.probe_ratio)
                          & (reduced != 0.0))
        totals = psum(jnp.stack([gcount, probe_c]).astype(jnp.float32),
                      axis_name)
        total_g = totals[0].astype(jnp.int32)
        gt_next = _newton_adapt(gt_use, total_g, totals[1].astype(jnp.int32),
                                k, cfg, band_hi=cfg.band_hi_global,
                                target=_target_k(k, n, cfg.global_k_target))
        vol = 2.0 * gcount + 2.0 * (total_g - gcount)
        return pvary_like((result, gt_next, total_g, vol, branch_b), acc)

    result, gt_next, g_count, vol_b, branch_b = lax.cond(
        recompute_global, exact_branch, predicted_branch)

    # ---- residual: zero only at indices that made the global result
    # (reference VGG/allreducer.py:1051-1052); under the bf16 wire the
    # rounding errors stay in the residual (collectives/wire.py).
    # With the phase-(b) values pre-divided at cap scale, the old
    # result/P + winner_mask + residual trio collapses into ONE consumer
    # pass over (result, acc, reduced) — the last n-scale sweep of the
    # step (docs/PERF.md "selection hot path").
    with phase_scope("combine", bkt):
        winner_mask = result != 0.0
        residual = residual_after_winners(acc, winner_mask, mask, reduced,
                                          cfg)

    # Both phases move (index, value) pairs and count volume as scalars
    # (2 per pair), so the realised wire bytes follow from the same
    # counts — the measured side of the paper's 6k-scalar budget.
    wb = pair_wire_bytes(0.5 * (vol_a + vol_b), cfg)

    return result, bump(state, volume=vol_a + vol_b, wire_bytes=wb,
                        residual=residual,
                        local_threshold=lt_next, global_threshold=gt_next,
                        boundaries=boundaries, drift=drift,
                        last_exact_lt=last_exact_lt,
                        local_count=local_count, global_count=g_count,
                        # what this step did, in BRANCH_COUNTERS' order
                        counters=(branch_a[0], branch_a[1], branch_b[0],
                                  branch_b[1], recompute_local,
                                  recompute_global, repart))
