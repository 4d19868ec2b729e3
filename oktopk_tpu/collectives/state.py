"""Per-bucket algorithm state.

The reference's AllReducer holds this state as instance dicts keyed by bucket
name: allreduce counter, local/global thresholds, region boundaries/offsets
(VGG/allreducer.py:240-244). Here it is an explicit pytree threaded through
the jitted step — which makes it checkpointable (the reference never saves
residuals or thresholds; resume silently resets error feedback, SURVEY.md
§5.4) and makes every per-step quantity observable, including the analytic
communication volume counters that reproduce the paper's <6k claim without
reading XLA internals (SURVEY.md §7.3.7).
"""

from __future__ import annotations

import flax.struct
import jax.numpy as jnp

from oktopk_tpu.config import OkTopkConfig

# The order of a step's ``metrics["counters"]`` (i32[len(COUNTERS)],
# replicated like every other metric; what the step did, not how long it
# took). The first ``len(BRANCH_COUNTERS)`` entries are written per bucket
# by the collective into ``SparseState.last_counters``;
# ``optim/distributed.py`` takes the worst branch and the sum of everything
# else over the buckets, then the largest over the workers (the step is as
# slow as its slowest worker: one chip in the wide branch holds all of
# them), and appends the realised counts of the ``local_k``/``global_k``
# metrics, then ``MODEL_COUNTERS``. A branch entry holds the place of its
# name in ``BRANCHES`` (ops/compaction.py); a dense step leaves everything
# it does not do at 0.
BRANCHES = ("fast", "repair", "wide")
BRANCH_COUNTERS = (
    "stage_branch",            # staging (phase a) overflow dispatch
    "stage_overflow_blocks",   # blocks over the fast staging width there
    "select_branch",           # phase-(b) threshold select's dispatch
    "select_overflow_blocks",  # blocks that mattered there
    "recompute_local",         # local threshold recomputed exactly
    "recompute_global",        # phase (b) took the exact branch
    "repartition",             # region boundaries recomputed
)
# What the model did, from the loss function's ``aux["counters"]`` (zeros
# for a model that reports none): a name that ends in ``_max`` is the
# largest over micro-steps and workers, every other the sum.
MODEL_COUNTERS = (
    "expert_rows",      # token-expert pairs computed at held experts
    "expert_rows_max",  # ... at the busiest held expert of any layer
    # 1,000 x the tokens' mean exit step, sum_t t p_t (a looped model:
    # where the exit gate puts its mass, between 1,000 and 1,000 R)
    "exit_step_milli_max",
)
COUNTERS = BRANCH_COUNTERS + ("local_k", "global_k") + MODEL_COUNTERS


@flax.struct.dataclass
class SparseState:
    step: jnp.ndarray                 # i32 — allreduce counter
    local_threshold: jnp.ndarray      # f32 — predicted local sel. threshold
    global_threshold: jnp.ndarray     # f32 — predicted global sel. threshold
    # Estimated per-step multiplicative growth of the selection threshold,
    # measured between consecutive exact local recomputes (collectives/
    # oktopk.py). Under error feedback at low density the unselected mass
    # grows every step, so thresholds must ride that drift between
    # recomputes — the reference's fixed +-1.2% band nudges
    # (VGG/allreducer.py:696-699) cannot track it.
    drift: jnp.ndarray                # f32 — ~1.0
    # The threshold measured at the last *exact* local recompute — the
    # clean baseline for the next drift measurement (the running predicted
    # threshold is polluted by the controller's own corrections).
    last_exact_lt: jnp.ndarray        # f32

    boundaries: jnp.ndarray           # i32[P+1] — region offsets, [0..n]
    residual: jnp.ndarray             # f32[n] — error-feedback buffer
    # Analytic comm-volume accounting (elements sent by this worker):
    volume_elems: jnp.ndarray         # f32 — cumulative over all steps
    last_volume: jnp.ndarray          # f32 — last step only
    # Wire-level byte accounting (obs/volume.py): realised payload bytes
    # crossing the collectives for this worker, wire-dtype-aware (bf16
    # pairs are 6 bytes, f32 pairs 8, dense psum values 4). Unlike
    # volume_elems — scalars in the paper's counting — these are the
    # bytes the conformance checker holds against each algorithm's
    # analytic budget. Threaded as traced values so lax.cond branches
    # (dense fallbacks, exact recomputes) account what actually ran.
    wire_bytes: jnp.ndarray           # f32 — cumulative over all steps
    last_wire_bytes: jnp.ndarray      # f32 — last step only
    # Per-level wire accounting (collectives/hierarchical.py): bytes on
    # the fast intra-pod edge vs the scarce inter-pod edge, so the DCN
    # link is priced separately (obs/volume.py hierarchical budgets).
    # Flat single-level algorithms leave all four at zero;
    # wire_bytes == wire_bytes_intra + wire_bytes_inter when hierarchical.
    wire_bytes_intra: jnp.ndarray      # f32 — cumulative, intra level
    last_wire_bytes_intra: jnp.ndarray  # f32 — last step only
    wire_bytes_inter: jnp.ndarray      # f32 — cumulative, inter level
    last_wire_bytes_inter: jnp.ndarray  # f32 — last step only
    # realised selected counts (observability; reference logs these under
    # settings.PROFILING, VGG/allreducer.py:702-703)
    last_local_count: jnp.ndarray     # i32
    last_global_count: jnp.ndarray    # i32
    # what the last step did: i32[len(BRANCH_COUNTERS)], in that order
    last_counters: jnp.ndarray


def init_state(cfg: OkTopkConfig, dtype=jnp.float32) -> SparseState:
    """Fresh state: equal static region split (the reference starts from an
    even split too, VGG/allreducer.py:240-244), zero thresholds (first step
    always takes the exact-recompute branch since step % every == 0)."""
    P, n = cfg.num_workers, cfg.n
    base, rem = divmod(n, P)
    sizes = jnp.asarray([base + (1 if i < rem else 0) for i in range(P)],
                        jnp.int32)
    boundaries = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    return SparseState(
        step=jnp.asarray(0, jnp.int32),
        local_threshold=jnp.asarray(0.0, dtype),
        global_threshold=jnp.asarray(0.0, dtype),
        drift=jnp.asarray(1.0, dtype),
        last_exact_lt=jnp.asarray(0.0, dtype),
        boundaries=boundaries,
        residual=jnp.zeros((n,), dtype),
        volume_elems=jnp.asarray(0.0, jnp.float32),
        last_volume=jnp.asarray(0.0, jnp.float32),
        wire_bytes=jnp.asarray(0.0, jnp.float32),
        last_wire_bytes=jnp.asarray(0.0, jnp.float32),
        wire_bytes_intra=jnp.asarray(0.0, jnp.float32),
        last_wire_bytes_intra=jnp.asarray(0.0, jnp.float32),
        wire_bytes_inter=jnp.asarray(0.0, jnp.float32),
        last_wire_bytes_inter=jnp.asarray(0.0, jnp.float32),
        last_local_count=jnp.asarray(0, jnp.int32),
        last_global_count=jnp.asarray(0, jnp.int32),
        last_counters=jnp.zeros((len(BRANCH_COUNTERS),), jnp.int32),
    )


def bump(state: SparseState, *, volume, wire_bytes=None, local_count=None,
         global_count=None, counters=None, **updates) -> SparseState:
    """Advance the step counter and record per-step accounting.

    ``wire_bytes`` is the step's realised wire-level byte count (None —
    external callers predating the counter — records 0 for the step).
    ``counters`` are the step's ``BRANCH_COUNTERS`` (None: a step that took
    none of those branches records zeros)."""
    vol = jnp.asarray(volume, jnp.float32)
    wb = jnp.asarray(0.0 if wire_bytes is None else wire_bytes, jnp.float32)
    kw = dict(
        step=state.step + 1,
        volume_elems=state.volume_elems + vol,
        last_volume=vol,
        wire_bytes=state.wire_bytes + wb,
        last_wire_bytes=wb,
    )
    # "* 0 +": the new vector varies over the mesh axes the carried one
    # does, whichever branch of a lax.cond writes it
    kw["last_counters"] = state.last_counters * 0
    if counters is not None:
        kw["last_counters"] += jnp.stack(
            [jnp.asarray(c, jnp.int32) for c in counters])
    if local_count is not None:
        kw["last_local_count"] = jnp.asarray(local_count, jnp.int32)
    if global_count is not None:
        kw["last_global_count"] = jnp.asarray(global_count, jnp.int32)
    kw.update(updates)
    return state.replace(**kw)
