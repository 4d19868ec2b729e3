"""Typed configuration tree.

The reference scatters its real tuning surface across three layers (shell conf
files, argparse, and magic constants in code — see e.g. THRESHOLD=640MiB at
reference VGG/allreducer.py:27, recompute intervals at VGG/allreducer.py:577-579
vs BERT/bert/allreducer.py:359-361, threshold scales at VGG/allreducer.py:209-211
vs BERT/bert/allreducer.py:188-190, dense warmup at VGG/allreducer.py:573).
Here every such constant is a field on one frozen dataclass so it is visible,
testable, and hashable (usable as a static arg under jit).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class OkTopkConfig:
    """Static configuration for the sparse allreduce algorithms.

    All fields are Python scalars so the config is hashable and can be closed
    over by jitted functions; anything that changes per-step lives in
    ``collectives.state.SparseState`` instead.
    """

    # Problem geometry (static under XLA: shapes must be known at trace time).
    n: int = 0                 # flattened gradient length
    num_workers: int = 1       # data-parallel world size (mesh axis length)
    density: float = 0.02      # target k = ceil(density * n); reference VGG run uses 0.02

    # Dynamic density schedule (reference get_current_density,
    # VGG/allreducer.py:264-268: per-epoch density lists, shipped
    # tuned-off). Sorted (start_step, density) pairs; the active density
    # is the last pair whose start_step <= state.step. TPU-first reading:
    # shapes must be static under jit, so the schedule changes the target
    # k the threshold controller chases (a traced scalar from the step
    # counter), while every fixed-capacity buffer stays sized by the MAX
    # density = ``density`` (validated below). Requires the sort-free
    # "bisect" threshold (count-based, traced-k-capable); ``lax.top_k``
    # needs a static k. oktopk only — the topkA family's exact local
    # top-k is itself a static-k sort.
    density_schedule: Optional[Tuple[Tuple[int, float], ...]] = None

    # Cadences (reference VGG/allreducer.py:577-579; BERT uses 128/128/64).
    local_recompute_every: int = 32    # exact local top-k threshold recompute
    global_recompute_every: int = 32   # exact global top-k threshold recompute
    repartition_every: int = 64        # load-balanced region repartition

    # Dense warmup (reference VGG/allreducer.py:573 = 512; LSTM 128; BERT 0).
    warmup_steps: int = 512

    # Multiplicative threshold adaptation for the baseline algorithms
    # (reference VGG/allreducer.py:209-211 uses 1.012/1.008;
    # BERT/bert/allreducer.py:188-190 uses 1.025/1.036).
    local_adapt_scale: float = 1.012
    global_adapt_scale: float = 1.008

    # Ok-Topk threshold controller (collectives/oktopk.py::_newton_adapt):
    # one Newton step on the measured log-count/log-threshold slope,
    # sampled with a second count at thresh*probe_ratio (fused into the
    # same data pass). Replaces the reference's fixed +-1.2% nudge, which
    # cannot re-enter the band within a recompute window under threshold
    # drift. newton_exp_* bound the step exponent (-1/slope); per-step
    # correction is clamped to adapt_max_step.
    # Half Newton steps + a 1.5x/step clamp: underdamped full steps
    # resonate with real training dynamics (gradient scale itself moves
    # with the updates the collective delivers).
    probe_ratio: float = 1.25
    newton_exp_lo: float = 0.03
    newton_exp_hi: float = 0.5
    adapt_max_step: float = 1.5
    # Per-step threshold drift estimate (SparseState.drift): clip range for
    # the measured rate and the EMA mixing factor across recompute windows.
    drift_clip_lo: float = 0.5
    drift_clip_hi: float = 2.0
    # 1.0 = adopt each window's measured rate outright; the damped Newton
    # controller absorbs measurement noise, and a lagging drift estimate
    # costs more than a noisy one (it decays into systematic under/over-
    # selection for the whole next window).
    drift_ema: float = 1.0

    # Control band for the per-step selected count, as multiples of k
    # (reference grows/shrinks the threshold toward [2k/3, 5k/4],
    # VGG/allreducer.py:696-699).
    band_lo: float = 2.0 / 3.0
    band_hi: float = 5.0 / 4.0
    # Global-count band ceiling. The volume identity is
    #   vol ~ 4k(P-1)/P + 2*E[global_count]
    # so with E at the reference's 5k/4 ceiling the total sits exactly ON
    # the 6k budget; capping the global dead zone at 1.0*k targets ~5.7k
    # with margin. Local selection keeps the full reference band.
    band_hi_global: float = 1.0
    # Controller setpoints, as factors of k. 1.0 chases exactly k (the
    # reference behaviour); slightly below 1 operates realised counts in
    # the lower half of the reference band [2k/3, 5k/4] — still the same
    # nominal density d, but with volume margin under the 6k budget
    # instead of sitting 5% from the line (VERDICT r4). local applies to
    # the exact local-threshold recompute and local feedback; global to
    # the predicted-phase global feedback (exact global recomputes still
    # deliver exactly k winners).
    local_k_target: float = 0.9
    global_k_target: float = 0.85

    # Fixed-capacity factors. XLA has no ragged collectives (no Allgatherv /
    # size Alltoall), so every variable-length exchange in the reference
    # becomes a fixed-capacity (values, indices, count) buffer here.
    # Capacities are multiples of the expected count; the reference's own
    # threshold feedback keeps realised counts inside the band above, so a
    # modest headroom factor suffices (SURVEY.md §7.3.1).
    cap_pair_factor: float = 2.0    # per (src -> dst-region) buffer, of k/P
    cap_gather_factor: float = 2.5  # per-region allgather buffer, of k/P
    # Exact-recompute candidate pool per region, of k/P. Load-balanced
    # regions hold ~k/P of the global top-k each (that balance is what makes
    # the paper's volume O(k) instead of O(kP)); 4x headroom covers drift
    # between repartitions. The reference instead gathers ALL nonzeros of
    # the reduced region (VGG/allreducer.py:819) — unbounded on the wire.
    cap_exact_factor: float = 4.0

    # Gaussian threshold estimation (reference compression.py:238-259 refines a
    # scipy ppf estimate in a bounded loop; we binary-search, see ops/gaussian).
    gaussian_refine_iters: int = 16
    sigma_scale: float = 2.5        # reference VGG/vgg16_oktopk.sh:28

    # Exact-threshold implementation for the periodic recomputes:
    # "bisect" (default, TPU-first): sort-free count-bisection — O(iters*n)
    #   VPU compares instead of the O(n log n) sort the reference pays for
    #   torch.topk (SURVEY.md §7.3.5); ties resolved within float tolerance.
    # "sort": exact lax.top_k (reference-faithful; fine on CPU/small n).
    threshold_method: str = "bisect"
    bisect_iters: int = 30

    # topkSA density-adaptive fallback: switch to dense allgather when the
    # reduced result is >= this dense (reference VGG/allreducer.py:1318-1351).
    sa_dense_fallback_ratio: float = 2.0 / 3.0

    # Selection compaction backend: True = Pallas stream-compaction kernels
    # (ops/compaction.py and, for oktopk's float32 gradients, the fused
    # front-end ops/fused_select.py; TPU only), False = portable
    # cumsum+scatter,
    # None = resolve from the mesh backend at step-build time
    # (collectives/api.py, optim/distributed.py).
    use_pallas: Optional[bool] = None

    # Which reverse-layer-order gradient bucket this config instance
    # serves. Set by the multi-bucket step builder (optim/distributed.py)
    # so trace-time seams that only see the config — e.g. the wire
    # fault-injection hook (collectives/wire.py, resilience/faults.py) —
    # can target a single bucket. Purely informational for the
    # algorithms themselves.
    bucket_index: int = 0

    # Wire dtype for sparse message VALUES (indices stay int32). "bfloat16"
    # halves the value bytes of every exchange — the TPU-native analogue of
    # the reference's custom float16 MPI datatype + sum op
    # (VGG/allreducer.py:20-25) — with the rounding error folded back into
    # the error-feedback residual (collectives/oktopk.py), so the mass is
    # delivered later rather than lost. "float32" = uncompressed.
    wire_dtype: str = "bfloat16"

    @property
    def k(self) -> int:
        """Target number of selected elements (k = density * n). With a
        density_schedule this is the MAX over the schedule (capacity
        sizing); the per-step target is :func:`scheduled_k`."""
        return max(1, int(self.density * self.n))

    @property
    def k_region(self) -> int:
        """Expected per-region winner count (k / P)."""
        return max(1, self.k // max(1, self.num_workers))

    @property
    def cap_pair(self) -> int:
        """Capacity of each (worker -> region) exchange buffer."""
        cap = int(self.cap_pair_factor * self.k / max(1, self.num_workers)) + 8
        return min(self.n, cap)

    @property
    def cap_gather(self) -> int:
        """Capacity of each per-region allgather buffer (phase b)."""
        cap = int(self.cap_gather_factor * self.k / max(1, self.num_workers)) + 8
        return min(self.n, cap)

    @property
    def cap_exact(self) -> int:
        """Per-region candidate pool for the exact global recompute."""
        cap = int(self.cap_exact_factor * self.k / max(1, self.num_workers)) + 8
        return min(self.n, cap)

    @property
    def cap_local(self) -> int:
        """Capacity for whole-vector local selections (topkAopt / gaussiank)."""
        return min(self.n, int(self.cap_gather_factor * self.k) + 8)

    def __post_init__(self):
        if self.wire_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"wire_dtype must be 'float32' or 'bfloat16', "
                f"got {self.wire_dtype!r}")
        if self.density_schedule:
            starts = [s for s, _ in self.density_schedule]
            if starts != sorted(starts):
                raise ValueError(
                    f"density_schedule starts must be ascending: {starts}")
            if starts[0] != 0:
                raise ValueError(
                    f"density_schedule must start at step 0 (got "
                    f"{starts[0]}): every step needs an active pair — "
                    "add an explicit (0, density) entry for the early "
                    "phase")
            worst = max(d for _, d in self.density_schedule)
            if worst > self.density:
                raise ValueError(
                    f"density_schedule peaks at {worst} > density "
                    f"{self.density}; capacities are sized by `density`, "
                    "set it to the schedule's max")
            if self.threshold_method != "bisect":
                raise ValueError(
                    "density_schedule needs threshold_method='bisect' (a "
                    "traced target k; lax.top_k wants it static)")
        if self.threshold_method not in ("sort", "bisect"):
            raise ValueError(
                f"threshold_method must be 'sort' or 'bisect', "
                f"got {self.threshold_method!r}")
        for name in ("local_k_target", "global_k_target"):
            f = getattr(self, name)
            # below band_lo the setpoint fights its own dead zone (every
            # correction lands out-of-band low and is immediately pushed
            # back); above 1 it would overshoot the nominal density
            if not (self.band_lo <= f <= 1.0):
                raise ValueError(
                    f"{name}={f} must lie in [band_lo={self.band_lo:.3f}"
                    ", 1.0]")

    @property
    def wire_value_bytes(self) -> int:
        """Bytes per transmitted value scalar (indices are 4-byte int32)."""
        return 2 if self.wire_dtype == "bfloat16" else 4

    @property
    def wire_pair_bytes(self) -> int:
        """Bytes per transmitted (index, value) pair."""
        return 4 + self.wire_value_bytes

    def replace(self, **kw) -> "OkTopkConfig":
        return dataclasses.replace(self, **kw)


def scheduled_k(cfg: OkTopkConfig, step):
    """Per-step target k under ``cfg.density_schedule`` (a traced int32
    scalar of ``step``), or the static ``cfg.k`` without one.

    The reference looks its density up per epoch (get_current_density,
    VGG/allreducer.py:264-268) and re-sizes its MPI buffers implicitly;
    here the lookup is a tiny gather the step program traces once, and
    buffers never re-size (see the density_schedule field note)."""
    import jax.numpy as jnp

    if not cfg.density_schedule:
        return cfg.k
    starts = jnp.asarray([s for s, _ in cfg.density_schedule], jnp.int32)
    ks = jnp.asarray([max(1, int(d * cfg.n))
                      for _, d in cfg.density_schedule], jnp.int32)
    i = jnp.maximum(jnp.sum(step >= starts) - 1, 0)
    return ks[i]


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Mesh geometry. The reference's world is a flat MPI communicator
    (MPI.COMM_WORLD); ours is a named-axis device mesh. ``data`` is the
    data-parallel axis (maps to the reference's rank space); ``model`` /
    ``pipe`` / ``seq`` are TPU-side extensions."""

    data_axis: str = "data"
    model_axis: str = "model"
    pipe_axis: str = "pipe"
    seq_axis: str = "seq"
    mesh_shape: Tuple[int, ...] = (1,)
    axis_names: Tuple[str, ...] = ("data",)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Trainer configuration (reference main_trainer.py argparse surface,
    VGG/main_trainer.py:144-159 + exp_configs/*.conf)."""

    dnn: str = "vgg16"
    dataset: str = "cifar10"
    batch_size: int = 16
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 5e-4
    nesterov: bool = False
    max_epochs: int = 161
    nsteps_update: int = 1          # local gradient accumulation steps
    compressor: str = "oktopk"
    density: float = 0.02
    sigma_scale: float = 2.5
    seed: int = 0
    num_workers: int = 1
    # LSTM-only gradient clipping (reference LSTM/main_trainer.py:94-99).
    grad_clip: Optional[float] = None
    # DGC-style momentum correction: fold momentum into the local gradient
    # stream before compression (reference VGG/distributed_optimizer.py:56,
    # 81-88); the base optimizer then runs momentum-free.
    momentum_correction: bool = False
    # BERT-style warmup-linear schedule knobs (transformers/optimization.py).
    warmup_proportion: float = 0.01
    total_steps: int = 0
    # Mixed precision: computation dtype for the model's matmuls/convs
    # ("bfloat16" doubles MXU throughput; master params, grads, the sparse
    # collective and the optimizer all stay float32). This replaces the
    # reference's NVIDIA-apex amp path (BERT/bert/main_bert.py:15,1009-1023,
    # SURVEY.md §2.4).
    compute_dtype: str = "float32"
    # Comm/backward overlap: number of reverse-layer-order gradient buckets,
    # each with its own sparse collective + SparseState (reference <=640 MiB
    # bucketing, VGG/allreducer.py:27,272-330). 1 = whole-model flat.
    num_buckets: int = 1

    # ---- per-bucket algorithm/density autotuning (autotune/) ----------
    # When True the trainer runs calibrate -> trial -> policy before the
    # first step (and again on the retune cadence) and builds each
    # bucket's collective from the resulting plan; ``compressor`` becomes
    # the fallback for buckets the tuner has not planned yet.
    autotune: bool = False
    # Candidate algorithms (registry names). Sparse ones are crossed with
    # ``autotune_densities``; "dense" is the single density-1.0 point.
    autotune_candidates: Tuple[str, ...] = ("dense", "oktopk")
    # Density grid for sparse candidates; () = just ``density``.
    autotune_densities: Tuple[float, ...] = ()
    # Timed steps per candidate per bucket in the trial phase.
    autotune_trial_steps: int = 3
    # Steps between re-tunes; 0 = tune once before the first step.
    autotune_retune_every: int = 0
    # A challenger must beat the incumbent's fresh measurement by this
    # fraction to flip a bucket's plan (anti-thrash dead zone: a flip
    # rebuilds + recompiles the jitted train step).
    autotune_hysteresis: float = 0.15
    # Trial only the top-N candidates by cost-model prior (0 = all).
    autotune_max_trials: int = 0
    # JSONL decision-journal path; None keeps the journal in memory.
    autotune_journal: Optional[str] = None

    # ---- numeric-health guard + escalation (resilience/) --------------
    # When True the distributed step carries the in-step anomaly guard:
    # nonfinite local gradients or nonfinite/absurd post-collective
    # values trip a psum-agreed skip — the optimizer update AND the
    # compressor residual/threshold updates roll back for that step (no
    # error-feedback poisoning) — and the trainer runs the host-side
    # supervisor (strike counters -> per-bucket dense fallback ->
    # checkpoint restore on divergence).
    resilience: bool = False
    # Reduced-gradient magnitude ceiling: finite-but-absurd values (wire
    # bit-flips land near 1e38) count as anomalies above it.
    resilience_abs_limit: float = 1e18
    # Guard trips on a bucket before the supervisor flips it to dense.
    resilience_strikes: int = 3
    # Consecutive skipped steps before a restore from the last good
    # checkpoint is attempted.
    resilience_divergence_limit: int = 8
    # Steps the supervisor waits after an escalation before escalating
    # again (retry/backoff: one fault burst must not cascade).
    resilience_cooldown: int = 4
    # Supervisor poll cadence in steps. Each check fetches the guard
    # metrics to host (a device sync); 1 = react within a step.
    resilience_check_every: int = 1
    # JSONL health-journal path; None keeps the journal in memory.
    resilience_journal: Optional[str] = None

    # ---- closed-loop policies (resilience/feedback.py, density.py) ----
    # Fault→autotune feedback: when True (and obs is on) the trainer
    # watches the bus for sustained regression/guard_trip streams and
    # forces an autotune re-calibrate + re-tune when the vote passes —
    # a degraded fabric re-tunes the plan instead of degrading forever.
    resilience_feedback: bool = False
    # Sliding evidence window (steps) and the votes needed inside it.
    resilience_feedback_window: int = 32
    resilience_feedback_signals: int = 3
    # Steps to back off after a forced re-tune (re-tuning recompiles).
    resilience_feedback_cooldown: int = 64
    # Guard-aware density backoff: when True (with resilience) the
    # effective selection density hysteretically backs off after
    # repeated near-abs_limit / guard-skip steps and re-advances after
    # a clean streak (resilience/density.py).
    resilience_density_backoff: bool = False
    # "Near" band: reduced_absmax > near_ratio * abs_limit is pressure.
    resilience_near_ratio: float = 0.1
    # Consecutive pressured steps before backing off one level.
    resilience_backoff_steps: int = 3
    # Density multiplier per level, and the level bound.
    resilience_backoff_factor: float = 0.5
    resilience_backoff_max_level: int = 3
    # Consecutive clean steps before re-advancing one level.
    resilience_clean_streak: int = 8

    # ---- unified observability (obs/) ---------------------------------
    # When True the trainer runs an event bus + run journal: per-step
    # metrics, autotune decisions, guard trips, fallbacks, checkpoints,
    # trace captures and end-of-run volume reports all land in ONE
    # JSONL file behind one environment header (obs/journal.py).
    obs: bool = False
    # Run-journal path; None keeps the journal in memory only.
    obs_journal: Optional[str] = None
    # Arm a bounded jax.profiler trace window on guard_trip/fallback
    # events (obs/tracing.py AnomalyTracer).
    obs_trace_on_anomaly: bool = False
    # Steps per anomaly-triggered trace window.
    obs_trace_steps: int = 3
    # Directory for anomaly trace captures; None derives from the
    # journal path (or a temp dir when the journal is in-memory).
    obs_trace_dir: Optional[str] = None
    # Max anomaly windows per run (a flapping guard must not fill disk).
    obs_max_traces: int = 3
    # BENCH_r*.json parsed key to build the step-time regression
    # baseline from (obs/regress.py); None disables regression checks.
    obs_regress_key: Optional[str] = None
    # Step time above tolerance x baseline journals a regression event.
    obs_regress_tolerance: float = 1.5
    # Per-phase duration limits in milliseconds ({"exchange": 50.0, ...});
    # a host-phase summary entry above its limit journals a regression
    # event with key="phase:<name>" (obs/regress.py observe_phases).
    obs_phase_limits: Optional[Dict[str, float]] = None
    # ---- signal-fidelity telemetry (obs/quality.py) -------------------
    # When True (with obs) the jitted step computes per-bucket fidelity
    # scalars — compression error vs the pre-selection dense gradient,
    # residual norm/growth, realised density, threshold drift, winner
    # churn — into a device-side ring (obs/metrics_buffer.py) flushed
    # to `quality` journal events; obs/rollup.py aggregates them with
    # breach detection feeding the closed-loop seams.
    obs_quality: bool = False
    # Flush cadence in steps (= ring capacity). Steady state pays NO
    # per-step host sync; each flush is one device_get.
    obs_quality_every: int = 32
    # Churn-signature bins (power of two; obs/quality.py).
    obs_quality_sig_bins: int = 512
    # Breach thresholds (obs/rollup.py): window-mean residual growth
    # ratio above this flags residual_growth ...
    obs_quality_growth_limit: float = 1.5
    # ... realised density below this fraction of the bucket target
    # flags density_collapse ...
    obs_quality_collapse_ratio: float = 0.25
    # ... mean winner churn above this flags churn_spike ...
    obs_quality_churn_limit: float = 0.9
    # ... and mean compression error above this flags comp_err.
    obs_quality_comp_err_limit: float = 1.0

    def experiment_slug(self) -> str:
        """Reference experiment naming convention
        (VGG/main_trainer.py:163-166)."""
        mode = "comp" if self.compressor != "dense" else "dense"
        return (
            f"allreduce-{mode}-{self.compressor}-gwarmup-dc1-model-mgwfbp"
            f"-{self.dnn}-n{self.num_workers}-bs{self.batch_size}"
            f"-lr{self.lr:.4f}-ns{self.nsteps_update}-ds{self.density}"
        )
