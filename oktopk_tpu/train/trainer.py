"""Trainer: model + data + distributed optimizer wiring.

Reference analogue: ``DLTrainer`` (VGG/dl_trainer.py:105-796) builds the net,
data loaders and base optimizer; ``robust_ssgd`` (VGG/main_trainer.py:26)
wraps it with the distributed optimizer and runs the epoch loop; BERT's
``main`` (BERT/bert/main_bert.py:641) does the same with BertAdam. Here one
Trainer covers all three drivers: the workload decides the loss function and
optimizer family, and the distributed step comes from
``optim.build_sparse_grad_step``.

The initial-model broadcast (reference ``comm.bcast(net.state_dict())``,
VGG/main_trainer.py:52-54) is unnecessary: params are initialised once on
host and replicated by sharding spec.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from oktopk_tpu.config import OkTopkConfig, TrainConfig
from oktopk_tpu.models import create_model
from oktopk_tpu.models.registry import TOKEN_LMS
from oktopk_tpu.optim import bert_adam, sgd
from oktopk_tpu.optim.distributed import (
    DistTrainState,
    build_sparse_grad_step,
    flat_size,
    init_dist_state,
    place_dist_state,
)
from oktopk_tpu.train import losses
from oktopk_tpu.comm.mesh import get_mesh
from oktopk_tpu.utils import profiling
from oktopk_tpu.utils.compile_cache import compile_counters
from oktopk_tpu.utils.profiling import span

# steps whose ``metrics["counters"]`` vector a Trainer keeps, unsynced
KEPT_COUNTERS = 512

CNN_DNNS = {"vgg16", "vgg19", "resnet20", "resnet56", "resnet110",
            "resnet50", "alexnet", "mnistnet"}


def _ctc_frame_len(spect_lengths):
    """Input-spectrogram-frame lengths (what data/audio.py and
    data/synthetic.py emit) -> output-logit-frame units for ctc_loss and
    the greedy decoder: the conv frontend downsamples time by
    CONV_TIME_STRIDE (the reference likewise divides loader lengths by its
    frontend stride before warpctc, VGG/dl_trainer.py:743)."""
    from oktopk_tpu.models.deepspeech import CONV_TIME_STRIDE
    s = CONV_TIME_STRIDE
    return (spect_lengths + s - 1) // s


class Trainer:
    """End-to-end distributed trainer over a data-parallel mesh."""

    def __init__(self, cfg: TrainConfig, mesh: Optional[Mesh] = None,
                 algo_cfg: Optional[OkTopkConfig] = None,
                 model_kwargs: Optional[Dict[str, Any]] = None,
                 axis_name: str = "data", warmup: bool = True,
                 profile_norm: Optional[bool] = None,
                 fault_plan=None):
        from oktopk_tpu import settings
        if profile_norm is None:
            profile_norm = settings.PROFILING_NORM
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else get_mesh()
        self.axis_name = axis_name
        num_workers = int(np.prod(
            [self.mesh.shape[a] for a in (axis_name,)]))
        if cfg.num_workers != num_workers:
            cfg = dataclasses.replace(cfg, num_workers=num_workers)
        self.cfg = cfg

        mk = dict(model_kwargs or {})
        if cfg.compute_dtype != "float32":
            # mixed precision: flax `dtype` sets computation dtype only;
            # params stay float32 (flax param_dtype default) — the apex-amp
            # replacement (SURVEY.md §2.4)
            mk.setdefault("dtype", jnp.dtype(cfg.compute_dtype))
        self.model, example_fn = create_model(cfg.dnn, **mk)
        self.example_fn = example_fn

        with span("oktopk/setup/model_init", recorder=profiling.SETUP):
            rng = jax.random.PRNGKey(cfg.seed)
            init_batch = self._example_batch(2)
            variables = self._init_variables(rng, init_batch)
            params = variables.pop("params")
        self.model_state = dict(variables)

        n = flat_size(params)
        self.algo_cfg = (algo_cfg or OkTopkConfig()).replace(
            n=n, num_workers=num_workers, density=cfg.density)

        # Momentum correction (DGC-style) folds momentum into the compressed
        # gradient stream; it belongs to the SGD path only — Adam has its own
        # moment accumulators, so folding on top would double-smooth.
        if cfg.dnn.startswith("bert"):
            if cfg.momentum_correction:
                warnings.warn(
                    "momentum_correction is an SGD-path feature (reference "
                    "VGG/distributed_optimizer.py:56,81-88); ignored for "
                    "BERT/Adam workloads", stacklevel=2)
            self._mc_factor = 0.0
            self.optimizer = bert_adam(
                lr=cfg.lr, warmup=cfg.warmup_proportion,
                t_total=cfg.total_steps or -1)
        else:
            self._mc_factor = (cfg.momentum if cfg.momentum_correction
                               else 0.0)
            # with momentum correction the momentum lives in the compressed
            # gradient stream, so the base SGD runs momentum-free
            self.optimizer = sgd(
                cfg.lr,
                momentum=0.0 if self._mc_factor else cfg.momentum,
                weight_decay=cfg.weight_decay, nesterov=cfg.nesterov)

        self._warmup = warmup
        self._profile_norm = profile_norm

        # ---- unified observability (obs/): event bus + run journal ----
        # Built BEFORE the resilience/autotune journals so both can be
        # constructed as thin views over the same bus.
        self.bus = None
        self.run_journal = None
        self.tracer = None
        self._fed_shapes = None
        self.regress = None
        self.rollup = None
        self._quality_cfg = None
        self.quality_flushes = 0   # host drains of the device rings
        self._q_cursors = {}       # bucket -> last drained ring cursor
        if cfg.obs:
            from oktopk_tpu.obs.journal import EventBus, RunJournal
            self.bus = EventBus()
            self.run_journal = RunJournal(cfg.obs_journal, bus=self.bus)
            if cfg.obs_quality:
                # journal first, rollup engine second: the engine's
                # nested emit then lands each quality_rollup directly
                # after its quality event in the file
                from oktopk_tpu.obs.quality import QualityConfig
                from oktopk_tpu.obs.rollup import RollupEngine
                self._quality_cfg = QualityConfig(
                    every=cfg.obs_quality_every,
                    sig_bins=cfg.obs_quality_sig_bins)
                self.rollup = RollupEngine(
                    self.bus,
                    growth_limit=cfg.obs_quality_growth_limit,
                    collapse_ratio=cfg.obs_quality_collapse_ratio,
                    churn_limit=cfg.obs_quality_churn_limit,
                    comp_err_limit=cfg.obs_quality_comp_err_limit,
                    on_breach=self._on_quality_breach)
            if cfg.obs_trace_on_anomaly:
                import os
                import tempfile
                from oktopk_tpu.obs.tracing import AnomalyTracer
                tdir = cfg.obs_trace_dir
                if tdir is None:
                    tdir = (os.path.join(os.path.dirname(
                                os.path.abspath(cfg.obs_journal)), "traces")
                            if cfg.obs_journal
                            else tempfile.mkdtemp(prefix="oktopk_traces_"))
                self.tracer = AnomalyTracer(
                    tdir, bus=self.bus, num_steps=cfg.obs_trace_steps,
                    max_captures=cfg.obs_max_traces,
                    step_counters=self.step_counters,
                    step_hlo=self.step_hlo)
            if cfg.obs_regress_key:
                from oktopk_tpu.obs.regress import RegressionDetector
                self.regress = RegressionDetector.from_bench_records(
                    key=cfg.obs_regress_key, bus=self.bus,
                    tolerance=cfg.obs_regress_tolerance,
                    phase_limits=cfg.obs_phase_limits)

        # ---- numeric-health guard + supervisor (resilience/) ----------
        self._fault_plan = fault_plan
        self._guard = None
        self.supervisor = None
        if cfg.resilience:
            from oktopk_tpu.resilience import (GuardConfig, HealthJournal,
                                               Supervisor)
            self._guard = GuardConfig(abs_limit=cfg.resilience_abs_limit)
            self.supervisor = Supervisor(
                num_buckets=cfg.num_buckets,
                max_strikes=cfg.resilience_strikes,
                divergence_limit=cfg.resilience_divergence_limit,
                cooldown_steps=cfg.resilience_cooldown,
                journal=HealthJournal(cfg.resilience_journal,
                                      bus=self.bus))
            if fault_plan is not None:
                # chaos drill: announce the planned schedule up front so
                # the journal distinguishes drills from real corruption
                for f in fault_plan.faults:
                    self.supervisor.journal.fault_seen(
                        f.step, f"planned:{f.kind}", buckets=[f.bucket])

        # ---- closed-loop policies (resilience/feedback.py, density.py)
        self.feedback = None
        if cfg.resilience_feedback and self.bus is not None:
            from oktopk_tpu.resilience import AutotuneFeedback
            kinds = ("regression", "guard_trip")
            if self._quality_cfg is not None:
                # breached quality rollups vote alongside guard trips and
                # perf regressions in the forced-retune window
                kinds = kinds + ("quality_rollup",)
            self.feedback = AutotuneFeedback(
                self.bus, window_steps=cfg.resilience_feedback_window,
                min_signals=cfg.resilience_feedback_signals,
                cooldown_steps=cfg.resilience_feedback_cooldown,
                kinds=kinds)
        self.density_backoff = None
        if cfg.resilience and cfg.resilience_density_backoff:
            from oktopk_tpu.resilience import DensityBackoff
            self.density_backoff = DensityBackoff(
                abs_limit=cfg.resilience_abs_limit,
                near_ratio=cfg.resilience_near_ratio,
                backoff_steps=cfg.resilience_backoff_steps,
                factor=cfg.resilience_backoff_factor,
                max_level=cfg.resilience_backoff_max_level,
                clean_streak=cfg.resilience_clean_streak)
        self._density_scale = 1.0  # density-backoff multiplier (≤ 1)
        self.retune_events = 0     # forced re-calibrations executed
        self._fake_ms = None       # remembered trial-timing injector

        with span("oktopk/setup/state", recorder=profiling.SETUP):
            self.state = place_dist_state(init_dist_state(
                params, self.model_state, self.optimizer, self.algo_cfg,
                momentum_correction=bool(self._mc_factor),
                num_buckets=cfg.num_buckets,
                with_health=self._with_health,
                quality=self._quality_cfg), self.mesh, axis_name)
        self.autotuner = None      # built lazily by autotune()
        self._plans = None         # per-bucket BucketPlan list, or None
        with span("oktopk/setup/build_step", recorder=profiling.SETUP):
            self.step_fn = self._build_step()
        self._rng = jax.random.PRNGKey(cfg.seed + 1)
        self.metrics_history = []
        # ---- what each step did (utils/profiling.py) ------------------
        self.step_num = 0          # host step counter: every record of
        # one step (spans, counters, compile seconds) carries it
        self._counters = deque(maxlen=KEPT_COUNTERS)
        self._compiles = compile_counters()
        self._stepped_fn = None    # the step_fn that has run at least once
        profiling.register(self)

    @property
    def _with_health(self) -> bool:
        return self._guard is not None or self._fault_plan is not None

    @property
    def _forced_dense(self):
        return self.supervisor.forced_dense if self.supervisor else ()

    def _build_step(self):
        nb = max(1, self.cfg.num_buckets)
        compressor = self.cfg.compressor
        densities = None
        if self._plans:
            compressor = [p.algo for p in self._plans]
            densities = [p.density for p in self._plans]
        acfg = self.algo_cfg
        if self._density_scale < 1.0:
            # guard-aware backoff: shrink the *effective* selection
            # density (schedule included) without touching cfg.density —
            # capacity sizing stays pinned so wire buffers never re-size
            # across a backoff level change
            if acfg.density_schedule:
                acfg = acfg.replace(density_schedule=tuple(
                    (s, d * self._density_scale)
                    for s, d in acfg.density_schedule))
            else:
                densities = [d * self._density_scale for d in
                             (densities if densities is not None
                              else [self.cfg.density] * nb)]
        if self._forced_dense:
            from oktopk_tpu.resilience.supervisor import plan_with_fallbacks
            names = (list(compressor) if not isinstance(compressor, str)
                     else [compressor] * nb)
            compressor = plan_with_fallbacks(names, self._forced_dense)
            if densities is not None:
                densities = [1.0 if b in self._forced_dense else d
                             for b, d in enumerate(densities)]
        return build_sparse_grad_step(
            self._loss_fn, self.optimizer, acfg, self.mesh,
            compressor=compressor, axis_name=self.axis_name,
            nsteps_update=self.cfg.nsteps_update,
            grad_clip=self.cfg.grad_clip, warmup=self._warmup,
            profile_norm=self._profile_norm,
            momentum_correction=self._mc_factor,
            num_buckets=self.cfg.num_buckets,
            bucket_densities=densities,
            guard=self._guard, fault_plan=self._fault_plan,
            quality=self._quality_cfg)

    # ---- signal-fidelity telemetry (obs/quality.py) -------------------

    def _flush_quality(self, step: int) -> None:
        """Drain the device-side quality rings to the journal — the ONLY
        device→host movement the telemetry plane performs. One
        ``jax.device_get`` of the ring leaves per flush; each bucket's
        new rows become a schema-versioned ``quality`` event, which the
        RollupEngine immediately aggregates into a ``quality_rollup``."""
        if self._quality_cfg is None or self.bus is None:
            return
        if self.state.quality is None:
            return
        from oktopk_tpu.obs.metrics_buffer import rows_since
        from oktopk_tpu.obs.quality import quality_event
        names, densities = self._bucket_plan()
        if self.rollup is not None:
            self.rollup.target_densities = [float(d) for d in densities]
        single = self.cfg.num_buckets <= 1
        bufs = ([self.state.quality] if single
                else list(self.state.quality))
        host = jax.device_get(bufs)
        for b, hb in enumerate(host):
            cursor = int(np.asarray(hb.cursor).reshape(-1)[0])
            prev = self._q_cursors.get(b, 0)
            if cursor == prev:
                continue
            rows = rows_since(np.asarray(hb.ring), cursor, prev)
            self._q_cursors[b] = cursor
            algo = names[b] if b < len(names) else self.cfg.compressor
            ev = quality_event(step, b, algo, rows)
            self.bus.emit("quality", **ev)
        self.quality_flushes += 1

    def _on_quality_breach(self, step: int, bucket: int, breaches) -> None:
        """RollupEngine breach hook: route sustained FIDELITY breaches to
        the density-backoff controller. Guard pressure pushes density
        down; compression-quality pressure pulls it back up — the two
        halves of the closed loop meet in the same hysteretic policy."""
        if self.density_backoff is None:
            return
        change = None
        for kind in breaches:
            change = self.density_backoff.note_quality_breach(
                int(step), str(kind)) or change
        if change is not None:
            self._density_scale = float(change["scale"])
            if self.supervisor is not None:
                self.supervisor.journal.density_backoff(int(step), **change)
            elif self.bus is not None:
                self.bus.emit("density_backoff", step=int(step), **change)
            self.step_fn = self._build_step()

    # ---- autotuning ---------------------------------------------------

    def _make_autotuner(self, fake_ms=None):
        from oktopk_tpu.autotune import (Autotuner, AutotunePolicy,
                                         DecisionJournal, TrialRunner)
        from oktopk_tpu.autotune.policy import make_candidates
        from oktopk_tpu.optim.distributed import (bucket_partition,
                                                  bucket_sizes)

        cfg = self.cfg
        densities = tuple(cfg.autotune_densities) or (cfg.density,)
        policy = AutotunePolicy(
            candidates=make_candidates(cfg.autotune_candidates, densities),
            hysteresis=cfg.autotune_hysteresis,
            retune_every=cfg.autotune_retune_every,
            max_trials=cfg.autotune_max_trials)
        runner = TrialRunner(
            mesh=self.mesh, axis_name=self.axis_name,
            trial_steps=cfg.autotune_trial_steps, seed=cfg.seed,
            base_cfg=self.algo_cfg, fake_ms=fake_ms)
        sizes = bucket_sizes(self.state.params,
                             bucket_partition(self.state.params,
                                              cfg.num_buckets))
        return Autotuner(
            sizes, self.cfg.num_workers, policy, runner,
            journal=DecisionJournal(cfg.autotune_journal, bus=self.bus))

    def autotune(self, step: int = 0, fake_ms=None):
        """Run (or re-run) the calibrate -> trial -> policy pass and adopt
        the resulting per-bucket plan. The jitted step is rebuilt only
        when the plan actually changed — the policy's hysteresis is what
        keeps borderline buckets from forcing a recompile every re-tune.
        Returns the plan list.

        ``fake_ms(algo, n, density) -> ms`` injects synthetic trial
        timings (CPU tests of the decision logic; see autotune/trial.py).
        """
        from oktopk_tpu.autotune import Autotuner

        if fake_ms is not None:
            # remember the injector: a forced re-tune (force_retune) or
            # elastic resize rebuilds the tuner and must keep measuring
            # through the same seam
            self._fake_ms = fake_ms
        if self.autotuner is None:
            self.autotuner = self._make_autotuner(fake_ms=self._fake_ms)
        old = self._plans
        self._plans = self.autotuner.tune(step=step, mesh=self.mesh)
        if Autotuner.plans_changed(self._plans, old):
            self.step_fn = self._build_step()
        return self._plans

    def maybe_autotune(self, step: int):
        """Tune on first use and on the configured re-tune cadence."""
        if not self.cfg.autotune:
            return
        if self.autotuner is None or self.autotuner.should_retune(step):
            self.autotune(step=step)

    def force_retune(self, step: int, trigger: str = "manual",
                     signals=()):
        """Drop the autotuner and re-tune from scratch — the
        fault→autotune feedback path (resilience/feedback.py). A fresh
        tuner has no fabric coefficients, so the next ``tune()``
        re-calibrates against the *current* (possibly degraded) fabric
        before re-deciding; the journal carries the causal chain as
        ``retune`` (with the evidence steps) → ``calibration`` →
        ``autotune_decision``. Returns the new plan (None when autotune
        is off — the retune is still journalled so the evidence isn't
        lost)."""
        self.retune_events += 1
        if self.bus is not None:
            self.bus.emit("retune", step=int(step), trigger=str(trigger),
                          signals=[int(s) for s in signals],
                          cleared="autotuner")
        self.autotuner = None
        if self.cfg.autotune:
            return self.autotune(step=step)
        return None

    def check_feedback(self, step: int):
        """Poll the fault→autotune feedback policy; execute the forced
        re-calibrate + re-tune when its window vote passes. Returns the
        trigger descriptor (or None)."""
        if self.feedback is None:
            return None
        trig = self.feedback.should_retune(step)
        if trig is not None:
            self.force_retune(step, trigger=trig["trigger"],
                              signals=trig["signals"])
        return trig

    # ---- resilience supervision ---------------------------------------

    def supervise(self, step: int, metrics) -> None:
        """Feed one step's guard metrics to the supervisor and execute
        whatever it escalates to: a per-bucket dense fallback rebuilds
        the jitted step exactly like an autotune plan change; a restore
        reloads the last good checkpoint registered via
        :meth:`note_checkpoint` (journalled either way); a chip loss
        remeshes onto the surviving devices; and the density-backoff
        policy digests the step's guard pressure."""
        if self.supervisor is None:
            return
        # chip loss is a host/orchestrator observation, not a guard
        # metric: poll the plan's dead set (faults.dead_workers) and let
        # the supervisor escalate any newly dead rank straight to remesh
        if self._fault_plan is not None:
            from oktopk_tpu.resilience.faults import dead_workers
            dead = dead_workers(self._fault_plan, step)
            if dead:
                for act in self.supervisor.note_chip_loss(step, dead):
                    self._execute_action(act, step)
        host = {k: np.asarray(metrics[k])
                for k in ("step_skipped", "bucket_anomalies")
                if k in metrics}
        for act in self.supervisor.observe(step, host):
            self._execute_action(act, step)
        if self.density_backoff is not None and "reduced_absmax" in metrics:
            change = self.density_backoff.observe(
                step, absmax=float(np.asarray(metrics["reduced_absmax"])),
                skipped=int(np.asarray(metrics.get("step_skipped", 0))))
            if change is not None:
                self._density_scale = float(change["scale"])
                self.supervisor.journal.density_backoff(step, **change)
                self.step_fn = self._build_step()

    def _execute_action(self, act, step: int) -> None:
        """Execute one supervisor escalation action."""
        if act.kind == "fallback":
            # forced_dense already updated by the supervisor
            self.step_fn = self._build_step()
        elif act.kind == "restore" and act.ckpt:
            # verified restore: walk newest -> oldest past corrupt
            # files, journalling ckpt_verify_failed per rejected file
            # BEFORE the restore record — so the journal names the
            # checkpoint actually loaded, not the intended target
            from oktopk_tpu.train.durable import verified_restore
            journal = (self.supervisor.journal
                       if self.supervisor is not None else None)
            try:
                self.state, ckpt_step, used, _, _ = verified_restore(
                    act.ckpt, self.state, journal=journal, bus=self.bus,
                    step=step)
            except FileNotFoundError:
                # every candidate corrupt: a restore cannot happen —
                # journal the fact and fail loudly rather than keep
                # training a diverged model
                if journal is not None:
                    journal.restore(step, None, -1)
                raise
            if journal is not None:
                journal.restore(step, used, ckpt_step)
        elif act.kind == "remesh":
            self._execute_remesh(step, act.workers)

    def _execute_remesh(self, step: int, workers) -> None:
        """Shrink the mesh to the devices whose ranks survive and resize
        onto it — the no-requeue recovery path for chip loss. Rank i is
        position i in the flattened device list (the data-parallel-only
        layout every emulated drill uses)."""
        dead = {int(w) for w in workers}
        devs = [d for i, d in enumerate(
                    np.asarray(self.mesh.devices).reshape(-1))
                if i not in dead]
        if not devs:
            raise RuntimeError(
                f"chip_loss at step {step} left no surviving devices")
        new_mesh = get_mesh(axis_names=self.mesh.axis_names, devices=devs)
        self.resize_workers(new_mesh, trigger="chip_loss",
                            dead_workers=sorted(dead), step=step)

    def note_checkpoint(self, path: str, step: int) -> None:
        """Register a saved checkpoint as a restore candidate (and record
        the supervisor's own state next to it, see ``supervisor_extra``).
        Journalled either way: via the supervisor's health journal when
        resilience is on, straight onto the bus otherwise."""
        if self.supervisor is not None:
            self.supervisor.note_checkpoint(path, step)
        elif self.bus is not None:
            self.bus.emit("checkpoint", step=int(step), path=path,
                          qualified=True)

    @property
    def checkpoint_qualified(self) -> bool:
        """Whether a checkpoint taken NOW would be a restore target (no
        skips in flight) — recorded into the manifest's ``qualified``
        bit so the retention policy and offline fsck see the same
        good/mid-incident distinction the supervisor does."""
        if self.supervisor is None:
            return True
        return self.supervisor.consecutive_skips == 0

    def note_ckpt_failure(self, step: int, path: str, error) -> None:
        """Escalate a failed (async) checkpoint write to the supervisor —
        the ``on_failure`` hook for ``durable.AsyncCheckpointer``."""
        if self.supervisor is not None:
            self.supervisor.note_ckpt_write_failure(step, path, error)
        elif self.bus is not None:
            self.bus.emit("ckpt_verify_failed", step=int(step), path=path,
                          reason=f"write_failed: {error}")

    def supervisor_extra(self):
        """The ``extra`` payload for ``checkpoint.save_checkpoint``: the
        supervisor's strike counters, active fallbacks, and last-good
        marker, so a resumed run keeps its escalation state."""
        if self.supervisor is None:
            return None
        return {"supervisor": self.supervisor.to_state()}

    def restore_supervisor(self, ckpt_dir_or_file: str) -> None:
        """Re-arm the supervisor from a checkpoint's extra payload and
        re-apply its per-bucket fallbacks to the jitted step."""
        if self.supervisor is None:
            return
        from oktopk_tpu.train.checkpoint import load_extra
        extra = load_extra(ckpt_dir_or_file) or {}
        self.supervisor.load_state(extra.get("supervisor") or {})
        if self.supervisor.forced_dense:
            self.step_fn = self._build_step()

    # ---- workload-specific pieces -------------------------------------

    def _init_variables(self, rng, batch):
        rngs = {"params": rng, "dropout": jax.random.fold_in(rng, 1)}
        if self.cfg.dnn in TOKEN_LMS:
            init = self.model.init
            if getattr(self.model, "jit_init", False):
                # one program instead of an eager pass: on the chip each
                # small operation of an eager pass is a compilation
                init = jax.jit(init, static_argnames=("train",))
            return init(rngs, *self._lm_inputs(batch), train=False)
        if self.cfg.dnn.startswith("bert"):
            return self.model.init(rngs, batch["input_ids"],
                                   batch["token_type_ids"],
                                   batch["attention_mask"], train=False)
        if self.cfg.dnn.startswith("lstman4"):
            return self.model.init(rngs, batch["spect"], train=False)
        return self.model.init(rngs, batch["image"], train=False)

    def _example_batch(self, bs: int):
        """Zero-filled batch with the workload's shapes (for init/tracing)."""
        dnn = self.cfg.dnn
        if dnn in TOKEN_LMS:
            tokens = self.example_fn(bs)    # the registry's example shape
            return {"tokens": tokens, "targets": jnp.zeros_like(tokens)}
        if dnn.startswith("bert"):
            t = 32 if dnn == "bert_tiny" else 128
            return {"input_ids": jnp.zeros((bs, t), jnp.int32),
                    "token_type_ids": jnp.zeros((bs, t), jnp.int32),
                    "attention_mask": jnp.ones((bs, t), jnp.int32),
                    "mlm_labels": jnp.full((bs, t), -1, jnp.int32),
                    "nsp_labels": jnp.zeros((bs,), jnp.int32)}
        if dnn.startswith("lstman4"):
            return {"spect": jnp.zeros((bs, 161, 201, 1), jnp.float32),
                    "spect_lengths": jnp.full((bs,), 201, jnp.int32),
                    "labels": jnp.zeros((bs, 40), jnp.int32),
                    "label_lengths": jnp.full((bs,), 10, jnp.int32)}
        img = self.example_fn(bs)
        return {"image": img,
                "label": jnp.zeros((bs,), jnp.int32)}

    @property
    def _own_loss(self) -> bool:
        """Does the token language model compute its own loss (its class
        says so by ``computes_loss``: a loss that is not one cross-entropy
        of one logits tensor, computed where the hidden state is)? It is
        then called with the targets too and returns ``(loss, extra)``,
        ``extra["eval_loss"]`` being what an evaluation reports."""
        return getattr(self.model, "computes_loss", False)

    def _lm_inputs(self, batch):
        """What a token language model is called with."""
        if self._own_loss:
            return batch["tokens"], batch["targets"]
        return (batch["tokens"],)

    def _loss_fn(self, params, model_state, batch, rng):
        dnn = self.cfg.dnn
        variables = {"params": params, **model_state}
        mutable = [k for k in model_state]
        rngs = {"dropout": rng}

        if dnn in TOKEN_LMS:
            # every token language model: ``apply`` gives the logits, or
            # the model's own loss, and one thing more (the LSTM its carry,
            # dropped here as ever; any other a dict of what it counted)
            (out, extra), mut = self.model.apply(
                variables, *self._lm_inputs(batch), train=True,
                mutable=mutable, rngs=rngs)
            loss = (out if self._own_loss
                    else losses.lm_cross_entropy(out, batch["targets"]))
            return loss, (dict(mut), losses.model_counters(extra))
        if dnn.startswith("bert"):
            (mlm, nsp), mut = self.model.apply(
                variables, batch["input_ids"], batch["token_type_ids"],
                batch["attention_mask"], train=True, mutable=mutable,
                rngs=rngs)
            loss, aux = losses.bert_pretrain_loss(
                mlm, nsp, batch["mlm_labels"], batch["nsp_labels"])
            return loss, (dict(mut), aux)
        if dnn.startswith("lstman4"):
            logits, mut = self.model.apply(
                variables, batch["spect"], train=True, mutable=mutable,
                rngs=rngs)
            frames = logits.shape[1]
            frame_len = jnp.minimum(_ctc_frame_len(batch["spect_lengths"]),
                                    frames)
            loss = losses.ctc_loss(logits, frame_len, batch["labels"],
                                   batch["label_lengths"])
            return loss, (dict(mut), {})
        logits, mut = self.model.apply(
            variables, batch["image"], train=True, mutable=mutable, rngs=rngs)
        loss = losses.softmax_cross_entropy(logits, batch["label"])
        return loss, (dict(mut), {})

    # ---- loops --------------------------------------------------------

    def train_step(self, batch):
        self.step_num += 1
        with span("oktopk/step", step=self.step_num):
            with span("oktopk/rng"):
                self._rng, rng = jax.random.split(self._rng)
            compiled = self._compiles.counts["compile"]
            seconds = self._compiles.seconds["compile"]
            with span("oktopk/dispatch"):
                self.state, metrics = self.step_fn(self.state, batch, rng)
            if self._stepped_fn is not self.step_fn:
                self._stepped_fn = self.step_fn    # its first call compiles
            elif self._compiles.counts["compile"] != compiled:
                self._note_recompile(
                    self._compiles.seconds["compile"] - seconds)
        # device arrays, not fetched until somebody asks (step_counters)
        self._counters.append((self.step_num, metrics["counters"]))
        return metrics

    def _note_recompile(self, seconds: float) -> None:
        """A back-end compile in a later call of a step function than its
        first: new input shapes or shardings, at full compile cost."""
        self._compiles.note_recompile(self.step_num, seconds)
        if self.bus is not None:
            self.bus.emit("recompile", step=self.step_num,
                          seconds=float(seconds))

    def step_counters(self):
        """The retained ``(step_num, counters)`` pairs, oldest first; each
        ``counters`` is the step's device vector in
        ``collectives/state.COUNTERS`` order."""
        return list(self._counters)

    def train(self, data_iter: Iterable, num_iters: int,
              log_every: int = 50, logger=None, metric_writer=None,
              timers=None, trace=None, start_step: int = 0,
              should_stop=None):
        """Run ``num_iters`` steps (reference trainer.train(nsteps),
        VGG/dl_trainer.py:597). Returns the last metrics dict.

        Optional observability hooks (SURVEY.md §5.1): ``metric_writer``
        (utils.profiling.MetricWriter) records per-step scalars,
        ``timers`` (PhaseTimers) is attached as the recorder of the
        loop's host spans (``oktopk/data``, ``oktopk/step`` and one round
        each place that may wait for the device) and tabulates the loop as
        it runs: it adds no wait of its own,
        ``trace`` (TraceWindow) captures a bounded jax.profiler trace.
        """
        if timers is not None:
            prev = profiling.attach(timers)
        try:
            return self._train(data_iter, num_iters, log_every, logger,
                               metric_writer, timers, trace, start_step,
                               should_stop)
        finally:
            if timers is not None:
                profiling.attach(prev)

    def _train(self, data_iter, num_iters, log_every, logger, metric_writer,
               timers, trace, start_step, should_stop):
        metrics = {}
        pending = []  # (step, device-metrics) — flushed on the log cadence
        # so the writer never forces a per-step device sync
        nf_window = []  # per-step nonfinite-grad counters (device scalars;
        # summed host-side only on the log cadence)

        def flush_pending():
            for s, dm in pending:
                host = self._host_scalars(dm)
                if metric_writer is not None:
                    metric_writer.write(s, host)
                if self.bus is not None:
                    self.bus.emit("step", step=s, **host)
            pending.clear()

        t0 = time.time()
        self.last_step = start_step
        for i in range(num_iters):
            if should_stop is not None and should_stop():
                # preemption: break between steps so state is consistent
                # (reference's clean-exit Event, BERT/bert/main_bert.py:73-96)
                break
            step = start_step + i + 1
            self.last_step = step
            self.step_num = step - 1     # train_step counts it up to step
            # plan (or re-plan) the per-bucket collectives before the step
            # runs; a no-change verdict leaves step_fn (and its compiled
            # program) untouched
            if self.cfg.autotune:
                with span("oktopk/autotune", step=step):
                    self.maybe_autotune(step)
            if trace is not None:
                trace.on_step(step)
            if self.tracer is not None:
                # anomaly-armed profiler window (obs/tracing.py): opens
                # here on the step after a guard_trip/fallback event,
                # closes num_steps later with a trace_captured event
                self.tracer.on_step(step)
            with span("oktopk/data", step=step):
                batch = next(data_iter)
            if self.tracer is not None and self.tracer.active:
                # what the captured steps are fed, for step_hlo()
                self._fed_shapes = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch)
            metrics = self.train_step(batch)
            if (self.supervisor is not None
                    and step % max(1, self.cfg.resilience_check_every) == 0):
                # reacting to guard trips costs a device sync on the
                # check cadence; escalation may rebuild step_fn or
                # restore state before the next iteration
                with span("oktopk/supervise", step=step):
                    self.supervise(step, metrics)
            if (self._quality_cfg is not None
                    and step % self._quality_cfg.every == 0):
                # drain the device metric rings on the flush cadence —
                # steady state between flushes adds zero host syncs
                with span("oktopk/flush_quality", step=step):
                    self._flush_quality(step)
            if self.feedback is not None:
                # fault→autotune feedback: a passing window vote forces
                # a re-calibrate + re-tune (host-side list ops only
                # until it actually fires)
                self.check_feedback(step)
            if metric_writer is not None or self.bus is not None:
                pending.append((step, metrics))
            if "grad_nonfinite" in metrics:
                nf_window.append(metrics["grad_nonfinite"])
            if (i + 1) % log_every == 0:
                # the log cadence's one wait for the device
                with span("oktopk/log_flush", step=step):
                    if pending:
                        flush_pending()
                    if logger:
                        loss = float(metrics["loss"])
                dt = (time.time() - t0) / log_every
                if self.regress is not None:
                    self.regress.observe(step, dt * 1e3)
                if logger:
                    # absolute step, not the loop index: after a preemption
                    # resume the log must agree with scalars.csv/checkpoints
                    logger.info(
                        "iter %d loss %.4f vol %.0f %.3fs/it", step,
                        loss, float(metrics["comm_volume"]), dt)
                    nf = sum(float(x) for x in nf_window)
                    if nf:
                        # the reference warns on NaN gradient sparsity
                        # (VGG/dl_trainer.py:608-609); the whole window is
                        # summed so a mid-window blow-up cannot hide
                        logger.warning(
                            "window ending iter %d: %d nonfinite gradient "
                            "elements", step, int(nf))
                    nf_window.clear()
                if timers is not None and self.bus is not None:
                    phase_summary = timers.summary()
                    self.bus.emit("phase", step=step, phases=phase_summary)
                    if self.regress is not None:
                        # host-phase durations vs configured phase limits
                        # (key="phase:<name>" regressions on the bus).
                        # The limits' old keys keep their meaning: "data"
                        # is the wait in next(data_iter); "step" was the
                        # blocked device step, which no span holds now
                        # that nothing blocks: the window's wall time a
                        # step is what the device sets in a steady loop
                        self.regress.observe_phases(step, {
                            **phase_summary,
                            "data": phase_summary.get("oktopk/data"),
                            "step": dt * 1e3})
                t0 = time.time()
            if timers is not None and logger is not None:
                timers.maybe_log(step, logger)
        if pending:
            flush_pending()
        if self.tracer is not None:
            self.tracer.finish(self.last_step)
        if self._quality_cfg is not None:
            # partial-window flush so the tail of the run is journalled
            self._flush_quality(self.last_step)
        if self.bus is not None:
            self._emit_volume_report()
        self.metrics_history.append(self._host_scalars(metrics))
        return metrics

    @staticmethod
    def _host_scalars(metrics) -> Dict[str, float]:
        """One step's device metrics as host floats; the ``counters``
        vector is spread under its entries' names (the realised counts are
        ``local_k``/``global_k`` already)."""
        from oktopk_tpu.collectives.state import BRANCH_COUNTERS
        host = {k: float(np.asarray(v).mean())
                for k, v in metrics.items() if k != "counters"}
        if "counters" in metrics:
            host.update(zip(BRANCH_COUNTERS, map(
                float, np.asarray(metrics["counters"]))))
        return host

    def _bucket_plan(self):
        """Per-bucket (algo name, density) after autotune plans and forced
        dense fallbacks — the same resolution :meth:`_build_step`
        performs, exposed for reporting."""
        nb = max(1, self.cfg.num_buckets)
        names = [self.cfg.compressor] * nb
        densities = [self.cfg.density] * nb
        if self._plans:
            names = [p.algo for p in self._plans]
            densities = [p.density for p in self._plans]
        if self._density_scale < 1.0 and not self.algo_cfg.density_schedule:
            densities = [d * self._density_scale for d in densities]
        for b in self._forced_dense:
            if 0 <= b < nb:
                names[b] = "dense"
                densities[b] = 1.0
        return names, densities

    def _bucket_cfgs(self):
        """``[(algo name, OkTopkConfig, SparseState), ...]``, a bucket
        each: the config with the bucket's own n and the density of
        :meth:`_bucket_plan`, which is what sizes its buffers."""
        names, densities = self._bucket_plan()
        sps = ([self.state.sparse_state] if self.cfg.num_buckets <= 1
               else list(self.state.sparse_state))
        return [(nm, self.algo_cfg.replace(
            n=int(sp.residual.shape[-1]), density=float(dens)), sp)
            for nm, dens, sp in zip(names, densities, sps)]

    def capacities(self):
        """The static sizes of the two buffers a step's selections fill, a
        bucket each: ``local_k / cap_pair`` and ``global_k / cap_gather``
        (``collectives/state.COUNTERS``, summed over the buckets) are the
        live shares that the materialise's cost follows
        (``ops/compaction._gather_live``). Beside them ``leafwise``,
        another static fact of the built step: whether the bucket is
        reduced a leaf at a time, with no flat vector built or cut up
        (``optim/distributed.build_sparse_grad_step``)."""
        return [{"cap_pair": c.cap_pair, "cap_gather": c.cap_gather,
                 "leafwise": lw}
                for (_, c, _), lw in zip(self._bucket_cfgs(),
                                         self.step_fn.leafwise)]

    def step_hlo(self, batch=None) -> str:
        """The compiled text of the step as last built, for
        ``obs/anatomy.owners``: lower and compile again (from the
        persistent cache where the step has run), nothing executes.
        ``batch`` gives the shapes the step is fed; left out, those of
        the anomaly tracer's last captured step, else the registry's
        example at the configured global batch. Called by
        nobody on the step path."""
        if batch is None:
            batch = self._fed_shapes or self._example_batch(
                self.cfg.batch_size * self.cfg.num_workers)
        return self.step_fn.lower(
            self.state, batch, self._rng).compile().as_text()

    def step_owners(self, batch=None):
        """Whose each instruction of the compiled step is:
        ``{instruction name: anatomy.Owner}``, the map that
        ``anatomy.analyze_device`` / ``analyze_xplane`` label a device
        trace with."""
        from oktopk_tpu.obs import anatomy
        return anatomy.owners(self.step_hlo(batch))

    def _emit_volume_report(self):
        """One ``volume_report`` event per bucket: mean realised wire
        bytes per step (from the SparseState accounting) against the
        algorithm's analytic budget (obs/volume.py). The mean covers the
        WHOLE run — dense warmup steps and exact recomputes included —
        so a warmed-up sparse run legitimately reports above the
        steady-state budget; the per-algorithm conformance guarantee is
        asserted by the steady-state tests, not here."""
        from oktopk_tpu.obs import volume as obs_volume
        for b, (nm, cfg_b, sp) in enumerate(self._bucket_cfgs()):
            steps_done = int(np.asarray(sp.step)[0])
            wb = float(np.asarray(sp.wire_bytes)[0])
            rep = obs_volume.volume_report(
                nm, cfg_b, wb / max(1, steps_done), bucket=b,
                step=getattr(self, "last_step", 0), steps=steps_done)
            self.bus.emit("volume_report", **rep)

    # ---- elasticity ---------------------------------------------------

    def resize_workers(self, new_mesh: Mesh, trigger: str = "manual",
                       dead_workers=(), step: Optional[int] = None):
        """Rebuild the distributed step for a new world size, keeping model
        and optimizer state.

        Reference analogue: the elastic hooks ``err_callback`` ->
        ``trainer.update_nworker`` which rebuild samplers/loaders for a new
        world size (VGG/main_trainer.py:42-44, VGG/dl_trainer.py:472-493).
        Detection lives in the supervisor's chip-loss path
        (:meth:`supervise` → ``note_chip_loss`` → ``remesh`` action →
        here with ``trigger="chip_loss"``); an orchestrator-driven resize
        calls this directly (``trigger="manual"``). Per-worker algorithm
        state (residuals, boundaries) is re-initialised for the new
        topology; replicated state — params, model/opt state, the health
        attempted-step clock, and the host-side supervisor counters —
        carries over, so fault plans and strike histories stay aligned
        with the run's step indices. The resize is journalled as a
        schema-versioned ``remesh`` event naming exactly which state
        carried vs was re-initialised.
        """
        old_world = int(self.cfg.num_workers)
        num_workers = int(new_mesh.shape[self.axis_name])
        self.mesh = new_mesh
        self.cfg = dataclasses.replace(self.cfg, num_workers=num_workers)
        self.algo_cfg = self.algo_cfg.replace(num_workers=num_workers)
        # pull replicated state off the old mesh's devices before re-placing;
        # params/model/opt state carry over, per-worker state re-initialises
        old = jax.device_get(
            (self.state.params, self.state.model_state, self.state.opt_state))
        old_health = (jax.device_get(self.state.health)
                      if self.state.health is not None else None)
        self.state = place_dist_state(init_dist_state(
            old[0], old[1], self.optimizer, self.algo_cfg,
            momentum_correction=bool(self._mc_factor), opt_state=old[2],
            num_buckets=self.cfg.num_buckets,
            with_health=self._with_health,
            quality=self._quality_cfg), new_mesh, self.axis_name)
        carried = ["params", "model_state", "opt_state"]
        reinit = ["sparse_state", "local_momentum", "autotuner"]
        if self._quality_cfg is not None:
            # fresh per-worker rings for the new topology; drained-cursor
            # bookkeeping restarts with them so the first post-resize
            # flush doesn't replay stale rows
            self._q_cursors = {}
            reinit.append("quality")
        if old_health is not None and self.state.health is not None:
            # the attempted-step counter is the clock every fault plan
            # and supervisor cadence indexes by — it must stay monotonic
            # across the resize, not restart at 0
            self.state = self.state.replace(health=old_health)
            carried.append("health")
        elif self.state.health is not None:
            reinit.append("health")
        if self.supervisor is not None:
            carried.append("supervisor")
        # trial measurements were taken on the old topology: drop the
        # tuner (it re-tunes against the new mesh on the next cadence)
        # but keep the current plan so the rebuilt step stays consistent
        self.autotuner = None
        self.step_fn = self._build_step()
        ev = dict(step=int(step if step is not None
                           else getattr(self, "last_step", 0)),
                  old_world=old_world, new_world=num_workers,
                  trigger=str(trigger),
                  dead_workers=[int(w) for w in dead_workers],
                  carried=carried, reinitialised=reinit)
        if self.supervisor is not None:
            self.supervisor.journal.remesh(**ev)
        elif self.bus is not None:
            self.bus.emit("remesh", **ev)

    # ---- eval ---------------------------------------------------------

    def eval_step(self, batch):
        """Forward-only accuracy/loss on a replicated batch (reference
        DLTrainer.test, VGG/dl_trainer.py:709)."""
        params = self.state.params
        variables = {"params": params, **self.state.model_state}
        dnn = self.cfg.dnn
        if dnn in TOKEN_LMS:
            out, extra = self.model.apply(
                variables, *self._lm_inputs(batch), train=False)
            loss = (extra["eval_loss"] if self._own_loss
                    else losses.lm_cross_entropy(out, batch["targets"]))
            return {"loss": loss, "ppl": jnp.exp(loss)}
        if dnn.startswith("bert"):
            mlm, nsp = self.model.apply(
                variables, batch["input_ids"], batch["token_type_ids"],
                batch["attention_mask"], train=False)
            loss, aux = losses.bert_pretrain_loss(
                mlm, nsp, batch["mlm_labels"], batch["nsp_labels"])
            return {"loss": loss, **aux}
        if dnn.startswith("lstman4"):
            # real CTC loss + greedy-decoded WER/CER — the reference's test
            # loop decodes every eval batch and averages word/char distances
            # (VGG/dl_trainer.py:743-762, decoder at VGG/decoder.py:23-197)
            from oktopk_tpu.data.audio import AN4_LABELS
            from oktopk_tpu.utils.decoder import GreedyDecoder

            logits = self.model.apply(variables, batch["spect"], train=False)
            frames = logits.shape[1]
            frame_len = jnp.minimum(_ctc_frame_len(batch["spect_lengths"]),
                                    frames)
            loss = losses.ctc_loss(logits, frame_len, batch["labels"],
                                   batch["label_lengths"])
            dec = GreedyDecoder(AN4_LABELS)
            hyps = dec.decode(np.asarray(logits), np.asarray(frame_len))
            labs = np.asarray(batch["labels"])
            lens = np.asarray(batch["label_lengths"])
            refs = ["".join(AN4_LABELS[c] for c in labs[b, : lens[b]])
                    for b in range(labs.shape[0])]
            wer = float(np.mean([dec.wer(h, r) for h, r in zip(hyps, refs)]))
            cer = float(np.mean([dec.cer(h, r) for h, r in zip(hyps, refs)]))
            return {"loss": loss, "wer": jnp.asarray(wer),
                    "cer": jnp.asarray(cer)}
        logits = self.model.apply(variables, batch["image"], train=False)
        loss = losses.softmax_cross_entropy(logits, batch["label"])
        acc = jnp.mean(
            (jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
        return {"loss": loss, "accuracy": acc}
