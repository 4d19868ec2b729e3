"""CLI driver for the CNN/LSTM workloads (reference VGG/main_trainer.py and
LSTM/main_trainer.py: robust_ssgd + argparse at :143-180).

The reference launches one MPI rank per GPU node via srun; here one process
drives the whole mesh. ``--fake-devices N`` reproduces the multi-worker
topology on CPU for dry runs (the reference's two-local-process trick,
SURVEY.md §4).

Example:
    python -m oktopk_tpu.train.main_trainer --dnn vgg16 --dataset cifar10 \\
        --batch-size 16 --lr 0.1 --compressor oktopk --density 0.02 \\
        --max-iters 200
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    # reference flag surface (VGG/main_trainer.py:144-159)
    p.add_argument("--dnn", default="vgg16")
    p.add_argument("--dataset", default="cifar10")
    p.add_argument("--data-dir", default="./data")
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=5e-4)
    p.add_argument("--nesterov", action="store_true")
    p.add_argument("--max-epochs", type=int, default=161)
    p.add_argument("--max-iters", type=int, default=0,
                   help="if set, run exactly this many iterations")
    p.add_argument("--nsteps-update", type=int, default=1)
    p.add_argument("--compute-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="model computation dtype (bf16 = 2x MXU; params/"
                        "grads/collective stay f32 - the apex-amp role)")
    p.add_argument("--wire-dtype", default="bfloat16",
                   choices=["bfloat16", "float32"],
                   help="sparse message VALUE dtype on the wire (the "
                        "reference's fp16 MPI datatype role; float32 = "
                        "reference-exact uncompressed messages)")
    p.add_argument("--num-buckets", type=int, default=1,
                   help="reverse-layer-order gradient buckets, one sparse "
                        "collective each (reference <=640MiB bucketing, "
                        "VGG/allreducer.py:27); 1 = whole-model flat")
    p.add_argument("--compressor", default="oktopk")
    p.add_argument("--autotune", action="store_true",
                   help="pick each bucket's collective + density at "
                        "runtime (autotune/: calibrated cost-model prior "
                        "-> timed trial posterior); --compressor becomes "
                        "the pre-plan fallback")
    p.add_argument("--autotune-candidates", default="dense,oktopk",
                   help="comma-separated registry names to trial")
    p.add_argument("--autotune-trial-steps", type=int, default=3)
    p.add_argument("--autotune-retune-every", type=int, default=0,
                   help="steps between re-tunes (0 = tune once)")
    p.add_argument("--autotune-journal", default=None,
                   help="JSONL decision-journal path (see docs/PERF.md)")
    p.add_argument("--resilience", action="store_true",
                   help="numeric-health guard + supervisor (resilience/): "
                        "psum-agreed skip of anomalous steps with "
                        "residual rollback, per-bucket dense fallback "
                        "after repeated strikes, checkpoint restore on "
                        "divergence")
    p.add_argument("--resilience-strikes", type=int, default=3,
                   help="guard trips on a bucket before it falls back "
                        "to the dense collective")
    p.add_argument("--resilience-abs-limit", type=float, default=1e18,
                   help="reduced-gradient magnitude treated as anomalous "
                        "even while finite (wire bit-flips land ~1e38)")
    p.add_argument("--resilience-journal", default=None,
                   help="JSONL health-journal path (docs/RESILIENCE.md)")
    p.add_argument("--resilience-feedback", action="store_true",
                   help="fault->autotune feedback: a sustained stream of "
                        "regression/guard_trip events forces an autotune "
                        "re-calibrate + re-tune against the degraded "
                        "fabric (resilience/feedback.py; needs --obs)")
    p.add_argument("--resilience-feedback-window", type=int, default=32,
                   help="steps a feedback signal stays live in the vote")
    p.add_argument("--resilience-feedback-signals", type=int, default=3,
                   help="signals within the window needed to force a "
                        "re-tune")
    p.add_argument("--resilience-feedback-cooldown", type=int, default=64,
                   help="steps between forced re-tunes")
    p.add_argument("--resilience-density-backoff", action="store_true",
                   help="guard-aware density backoff: repeated "
                        "near-abs-limit/guard-skip steps back the "
                        "effective density off (bounded, hysteretic, "
                        "journalled; resilience/density.py)")
    p.add_argument("--resilience-near-ratio", type=float, default=0.1,
                   help="fraction of abs-limit counted as guard pressure")
    p.add_argument("--resilience-backoff-steps", type=int, default=3,
                   help="pressured steps before one backoff level")
    p.add_argument("--resilience-backoff-factor", type=float, default=0.5,
                   help="density multiplier per backoff level")
    p.add_argument("--resilience-backoff-max-level", type=int, default=3,
                   help="deepest backoff level")
    p.add_argument("--resilience-clean-streak", type=int, default=8,
                   help="clean steps before re-advancing one level")
    p.add_argument("--obs", action="store_true",
                   help="unified run journal (obs/): per-step metrics, "
                        "autotune decisions, guard trips, checkpoints, "
                        "trace captures and volume reports in ONE JSONL "
                        "file (docs/OBSERVABILITY.md)")
    p.add_argument("--obs-journal", default=None,
                   help="run-journal path (default: "
                        "<logdir>/<slug>/run_journal.jsonl)")
    p.add_argument("--obs-trace-on-anomaly", action="store_true",
                   help="arm a bounded jax.profiler window on guard_trip/"
                        "fallback events (obs/tracing.py)")
    p.add_argument("--obs-trace-steps", type=int, default=3,
                   help="steps per anomaly-triggered trace window")
    p.add_argument("--obs-regress-key", default=None,
                   help="BENCH_r*.json parsed key (e.g. oktopk_ms) to "
                        "baseline step-time regression checks against")
    p.add_argument("--obs-quality", action="store_true",
                   help="in-jit signal-fidelity taps (obs/quality.py): "
                        "per-bucket compression error, residual growth, "
                        "effective density, threshold drift and index "
                        "churn accumulated in device-side rings and "
                        "journalled every --obs-quality-every steps")
    p.add_argument("--obs-quality-every", type=int, default=32,
                   help="quality ring capacity / host-flush cadence "
                        "(steps); between flushes the taps add zero "
                        "host syncs")
    p.add_argument("--density", type=float, default=0.02)
    p.add_argument("--sigma-scale", type=float, default=2.5)
    p.add_argument("--grad-clip", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--model-kwargs", default=None,
                   help="JSON object of keyword arguments for the model "
                        "(models/registry.py), e.g. the share of "
                        "deepseek_v2_lite one chip holds: "
                        '\'{"num_hidden_layers": 5, "vocab_size": 12800, '
                        '"held_experts": [0, 1, 2, 3, 4, 5, 6, 7]}\'')
    p.add_argument("--seq-len", type=int, default=None,
                   help="tokens a sequence of the synthetic data "
                        "(token models; default: the registry's)")
    p.add_argument("--warmup-steps", type=int, default=None,
                   help="dense warmup iterations (default: reference's 512)")
    p.add_argument("--fake-devices", type=int, default=0,
                   help="virtual CPU devices for dry runs")
    p.add_argument("--log-every", type=int, default=50)
    p.add_argument("--logdir", default="./logs")
    p.add_argument("--trace-at", type=int, default=0,
                   help="capture a jax.profiler trace starting at this "
                        "step (0 = off); view with xprof/tensorboard")
    p.add_argument("--trace-steps", type=int, default=3)
    p.add_argument("--phase-timers", action="store_true",
                   help="record the loop's host spans and log their table "
                        "every --log-every steps (reference "
                        "_print_profiling, VGG/allreducer.py:379-439); "
                        "adds no wait for the device")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=0,
                   help="checkpoint every N iterations (0 = off)")
    p.add_argument("--ckpt-async", action="store_true",
                   help="write checkpoints on a background thread "
                        "(durable.AsyncCheckpointer): the step loop only "
                        "pays jax.device_get; serialize+fsync+verify run "
                        "off-thread with bounded queue depth and a drain "
                        "barrier on exit")
    p.add_argument("--ckpt-keep", type=int, default=0,
                   help="retention: keep the newest N checkpoints plus "
                        "the newest qualified one (0 = keep everything)")
    p.add_argument("--ckpt-force", action="store_true",
                   help="restore a checkpoint even when most of its "
                        "leaves mismatch the model (normally that raises "
                        "— it almost always means the wrong --model for "
                        "this checkpoint)")
    p.add_argument("--resume", default=None,
                   help="checkpoint directory to resume from")
    p.add_argument("--handle-preemption", action="store_true",
                   help="install SIGTERM/SIGUSR1/SIGUSR2 handlers: on "
                        "preemption, checkpoint to ~/.interrupted_states "
                        "and (SIGUSR1) scontrol requeue — reference "
                        "BERT/bert/main_bert.py:73-203")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}")
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    if args.fake_devices:
        jax.config.update("jax_platforms", "cpu")
    else:
        # Multi-host rendezvous (reference init_distrib_slurm,
        # BERT/bert/main_bert.py:159-203) — no-op for single-process jobs.
        from oktopk_tpu.launch import maybe_initialize
        penv = maybe_initialize()
        if penv.num_processes > 1:
            print(f"[launch] process {penv.process_id}/{penv.num_processes}"
                  f" via {penv.source}, coordinator={penv.coordinator}")

    from oktopk_tpu.config import OkTopkConfig, TrainConfig
    from oktopk_tpu.data import make_dataset
    from oktopk_tpu.train.trainer import Trainer
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache
    from oktopk_tpu.utils.logging import get_logger

    cache_dir = ensure_compile_cache()

    cfg = TrainConfig(
        dnn=args.dnn, dataset=args.dataset, batch_size=args.batch_size,
        lr=args.lr, momentum=args.momentum, weight_decay=args.weight_decay,
        nesterov=args.nesterov, max_epochs=args.max_epochs,
        nsteps_update=args.nsteps_update, compressor=args.compressor,
        num_buckets=args.num_buckets,
        compute_dtype=args.compute_dtype,
        density=args.density, sigma_scale=args.sigma_scale,
        grad_clip=args.grad_clip, seed=args.seed,
        num_workers=len(jax.devices()),
        autotune=args.autotune,
        autotune_candidates=tuple(
            s for s in args.autotune_candidates.split(",") if s),
        autotune_trial_steps=args.autotune_trial_steps,
        autotune_retune_every=args.autotune_retune_every,
        autotune_journal=args.autotune_journal,
        resilience=args.resilience,
        resilience_strikes=args.resilience_strikes,
        resilience_abs_limit=args.resilience_abs_limit,
        resilience_journal=args.resilience_journal,
        resilience_feedback=args.resilience_feedback,
        resilience_feedback_window=args.resilience_feedback_window,
        resilience_feedback_signals=args.resilience_feedback_signals,
        resilience_feedback_cooldown=args.resilience_feedback_cooldown,
        resilience_density_backoff=args.resilience_density_backoff,
        resilience_near_ratio=args.resilience_near_ratio,
        resilience_backoff_steps=args.resilience_backoff_steps,
        resilience_backoff_factor=args.resilience_backoff_factor,
        resilience_backoff_max_level=args.resilience_backoff_max_level,
        resilience_clean_streak=args.resilience_clean_streak,
        obs=args.obs,
        obs_trace_on_anomaly=args.obs_trace_on_anomaly,
        obs_trace_steps=args.obs_trace_steps,
        obs_regress_key=args.obs_regress_key,
        obs_quality=args.obs_quality,
        obs_quality_every=args.obs_quality_every)
    slug = cfg.experiment_slug()
    # Observability and checkpoints are rank-0 work (the reference gates its
    # writer/checkpointer the same way, VGG/dl_trainer.py:614-616) — on a
    # shared filesystem every process writing the same paths corrupts them.
    is_rank0 = jax.process_index() == 0
    if args.obs and is_rank0:
        # non-rank-0 processes keep the bus with an in-memory journal
        # (tracer arming still works) but never write the shared file
        import dataclasses as _dc
        cfg = _dc.replace(
            cfg, obs_journal=(args.obs_journal or os.path.join(
                args.logdir, slug, "run_journal.jsonl")))
    logger = get_logger(
        "oktopk_tpu",
        os.path.join(args.logdir, slug, f"rank{jax.process_index()}.log"))
    logger.info("experiment %s on %d devices", slug, len(jax.devices()))
    logger.info("compile cache: %s", cache_dir)

    algo_cfg = OkTopkConfig(sigma_scale=args.sigma_scale,
                            wire_dtype=args.wire_dtype)
    if args.warmup_steps is not None:
        algo_cfg = algo_cfg.replace(warmup_steps=args.warmup_steps)

    model_kwargs = json.loads(args.model_kwargs) if args.model_kwargs else None
    trainer = Trainer(cfg, algo_cfg=algo_cfg, model_kwargs=model_kwargs)

    preempt = None
    if args.handle_preemption:
        from oktopk_tpu.train.preemption import (PreemptionHandler,
                                                 load_interrupted_state)
        preempt = PreemptionHandler()

    start_iter = 0
    if args.resume:
        from oktopk_tpu.train.checkpoint import restore_checkpoint
        # verifying resume: digest-checked against the sidecar manifest,
        # walking newest -> oldest past corrupt files, journalled on the
        # run's bus (ckpt_verify_failed / ckpt_restore)
        trainer.state, start_iter = restore_checkpoint(
            args.resume, trainer.state, bus=trainer.bus,
            force=args.ckpt_force)
        # re-arm the escalation ladder: strike counters + any active
        # per-bucket dense fallbacks resume with the train state
        trainer.restore_supervisor(args.resume)
        logger.info("resumed from %s at iter %d", args.resume, start_iter)
    elif args.handle_preemption:
        parked = load_interrupted_state(trainer.state)
        if parked is not None:
            trainer.state, start_iter = parked
            from oktopk_tpu.train.preemption import interrupted_state_path
            trainer.restore_supervisor(interrupted_state_path() + ".d")
            logger.info("resumed interrupted state at iter %d", start_iter)

    # global batch = per-worker batch * workers * accumulation
    global_bs = (args.batch_size * trainer.algo_cfg.num_workers
                 * args.nsteps_update)
    data_iter, meta = make_dataset(
        args.dataset, args.dnn, global_bs, path=args.data_dir,
        seed=args.seed, seq_len=args.seq_len,
        vocab=(model_kwargs or {}).get("vocab_size"))
    if meta.get("synthetic"):
        logger.warning("dataset %s not found on disk: using synthetic data",
                       args.dataset)

    iters_per_epoch = max(1, meta["num_examples"] // global_bs)
    total = args.max_iters or args.max_epochs * iters_per_epoch
    logger.info("training %d iterations (%d/epoch)", total, iters_per_epoch)

    from oktopk_tpu.utils.profiling import (MetricWriter, PhaseTimers,
                                            TraceWindow, device_memory_stats,
                                            attach, dump, span)
    rundir = os.path.join(args.logdir, slug)
    checkpointer = None
    if is_rank0 and args.ckpt_dir and args.ckpt_every and args.ckpt_async:
        from oktopk_tpu.train.durable import AsyncCheckpointer
        journal = (trainer.supervisor.journal
                   if trainer.supervisor is not None else None)
        checkpointer = AsyncCheckpointer(
            args.ckpt_dir, keep_last=args.ckpt_keep,
            journal=journal, bus=trainer.bus,
            on_failure=trainer.note_ckpt_failure)
    writer = MetricWriter(rundir) if is_rank0 else None
    timers = PhaseTimers(every=args.log_every) if args.phase_timers else None
    # attached here and not only inside train(): the checkpoint hand-off
    # between chunks is a span too, and the snapshot at the end reads it
    attach(timers)
    trace = (TraceWindow(os.path.join(rundir, "trace"), args.trace_at,
                         args.trace_steps) if args.trace_at and is_rank0
             else None)

    done = start_iter
    try:
        while done < total:
            if preempt is not None and preempt.should_stop():
                break
            chunk = min(total - done, iters_per_epoch)
            m = trainer.train(data_iter, chunk, log_every=args.log_every,
                              logger=logger, metric_writer=writer,
                              timers=timers, trace=trace, start_step=done,
                              should_stop=(preempt.should_stop
                                           if preempt else None))
            done = trainer.last_step if preempt is not None else done + chunk
            if not m:  # stopped before the first step of this chunk
                break
            from oktopk_tpu import settings
            if settings.PROFILING_GRAD and is_rank0:
                # gradient-stream snapshot (reference dumps raw .npy grads at
                # fixed iterations, VGG/allreducer.py:608-623): the residual
                # IS the un-transmitted gradient mass plus thresholds/counts.
                import numpy as _np
                ss = jax.device_get(trainer.state.sparse_state)
                dump_dir = os.path.join(rundir, "grad_dumps")
                os.makedirs(dump_dir, exist_ok=True)
                _np.savez_compressed(
                    os.path.join(dump_dir, f"iter_{done}.npz"),
                    residual=_np.asarray(ss.residual),
                    local_threshold=_np.asarray(ss.local_threshold),
                    global_threshold=_np.asarray(ss.global_threshold))
            mem = device_memory_stats()
            logger.info(
                "epoch done @ iter %d: loss %.4f vol/step %.0f hbm %.0fMiB",
                done, float(m["loss"]), float(m["comm_volume"]),
                mem.get("bytes_in_use", 0) / 2**20)
            if (is_rank0 and args.ckpt_dir and args.ckpt_every
                    and done % args.ckpt_every == 0):
                with span("oktopk/checkpoint", step=done):
                    if checkpointer is not None:
                        path = checkpointer.save(
                            trainer.state, done,
                            extra=trainer.supervisor_extra(),
                            qualified=trainer.checkpoint_qualified)
                    else:
                        from oktopk_tpu.train.checkpoint import (
                            save_checkpoint)
                        path = save_checkpoint(
                            args.ckpt_dir, trainer.state, done,
                            extra=trainer.supervisor_extra(),
                            qualified=trainer.checkpoint_qualified)
                        if args.ckpt_keep:
                            from oktopk_tpu.train.durable import (
                                apply_retention)
                            apply_retention(args.ckpt_dir,
                                            keep_last=args.ckpt_keep)
                    trainer.note_checkpoint(path, done)
    finally:
        if writer is not None:
            writer.close()
        if trace is not None:
            trace.close()
        if checkpointer is not None and preempt is None:
            # with a preemption handler the epilogue drains instead (an
            # async save in flight must publish whole before exit)
            checkpointer.close(timeout=300.0)
        if is_rank0:
            # what the run recorded about itself: set-up and loop spans,
            # compile seconds by step, the last steps' counters. Last, and
            # guarded: it fetches from the device, which may be what
            # failed, and must not hide the exception that ended training
            try:
                dump(os.path.join(rundir, "profile_snapshot.json"))
            except Exception:
                logger.exception("profile snapshot not written")
        attach(None)

    if preempt is not None:
        # park-state/requeue (or clear on success) — reference
        # main_bert.py:99-153, actually wired here.
        from oktopk_tpu.train.preemption import epilogue
        return epilogue(trainer.state, done, preempt, logger,
                        rank=jax.process_index(), completed=done >= total,
                        extra=trainer.supervisor_extra(),
                        checkpointer=checkpointer)
    return 0


if __name__ == "__main__":
    sys.exit(main())
