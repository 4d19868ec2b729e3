"""CLI driver for BERT pretraining (reference BERT/bert/main_bert.py:641-1100
with the bert_oktopk.sh flag surface: --dataparallel --compressor oktopk
--density 0.01, bs 8/worker, seq 128, BertAdam lr 2e-4 warmup-linear).

The reference's SLURM rendezvous (init_distrib_slurm, :159-203), stage-module
importlib machinery (:806-822) and shape-inference dry run (:838-868) are all
unnecessary here: one process drives the mesh, the model is a single Flax
module, and shapes are static.

Example:
    python -m oktopk_tpu.train.main_bert --model bert_base \\
        --compressor oktopk --density 0.01 --num-minibatches 1024
"""

from __future__ import annotations

import argparse
import os
import sys


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="bert_base",
                   choices=["bert_base", "bert_large", "bert_tiny"])
    p.add_argument("--batch-size", type=int, default=8,
                   help="per-worker microbatch (reference bs 8)")
    p.add_argument("--max-seq-length", type=int, default=None,
                   help="default: 128 (32 for bert_tiny)")
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--warmup-proportion", type=float, default=0.01)
    p.add_argument("--num-minibatches", type=int, default=1024)
    p.add_argument("--gradient-accumulation-steps", type=int, default=1)
    p.add_argument("--compressor", default="oktopk")
    p.add_argument("--compute-dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--wire-dtype", default="bfloat16",
                   choices=["bfloat16", "float32"],
                   help="sparse message VALUE dtype on the wire "
                        "(float32 = reference-exact uncompressed)")
    p.add_argument("--density", type=float, default=0.01)
    p.add_argument("--pipeline-stages", type=int, default=1,
                   help="pipeline depth: split the encoder over a "
                        "data x pipe mesh (reference staged models "
                        "BERT/bert/models/bert/depth=N + StageRuntime, "
                        "BERT/runtime.py:842); 1 = pure DP")
    p.add_argument("--num-microbatches", type=int, default=4,
                   help="GPipe microbatches per flush when pipelining")
    p.add_argument("--remat", action="store_true",
                   help="rematerialise stage activations in backward "
                        "(the reference's recompute mode, "
                        "BERT/runtime.py:546-558)")
    p.add_argument("--seq-shards", type=int, default=1,
                   help="sequence/context parallelism: shard the token "
                        "axis over a seq mesh with ring attention "
                        "(long-context extension; the reference has none, "
                        "SURVEY.md 5.7); 1 = off")
    p.add_argument("--seq-data-shards", type=int, default=1,
                   help="data axis of the composed data x seq mesh: "
                        "sparse-allreduce DP (any --compressor) riding "
                        "under sequence parallelism; 1 = pure seq mesh "
                        "(dense only)")
    p.add_argument("--expert-shards", type=int, default=1,
                   help="expert parallelism: Switch-style top-1 MoE FFNs "
                        "sharded over an expert mesh, GShard all_to_all "
                        "dispatch (extension; the reference has none, "
                        "SURVEY.md 2.3); 1 = off")
    p.add_argument("--num-experts", type=int, default=0,
                   help="experts per MoE layer (default: = expert-shards)")
    p.add_argument("--expert-data-shards", type=int, default=1,
                   help="data axis of the composed data x expert mesh: "
                        "sparse-allreduce DP (any --compressor) riding "
                        "with the MoE dispatch; 1 = pure expert mesh "
                        "(dense only)")
    p.add_argument("--capacity-factor", type=float, default=1.25,
                   help="MoE token capacity per expert, as a multiple of "
                        "the even-routing share")
    p.add_argument("--data-dir", default="./data")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--fake-devices", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--resume", default=None)
    p.add_argument("--handle-preemption", action="store_true",
                   help="graceful preempt: checkpoint + requeue on SIGUSR1 "
                        "(reference BERT/bert/main_bert.py:73-203)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.max_seq_length is None:
        args.max_seq_length = 32 if args.model == "bert_tiny" else 128
    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}")
    import jax
    if args.fake_devices:
        jax.config.update("jax_platforms", "cpu")
    else:
        # Multi-host rendezvous (reference init_distrib_slurm,
        # BERT/bert/main_bert.py:159-203) — no-op for single-process jobs.
        from oktopk_tpu.launch import maybe_initialize
        maybe_initialize()

    from oktopk_tpu.config import OkTopkConfig, TrainConfig
    from oktopk_tpu.data import make_dataset
    from oktopk_tpu.train.trainer import Trainer
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache
    from oktopk_tpu.utils.logging import get_logger

    ensure_compile_cache()

    if args.pipeline_stages > 1:
        return run_pipeline(args)
    if args.seq_shards > 1:
        return run_seq_parallel(args)
    if args.seq_data_shards > 1:
        raise SystemExit("--seq-data-shards composes with sequence "
                         "parallelism — it needs --seq-shards > 1 "
                         "(plain sparse DP is the default path)")
    if args.expert_shards > 1:
        return run_expert_parallel(args)

    num_workers = len(jax.devices())
    cfg = TrainConfig(
        dnn=args.model, dataset="wikipedia", batch_size=args.batch_size,
        lr=args.lr, compressor=args.compressor, density=args.density,
        nsteps_update=args.gradient_accumulation_steps, seed=args.seed,
        warmup_proportion=args.warmup_proportion,
        compute_dtype=args.compute_dtype,
        total_steps=args.num_minibatches, num_workers=num_workers)
    logger = get_logger("oktopk_tpu.bert")
    logger.info("BERT pretrain: %s on %d devices, compressor=%s density=%g",
                args.model, num_workers, args.compressor, args.density)

    algo_cfg = _bert_algo_cfg(args)

    trainer = Trainer(cfg, algo_cfg=algo_cfg)
    preempt = None
    if args.handle_preemption:
        from oktopk_tpu.train.preemption import PreemptionHandler
        preempt = PreemptionHandler()
    start = 0
    if args.resume:
        from oktopk_tpu.train.checkpoint import restore_checkpoint
        trainer.state, start = restore_checkpoint(args.resume, trainer.state)
        logger.info("resumed at step %d", start)
    elif args.handle_preemption:
        from oktopk_tpu.train.preemption import load_interrupted_state
        parked = load_interrupted_state(trainer.state)
        if parked is not None:
            trainer.state, start = parked
            logger.info("resumed interrupted state at step %d", start)

    global_bs = (args.batch_size * num_workers
                 * args.gradient_accumulation_steps)
    data_iter, meta = make_dataset("wikipedia", args.model, global_bs,
                                   path=args.data_dir, seed=args.seed,
                                   seq_len=args.max_seq_length)
    if meta.get("synthetic"):
        logger.warning("Wikipedia shards not found: synthetic MLM/NSP data")

    remaining = max(0, args.num_minibatches - start)
    m = trainer.train(data_iter, remaining,
                      log_every=args.log_every, logger=logger,
                      start_step=start,
                      should_stop=(preempt.should_stop if preempt else None))
    if preempt is not None:
        from oktopk_tpu.train.preemption import epilogue
        rc = epilogue(trainer.state, trainer.last_step, preempt, logger,
                      rank=jax.process_index(),
                      completed=trainer.last_step >= args.num_minibatches)
        if rc:
            return rc
    if m:
        logger.info("done: loss %.4f comm volume/step %.0f elems",
                    float(m["loss"]), float(m["comm_volume"]))
    # rank-0 writes only (reference saves via rank_in_stage==0,
    # BERT/bert/main_bert.py:207-219): shared-filesystem safety.
    if args.ckpt_dir and jax.process_index() == 0:
        from oktopk_tpu.train.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt_dir, trainer.state, args.num_minibatches)
    return 0


def run_pipeline(args):
    """Pipeline-parallel pretraining path: data x pipe mesh, staged encoder
    (reference StageRuntime GPipe-with-flushes mode, BERT/runtime.py:842)."""
    import jax
    import numpy as np

    from oktopk_tpu.models.bert import BertConfig
    from oktopk_tpu.models.bert_staged import StagedBertPretrain
    from oktopk_tpu.optim import bert_adam
    from oktopk_tpu.parallel.bert_pipeline import (
        build_pipeline_train_step, init_pipeline_opt_state,
        make_pipeline_mesh)
    from oktopk_tpu.data import make_dataset
    from oktopk_tpu.utils.logging import get_logger

    logger = get_logger("oktopk_tpu.bert")
    cfg = {"bert_base": BertConfig.base, "bert_large": BertConfig.large,
           "bert_tiny": BertConfig.tiny}[args.model]()
    staged = StagedBertPretrain(cfg, args.pipeline_stages)
    mesh = make_pipeline_mesh(args.pipeline_stages)
    dp = mesh.shape["data"]
    logger.info("pipeline BERT: %s over mesh data=%d x pipe=%d, M=%d",
                args.model, dp, args.pipeline_stages, args.num_microbatches)

    params = staged.init(jax.random.PRNGKey(args.seed), 2,
                         args.max_seq_length)
    params = _maybe_warm_start(
        args, logger, {"params": params, "model_state": {}})["params"]
    stack, shared = staged.split(params)
    opt = bert_adam(lr=args.lr, warmup=args.warmup_proportion,
                    t_total=args.num_minibatches)

    sparse = args.compressor != "dense"
    if sparse:
        # composed sparse DP x pipeline: per-data-rank replica layout
        # (the architecture the reference shipped disabled — PipeDream
        # stages + per-stage-group sparse allreduce, SURVEY.md 2.3)
        import jax.numpy as jnp

        from oktopk_tpu.parallel.bert_pipeline import (
            build_pipeline_sparse_train_step, init_pipeline_sparse_states)
        from oktopk_tpu.parallel.bert_seq import stack_replicas
        if dp < 2:
            raise SystemExit("sparse pipeline composition needs a data "
                             "axis (more devices than --pipeline-stages) "
                             "— or pass --compressor dense")
        acfg = _bert_algo_cfg(args, density=args.density)
        stage_ss, shared_ss = init_pipeline_sparse_states(
            stack, shared, acfg, dp)
        opt_states = (stack_replicas(jax.vmap(opt.init)(stack), dp),
                      stack_replicas(opt.init(shared), dp))
        stack = stack_replicas(stack, dp)
        shared = stack_replicas(shared, dp)
        sstates = (stage_ss, shared_ss)
        step0 = build_pipeline_sparse_train_step(
            staged, mesh, num_microbatches=args.num_microbatches,
            optimizer=opt, algo_cfg=acfg, compressor=args.compressor,
            warmup=False, remat=args.remat)
        logger.info("sparse pipeline: compressor=%s density=%g",
                    args.compressor, args.density)
    else:
        opt_states = init_pipeline_opt_state(opt, stack, shared)
        step0 = build_pipeline_train_step(
            staged, mesh, num_microbatches=args.num_microbatches,
            optimizer=opt, remat=args.remat)

    global_bs = args.batch_size * dp * args.num_microbatches
    data_iter, meta = make_dataset("wikipedia", args.model, global_bs,
                                   path=args.data_dir, seed=args.seed,
                                   seq_len=args.max_seq_length)
    if meta.get("synthetic"):
        logger.warning("Wikipedia shards not found: synthetic MLM/NSP data")

    rng = jax.random.PRNGKey(args.seed + 1)
    import time
    t0 = time.time()
    for i in range(args.num_minibatches):
        rng, sub = jax.random.split(rng)
        if sparse:
            (stack, shared), sstates, opt_states, m = step0(
                (stack, shared), sstates, opt_states,
                next(data_iter), sub)
        else:
            stack, shared, opt_states, m = step0(stack, shared, opt_states,
                                                 next(data_iter), sub)
        if (i + 1) % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            logger.info("iter %d loss %.4f %.3fs/it", i + 1,
                        float(m["loss"]), dt)
            t0 = time.time()
    if args.ckpt_dir and jax.process_index() == 0:
        from oktopk_tpu.train.checkpoint import save_checkpoint
        if sparse:   # row 0 of the replicas is the canonical copy
            stack_c = jax.tree.map(lambda x: x[0], stack)
            shared_c = jax.tree.map(lambda x: x[0], shared)
        else:
            stack_c, shared_c = stack, shared
        save_checkpoint(args.ckpt_dir,
                        {"params": staged.merge(stack_c, shared_c),
                         "model_state": {}}, args.num_minibatches)
        logger.info("saved single-module-layout checkpoint to %s",
                    args.ckpt_dir)
    return 0


def _bert_algo_cfg(args, **kw):
    """The BERT sparse-allreduce tuning: dense warmup disabled (reference
    BERT/bert/allreducer.py:355), retuned cadences/scales (:359-361,
    :188-190). One definition for every BERT path."""
    from oktopk_tpu.config import OkTopkConfig
    return OkTopkConfig(
        warmup_steps=0, local_recompute_every=128,
        global_recompute_every=128, repartition_every=64,
        local_adapt_scale=1.025, global_adapt_scale=1.036,
        wire_dtype=args.wire_dtype, **kw)


def _maybe_warm_start(args, logger, template):
    """Params-only warm start for the extension paths: restore the saved
    payload shape into ``template`` and return it. Optimizer / sparse
    state start fresh (these paths checkpoint the canonical single-module
    or moe payload, not the full replica carry); the DP path keeps its
    full-state resume."""
    if not args.resume:
        return template
    import jax
    import numpy as np

    from oktopk_tpu.train.checkpoint import restore_checkpoint
    restored, rstep = restore_checkpoint(args.resume, template)
    # restore_checkpoint keeps template leaves for missing payload keys,
    # so a layout mismatch (e.g. a DP {"params": ...} checkpoint fed to
    # the moe path) would silently train from random init; and flax
    # accepts wrong-shaped leaves silently. Validate both.
    changed = False
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(template),
            jax.tree_util.tree_leaves_with_path(restored)):
        if np.shape(a) != np.shape(b):
            raise SystemExit(
                f"--resume leaf {jax.tree_util.keystr(pa)} has shape "
                f"{np.shape(b)} but this model expects {np.shape(a)} "
                f"(wrong --model for the checkpoint?)")
        if not changed and not np.array_equal(np.asarray(a),
                                              np.asarray(b)):
            changed = True
    if not changed:
        raise SystemExit(
            f"--resume {args.resume} restored nothing — its payload "
            f"layout does not match this path's checkpoint format")
    logger.info("warm-started from %s (saved at step %d; optimizer and "
                "sparse state start fresh)", args.resume, rstep)
    return restored


def _pretrain_loop(args, logger, step_fn, params, opt_state, global_bs,
                   checkpoint_payload):
    """Shared dataset/loop/log/checkpoint tail of the whole-model parallel
    paths (seq, expert): ``step_fn(params, opt_state, batch) -> (params,
    opt_state, loss)``; ``checkpoint_payload(params) -> dict`` shapes what
    rank 0 saves."""
    import time

    import jax

    from oktopk_tpu.data import make_dataset

    data_iter, meta = make_dataset("wikipedia", args.model, global_bs,
                                   path=args.data_dir, seed=args.seed,
                                   seq_len=args.max_seq_length)
    if meta.get("synthetic"):
        logger.warning("Wikipedia shards not found: synthetic MLM/NSP data")

    t0 = time.time()
    for i in range(args.num_minibatches):
        params, opt_state, loss = step_fn(params, opt_state,
                                          next(data_iter))
        if (i + 1) % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            logger.info("iter %d loss %.4f %.3fs/it", i + 1, float(loss),
                        dt)
            t0 = time.time()
    if args.ckpt_dir and jax.process_index() == 0:
        from oktopk_tpu.train.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt_dir, checkpoint_payload(params),
                        args.num_minibatches)
    return params


def run_seq_parallel(args):
    """Sequence-parallel pretraining: token axis sharded over a seq mesh
    with ring attention (long-context path; see parallel/bert_seq.py)."""
    import jax

    from oktopk_tpu.data import make_dataset
    from oktopk_tpu.models.bert import BertConfig, BertForPreTraining
    from oktopk_tpu.optim import bert_adam
    from oktopk_tpu.parallel.bert_seq import (build_seq_train_step,
                                              make_seq_mesh)
    from oktopk_tpu.utils.logging import get_logger
    import jax.numpy as jnp

    logger = get_logger("oktopk_tpu.bert")
    dp = args.seq_data_shards
    if args.max_seq_length % args.seq_shards:
        raise SystemExit("--max-seq-length must divide by --seq-shards")
    if args.compressor != "dense" and dp <= 1:
        raise SystemExit(
            "sparse collectives over a pure seq mesh have no data axis to "
            "reduce over — add --seq-data-shards N for the composed "
            "data x seq mesh, or pass --compressor dense")
    if args.gradient_accumulation_steps != 1 and not (
            dp > 1 and args.compressor != "dense"):
        raise SystemExit("--gradient-accumulation-steps on the seq path "
                         "needs the composed sparse form "
                         "(--seq-data-shards N, sparse --compressor)")
    import dataclasses
    dtype = jnp.dtype(args.compute_dtype)
    cfg = {"bert_base": BertConfig.base, "bert_large": BertConfig.large,
           "bert_tiny": BertConfig.tiny}[args.model](dtype=dtype)
    if cfg.max_position < args.max_seq_length:
        # long-context runs need position rows for every global position —
        # the embedding gather clamps silently under jit otherwise
        cfg = dataclasses.replace(cfg, max_position=args.max_seq_length)
    mesh = make_seq_mesh(args.seq_shards, data_size=dp)
    logger.info("seq-parallel BERT: %s, T=%d over %d shards "
                "(T/P=%d per chip)%s", args.model, args.max_seq_length,
                args.seq_shards, args.max_seq_length // args.seq_shards,
                f", data axis dp={dp} compressor={args.compressor}"
                if dp > 1 else "")

    ex = jnp.zeros((2, args.max_seq_length), jnp.int32)
    rng = jax.random.PRNGKey(args.seed)
    params = BertForPreTraining(cfg).init(
        {"params": rng, "dropout": rng}, ex, ex, jnp.ones_like(ex),
        train=False)["params"]
    params = _maybe_warm_start(
        args, logger, {"params": params, "model_state": {}})["params"]
    opt = bert_adam(lr=args.lr, warmup=args.warmup_proportion,
                    t_total=args.num_minibatches)

    if dp > 1 and args.compressor != "dense":
        # composed sparse DP x seq: per-data-rank replica layout
        from oktopk_tpu.collectives.state import init_state
        from oktopk_tpu.config import OkTopkConfig
        from oktopk_tpu.parallel.bert_seq import (
            build_seq_sparse_train_step, stack_replicas)

        n = sum(x.size for x in jax.tree.leaves(params))
        acfg = _bert_algo_cfg(args, n=n, num_workers=dp,
                              density=args.density)
        sstep = build_seq_sparse_train_step(
            cfg, mesh, opt, acfg, compressor=args.compressor,
            warmup=False,
            accum_steps=args.gradient_accumulation_steps)
        carry = (stack_replicas(params, dp),
                 stack_replicas(init_state(acfg), dp))
        opt_state = stack_replicas(opt.init(params), dp)

        def step(ps, opt_state, batch):
            p, ss = ps
            p, ss, opt_state, loss = sstep(p, ss, opt_state, batch)
            return (p, ss), opt_state, loss

        _pretrain_loop(
            args, logger, step, carry, opt_state,
            # --batch-size is per data rank per microstep
            args.batch_size * dp * args.gradient_accumulation_steps,
            # row 0 of the replicas IS the single-module layout
            lambda ps: {"params": jax.tree.map(lambda x: x[0], ps[0]),
                        "model_state": {}})
        return 0

    opt_state = opt.init(params)
    step = build_seq_train_step(cfg, mesh, opt)
    _pretrain_loop(args, logger, step, params, opt_state,
                   args.batch_size * dp,
                   lambda p: {"params": p, "model_state": {}})
    return 0


def run_expert_parallel(args):
    """Expert-parallel MoE pretraining: Switch-style top-1 MoE FFNs with
    GShard all_to_all dispatch over an expert mesh; batch sharded on the
    same axis (see parallel/bert_moe.py)."""
    import jax
    import jax.numpy as jnp

    from oktopk_tpu.models.bert import BertConfig, BertForPreTraining
    from oktopk_tpu.optim import bert_adam
    from oktopk_tpu.parallel.bert_moe import (MoEConfig,
                                              build_moe_train_step,
                                              experts_from_dense,
                                              make_moe_mesh)
    from oktopk_tpu.utils.logging import get_logger

    logger = get_logger("oktopk_tpu.bert")
    E = args.num_experts or args.expert_shards
    if E % args.expert_shards:
        raise SystemExit("--num-experts must divide by --expert-shards")
    dpx = args.expert_data_shards
    if args.compressor != "dense" and dpx <= 1:
        raise SystemExit(
            "sparse collectives over a pure expert mesh have no data axis "
            "to reduce over — add --expert-data-shards N for the composed "
            "data x expert mesh, or pass --compressor dense")
    if args.gradient_accumulation_steps != 1:
        raise SystemExit("--gradient-accumulation-steps is not wired into "
                         "the expert-parallel path yet")
    dtype = jnp.dtype(args.compute_dtype)
    cfg = {"bert_base": BertConfig.base, "bert_large": BertConfig.large,
           "bert_tiny": BertConfig.tiny}[args.model](dtype=dtype)
    mcfg = MoEConfig(num_experts=E,
                     capacity_factor=args.capacity_factor)
    mesh = make_moe_mesh(args.expert_shards, data_size=dpx)
    logger.info("expert-parallel MoE BERT: %s, %d experts over %d shards "
                "(cap factor %.2f)%s", args.model, E, args.expert_shards,
                args.capacity_factor,
                f", data axis dp={dpx} compressor={args.compressor}"
                if dpx > 1 else "")

    ex = jnp.zeros((2, args.max_seq_length), jnp.int32)
    rng = jax.random.PRNGKey(args.seed)
    dense_params = BertForPreTraining(cfg).init(
        {"params": rng, "dropout": rng}, ex, ex, jnp.ones_like(ex),
        train=False)["params"]
    # gate_scale > 0: a zero router ties every token to expert 0 and the
    # capacity bound then drops most of the batch (bert_moe.py docstring)
    params = experts_from_dense(dense_params, E, gate_scale=0.02,
                                seed=args.seed)
    restored = _maybe_warm_start(
        args, logger, {"moe_params": {"layers": params[0],
                                      "shared": params[1]},
                       "model_state": {}})
    params = (restored["moe_params"]["layers"],
              restored["moe_params"]["shared"])
    opt = bert_adam(lr=args.lr, warmup=args.warmup_proportion,
                    t_total=args.num_minibatches)
    # --batch-size is per-worker (as in the DP/pipeline paths); the MoE
    # batch is sharded over the (data x) expert axes, so request global
    global_bs = args.batch_size * args.expert_shards * dpx

    if dpx > 1 and args.compressor != "dense":
        # composed sparse DP x expert: per-data-rank replica layout
        from oktopk_tpu.parallel.bert_moe import (
            build_moe_sparse_train_step, init_moe_sparse_opt,
            init_moe_sparse_states)
        from oktopk_tpu.parallel.bert_seq import stack_replicas
        moe, shared = params
        acfg = _bert_algo_cfg(args, density=args.density)
        sstep = build_moe_sparse_train_step(
            cfg, mcfg, mesh, opt, acfg, compressor=args.compressor,
            warmup=False)
        carry = ((stack_replicas(moe, dpx), stack_replicas(shared, dpx)),
                 init_moe_sparse_states(moe, shared, acfg, dpx,
                                        args.expert_shards))
        opt_state = init_moe_sparse_opt(opt, moe, shared, dpx)

        def step_fn(ps, opt_st, batch):
            pr, ss = ps
            pr, ss, opt_st, m = sstep(pr, ss, opt_st, batch)
            return (pr, ss), opt_st, m["loss"]

        _pretrain_loop(
            args, logger, step_fn, carry, opt_state, global_bs,
            lambda ps: {"moe_params": {
                "layers": jax.tree.map(lambda x: x[0], ps[0][0]),
                "shared": jax.tree.map(lambda x: x[0], ps[0][1])},
                "model_state": {}})
        return 0

    opt_state = opt.init(params)
    step = build_moe_train_step(cfg, mcfg, mesh, opt)
    # MoE params cannot collapse to the single-module layout once the
    # experts diverge — save them under a distinct key so nothing mistakes
    # the tuple for BertForPreTraining params
    _pretrain_loop(args, logger, step, params, opt_state, global_bs,
                   lambda p: {"moe_params": {"layers": p[0],
                                             "shared": p[1]},
                              "model_state": {}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
