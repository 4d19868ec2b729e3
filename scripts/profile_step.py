"""Step-phase breakdown: where does a VGG-16 oktopk train step spend time?

The reference answers this with per-phase wall-clock dicts inside its
allreducer thread (_merge/_compression/_allreduce/... timers,
VGG/allreducer.py:256-262,379-439). Under XLA the phases fuse into one
compiled program, so the breakdown comes from timing *separately compiled*
subprograms on the same data instead:

  fwd_bwd      — loss + gradient only (the pure model compute path)
  select       — the full sparse allreduce on a same-sized flat gradient
                 (threshold + pack + exchange + gather + scatter)
  threshold    — just the exact k-th-value recompute (count-bisection)
  fused_select — the single-sweep selection front-end of
                 ops/fused_select.py (portable reference twin on CPU —
                 the interpreter at real n takes minutes — the Pallas
                 kernel on TPU), vs its separate-pass equivalent `pack`
  pack         — just the fixed-capacity selection/compaction
  full         — the actual fused train step (what bench.py times)

full < fwd_bwd + select is expected (XLA overlaps/fuses); a full that is
dominated by `select`'s components reproduces the round-2 diagnosis
(selection-bound step), and the Pallas-vs-portable delta is read directly
off `pack`.

Writes one JSON line (also to --json PATH for obs/regress.py baselines);
run on the real chip for BENCH profile notes, or on CPU for smoke.
Usage:  python scripts/profile_step.py [--iters 10] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _med_ms(fn, sync, iters, timers=None, name=None):
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        sync(fn())
        dt = time.perf_counter() - t0
        ts.append(dt * 1e3)
        if timers is not None and name:
            timers.add(name, dt)
    return statistics.median(ts)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dnn", default="vgg16",
                    help="model for the step probes (mnistnet for CPU smoke)")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--density", type=float, default=0.02)
    ap.add_argument("--use-pallas", default=None,
                    choices=["true", "false"],
                    help="default: resolve from backend")
    ap.add_argument("--platform", default=None,
                    help="jax platform override (e.g. cpu)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the profile dict to PATH as JSON "
                         "(machine-readable; feedable to obs/regress.py)")
    args = ap.parse_args()

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np

    from oktopk_tpu.collectives.api import batched_init_state, \
        build_allreduce_step
    from oktopk_tpu.comm.mesh import get_mesh
    from oktopk_tpu.config import OkTopkConfig, TrainConfig
    from oktopk_tpu.data.synthetic import synthetic_batch
    from oktopk_tpu.ops.compaction import resolve_use_pallas
    from oktopk_tpu.ops.fused_select import (
        fused_select_pallas,
        fused_select_reference,
    )
    from oktopk_tpu.ops.select import select_by_threshold
    from oktopk_tpu.ops.topk import k2threshold_method
    from oktopk_tpu.train.trainer import Trainer

    dev = jax.devices()[0]
    mesh = get_mesh((1,), ("data",), devices=[dev])
    rng = np.random.RandomState(0)
    batch = jax.device_put(synthetic_batch(args.dnn, args.batch_size, rng))

    def sync(x):
        jax.tree.map(lambda a: np.asarray(a), x)

    # host-phase stats ride along: every timed sample also lands in a
    # PhaseTimers so --json carries count/min/max/p50/p95 per probe,
    # comparable against the device anatomy in scripts/obs_report.py
    from oktopk_tpu.utils.profiling import PhaseTimers
    timers = PhaseTimers(every=0)

    def med(fn, key):
        return _med_ms(fn, sync, args.iters, timers=timers,
                       name=key[:-3] if key.endswith("_ms") else key)

    out = {"device": dev.platform, "iters": args.iters}

    # --- full fused train step + fwd/bwd-only (dense optimizer ~ compute)
    for comp, key in (("oktopk", "full_ms"), ("dense", "fwd_bwd_dense_ms")):
        cfg = TrainConfig(dnn=args.dnn, dataset="cifar10",
                          batch_size=args.batch_size,
                          lr=0.1, compressor=comp, density=args.density,
                          num_workers=1)
        tr = Trainer(cfg, mesh=mesh, warmup=False)
        fn = lambda tr=tr: tr.train_step(batch)
        _med_ms(fn, sync, 2)
        out[key] = med(fn, key)
        n = tr.algo_cfg.n

    # --- isolated sparse-allreduce on a same-sized gradient
    acfg = OkTopkConfig(n=n, num_workers=1, density=args.density,
                        warmup_steps=0)
    if args.use_pallas is not None:
        acfg = acfg.replace(use_pallas=args.use_pallas == "true")
    acfg = resolve_use_pallas(acfg, mesh)
    out["use_pallas"] = bool(acfg.use_pallas)
    step = build_allreduce_step("oktopk", acfg, mesh, warmup=False)
    g = jax.device_put(jnp.asarray(rng.randn(1, n).astype(np.float32)))

    # The timed loop re-uses one state, freezing the step counter — pin it
    # to an exact-recompute step (the branch that pays the threshold
    # method; predicted steps never call it). A profile loop that re-used
    # one state at step 1 would only ever time the predicted branch. This
    # is also why the step builder's donate_state stays off here: a
    # donated state is consumed by the first timed call.
    import dataclasses

    _, st = step(g, batched_init_state(acfg))
    state = dataclasses.replace(
        st, step=jnp.zeros_like(st.step) + acfg.local_recompute_every)
    out["select_ms"] = med(lambda: step(g, state)[0], "select_ms")

    # --- components: the exact threshold, and the pack
    k = acfg.k
    gf = g[0]
    thr_fn = jax.jit(lambda x: k2threshold_method(jnp.abs(x), k,
                                                  acfg.threshold_method,
                                                  acfg.bisect_iters))
    sync(thr_fn(gf))
    out["threshold_ms"] = med(lambda: thr_fn(gf), "threshold_ms")
    t = thr_fn(gf)

    pk = jax.jit(lambda x: select_by_threshold(
        x, t, acfg.cap_gather, use_pallas=bool(acfg.use_pallas)))
    sync(pk(gf))
    out["pack_ms"] = med(lambda: pk(gf), "pack_ms")

    # --- the fused single-sweep front-end (acc + stage + counts).
    # The Pallas interpreter at real n is minutes-slow, so off-TPU the
    # probe times the portable semantics twin — the XLA-fused equivalent
    # of the separate passes it replaces; the kernel itself is timed on
    # the chip.
    res = jax.device_put(jnp.zeros_like(gf))
    bnd = jnp.asarray([0, n], jnp.int32)
    tp = t * acfg.probe_ratio
    if dev.platform == "tpu":
        fs = jax.jit(lambda x, r: fused_select_pallas(
            x, r, t, tp, bnd, 1, acfg.cap_pair, interpret=False))
        out["fused_select_backend"] = "pallas"
    else:
        fs = jax.jit(lambda x, r: fused_select_reference(
            x, r, t, tp, bnd, 1, acfg.cap_pair))
        out["fused_select_backend"] = "reference"
    sync(fs(gf, res))
    out["fused_select_ms"] = med(lambda: fs(gf, res), "fused_select_ms")
    out["threshold_method"] = acfg.threshold_method

    out["host_phases"] = {
        name: {k3: round(v3, 4) for k3, v3 in stats.items()}
        for name, stats in timers.summary().items()}
    out = {k2: (round(v, 3) if isinstance(v, float) else v)
           for k2, v in out.items()}
    print("PROFILE " + json.dumps(out))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
