"""Ok-Topk LSTM quality-gap ablation (VERDICT r3 item #3).

Round-3 evidence (logs/convergence/lstm_tiny_*.jsonl) shows oktopk is the
worst sparse algorithm on the recurrent workload: best eval 0.732 vs
topkA 0.465 — but at 245k elems/step vs topkA's 788k, i.e. 3.2x less
traffic. This harness isolates WHY, one knob at a time, on the exact
round-3 recipe (lstm_tiny, 8-worker mesh, SGD lr 5.0, 1000 steps,
200-step dense warmup, density 0.05):

- density 0.10 / 0.16:   oktopk applies ~k global winners per step where
  topkA applies the up-to-P*k union of local selections (reference
  VGG/allreducer.py:819-846 vs :1171-1217), so at equal nominal density
  oktopk moves ~3x less information. d=0.16 is the ISO-VOLUME point:
  ~5k scalars/step * 0.16 * n ~ topkA@0.05's 788k.
- warmup 400:            the recurrent family is warmup-sensitive
  (docs/PERF.md:190-195); test whether more dense steps close the gap.
- band@k:                the controller band [2k/3, k] admits sustained
  ~0.7k under-selection (observed global_k 30-41k vs k=49280); target
  [k, 1.5k] instead.
- drift_ema 0.5:         damp the drift estimate — recurrent gradient
  scale is spiky (grad_norm 0.17->1.1 within 20 steps in the r3 logs),
  so a fully-adopted per-window rate may overshoot.
- recompute 8:           4x more frequent exact threshold recomputes, in
  case recurrent-scale drift outruns the predictor between windows.

Each variant writes logs/ablation/lstm_tiny_oktopk_<name>.jsonl in the
convergence-log schema, so the same analysis tooling reads both.

Usage: python scripts/ablate_lstm.py [--variants d010,d016,...] [--steps 1000]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name -> (TrainConfig overrides, OkTopkConfig overrides)
VARIANTS = {
    "base":    ({}, {}),
    "d010":    ({"density": 0.10}, {}),
    "d016":    ({"density": 0.16}, {}),
    "w400":    ({}, {"warmup_steps": 400}),
    # setpoints ride along: band_lo=1.0 forces the exact-k operating
    # point, so the sub-k r5 defaults would violate band_lo <= target
    "bandk":   ({}, {"band_lo": 1.0, "band_hi": 1.5, "band_hi_global": 1.5,
                     "local_k_target": 1.0, "global_k_target": 1.0}),
    "drift05": ({}, {"drift_ema": 0.5}),
    "rec8":    ({}, {"local_recompute_every": 8, "global_recompute_every": 8}),
    # the two knobs that moved the needle, combined (warmup is free —
    # same steady-state volume; d016 is the iso-volume point vs topkA)
    "w400d016": ({"density": 0.16}, {"warmup_steps": 400}),
    "w400d010": ({"density": 0.10}, {"warmup_steps": 400}),
}


def run_variant(name: str, steps: int, mesh, out_dir: str):
    import json
    import time

    import numpy as np

    from oktopk_tpu.config import OkTopkConfig, TrainConfig
    from oktopk_tpu.data.synthetic import finite_pool_iterator
    from oktopk_tpu.train.trainer import Trainer

    tr_over, algo_over = VARIANTS[name]
    cfg = TrainConfig(dnn="lstm_tiny", dataset="synthetic-teacher",
                      batch_size=8, lr=5.0, compressor="oktopk",
                      density=tr_over.get("density", 0.05))
    algo_kw = {"warmup_steps": 200}
    algo_kw.update(algo_over)
    trainer = Trainer(cfg, mesh=mesh, algo_cfg=OkTopkConfig(**algo_kw))
    P = trainer.cfg.num_workers
    it = finite_pool_iterator("lstm_tiny", 8 * P, seed=7)
    eval_batch = next(it)

    path = os.path.join(out_dir, f"lstm_tiny_oktopk_{name}.jsonl")
    t0 = time.time()
    with open(path, "w") as f:
        header = {"model": "lstm_tiny", "compressor": "oktopk",
                  "variant": name, "steps": steps, "workers": P,
                  "density": cfg.density, "lr": cfg.lr, "batch_size": 8,
                  "n_params": trainer.algo_cfg.n,
                  "overrides": {**tr_over, **algo_kw}}
        f.write(json.dumps(header) + "\n")
        for i in range(steps):
            m = trainer.train_step(next(it))
            if (i + 1) % 10 == 0 or i == 0 or i + 1 == steps:
                rec = {"step": i + 1, "loss": float(m["loss"]),
                       "comm_volume": float(m["comm_volume"])}
                if (i + 1) % 50 == 0 or i + 1 == steps:
                    em = trainer.eval_step(eval_batch)
                    rec.update({f"eval_{k}": float(np.asarray(v))
                                for k, v in em.items()})
                for k in ("local_k", "global_k", "grad_norm",
                          "grad_nonfinite"):
                    if k in m:
                        rec[k] = float(np.asarray(m[k]).mean())
                f.write(json.dumps(rec) + "\n")
                f.flush()
    print(f"[ablate] {name}: final loss {float(m['loss']):.4f} "
          f"({time.time()-t0:.0f}s) -> {path}", flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--variants", default=",".join(k for k in VARIANTS
                                                  if k != "base"))
    p.add_argument("--steps", type=int, default=1000)
    p.add_argument("--workers", type=int, default=8)
    p.add_argument("--out", default="logs/ablation")
    args = p.parse_args()

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.workers}")
    import jax
    jax.config.update("jax_platforms", "cpu")

    from oktopk_tpu.comm.mesh import get_mesh

    mesh = get_mesh((args.workers,), ("data",))
    os.makedirs(args.out, exist_ok=True)
    for name in args.variants.split(","):
        run_variant(name, args.steps, mesh, args.out)


if __name__ == "__main__":
    main()
