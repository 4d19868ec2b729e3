"""CTC/speech convergence probe: the last workload-family gap (VERDICT r3).

The reference trains DeepSpeech on AN4 with warp-ctc and evaluates WER in
its test loop (LSTM/dl_trainer.py:420-446, VGG/dl_trainer.py:743-762);
logs/convergence/ carried CNN, BERT and PTB-LSTM rows but nothing
exercised `optax.ctc_loss` training end-to-end. This harness runs
`lstman4_tiny` (2x128 summed-bidirectional DeepSpeech) on the tone-coded
synthetic AN4 pipeline (data/synthetic.py: each character renders as ~8
frames of energy in its own frequency band — a real alignment task, so
greedy-decoded WER is a real learning signal) and writes
logs/convergence/lstman4_tiny_<compressor>.jsonl with eval_wer/eval_cer
columns alongside loss and comm volume.

Sized for the 1-core virtual-mesh box: t=101-frame spectrograms, batch
4/worker, a couple hundred steps. Gradient clipping follows the reference
LSTM driver (LSTM/main_trainer.py:94-99).

Usage: python scripts/ctc_convergence.py [--compressors oktopk,dense,...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SEQ_LEN = 101          # spectrogram frames (downsampled ~2x by the frontend)


def run_one(comp: str, steps: int, mesh, density: float, lr: float,
            grad_clip: float, warmup_steps: int, out_dir: str,
            batch_size: int = 4):
    from oktopk_tpu.config import OkTopkConfig, TrainConfig
    from oktopk_tpu.data.synthetic import finite_pool_iterator
    from oktopk_tpu.train.trainer import Trainer

    cfg = TrainConfig(dnn="lstman4_tiny", dataset="synthetic",
                      batch_size=batch_size, lr=lr, compressor=comp,
                      density=density, grad_clip=grad_clip)
    trainer = Trainer(cfg, mesh=mesh,
                      algo_cfg=OkTopkConfig(warmup_steps=warmup_steps))
    P = trainer.cfg.num_workers
    it = finite_pool_iterator("lstman4_tiny", batch_size * P,
                              num_examples=max(128, batch_size * P),
                              seed=7, seq_len=SEQ_LEN)
    eval_batch = next(it)

    path = os.path.join(out_dir, f"lstman4_tiny_{comp}.jsonl")
    t0 = time.time()
    with open(path, "w") as f:
        header = {"model": "lstman4_tiny", "compressor": comp,
                  "steps": steps, "workers": P, "density": density,
                  "lr": lr, "grad_clip": grad_clip,
                  "batch_size": batch_size, "seq_len": SEQ_LEN,
                  "n_params": trainer.algo_cfg.n}
        f.write(json.dumps(header) + "\n")
        for i in range(steps):
            m = trainer.train_step(next(it))
            if (i + 1) % 10 == 0 or i == 0 or i + 1 == steps:
                rec = {"step": i + 1, "loss": float(m["loss"]),
                       "comm_volume": float(m["comm_volume"])}
                if (i + 1) % 40 == 0 or i + 1 == steps:
                    em = trainer.eval_step(eval_batch)
                    rec.update({f"eval_{k}": float(np.asarray(v))
                                for k, v in em.items()})
                for k in ("local_k", "global_k", "grad_norm",
                          "grad_nonfinite"):
                    if k in m:
                        rec[k] = float(np.asarray(m[k]).mean())
                f.write(json.dumps(rec) + "\n")
                f.flush()
    print(f"[ctc] {comp}: final loss {float(m['loss']):.3f} "
          f"({time.time()-t0:.0f}s) -> {path}", flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=240)
    p.add_argument("--batch-size", type=int, default=4,
                   help="per-worker examples per step")
    p.add_argument("--compressors", default="dense,oktopk,topkA")
    p.add_argument("--workers", type=int, default=8)
    p.add_argument("--density", type=float, default=0.05)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--grad-clip", type=float, default=400.0,
                   help="reference LSTM/main_trainer.py:94-99")
    p.add_argument("--warmup-steps", type=int, default=60)
    p.add_argument("--out", default="logs/convergence")
    args = p.parse_args()

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.workers}")
    import jax
    jax.config.update("jax_platforms", "cpu")

    from oktopk_tpu.comm.mesh import get_mesh

    mesh = get_mesh((args.workers,), ("data",))
    os.makedirs(args.out, exist_ok=True)
    for comp in args.compressors.split(","):
        run_one(comp, args.steps, mesh, args.density, args.lr,
                args.grad_clip, args.warmup_steps, args.out,
                batch_size=args.batch_size)


if __name__ == "__main__":
    main()
