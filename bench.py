"""Benchmark entry point.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Headline metric: Ok-Topk sparse-allreduce communication volume per worker per
step (bytes), measured on a multi-worker mesh in the threshold-tracking
regime, vs the dense-allreduce baseline (~2n elements/worker/step — the
BASELINE.md "allreduce bytes/step vs dense" north star). ``vs_baseline`` is
the reduction factor (dense bytes / oktopk bytes; higher is better; the
paper's property is volume < 6k elements, reference README.md:2).

The JSON line also carries the end-to-end numbers the volume claim has to be
anchored against: VGG-16/CIFAR-10 train-step time with the oktopk compressor
and with dense psum on one TPU chip, their variance, and the achieved MFU
(XLA cost-analysis flops / step time / the chip's published peak).

Two children, one chip: the parent imports no jax. The volume count runs in
a child pinned to an 8-worker virtual CPU mesh (it cannot take the chip);
the step times run in a second child that owns the TPU and fails without
one. A failure in either child, or a timed phase that produced no time,
makes ``python bench.py`` exit non-zero.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

BYTES_PER_ELEM = 4  # f32 scalars; indices are int32

# Published peak of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
# The MXU's native input is bf16, so this is the denominator of every MFU
# below, f32-compute rows included. A kind that is not here is an error.
PEAK_FLOPS = {"TPU v5 lite": 197e12}

CHILD_TIMEOUT_S = 1800     # each of the two children


def peak_flops(device_kind: str) -> float:
    if device_kind not in PEAK_FLOPS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add it to "
            "bench.PEAK_FLOPS with its source")
    return PEAK_FLOPS[device_kind]


def volume_probe():
    """Measure oktopk comm volume on an 8-worker virtual mesh (run in a
    subprocess with a CPU backend)."""
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from oktopk_tpu.collectives.api import batched_init_state, \
        build_allreduce_step
    from oktopk_tpu.comm.mesh import get_mesh
    from oktopk_tpu.config import OkTopkConfig
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    P, n = 8, 1 << 20
    cfg = OkTopkConfig(n=n, num_workers=P, density=0.01, warmup_steps=0,
                       local_recompute_every=1, global_recompute_every=4)
    mesh = get_mesh((P,), ("data",))
    step = build_allreduce_step("oktopk", cfg, mesh, warmup=False)
    state = batched_init_state(cfg)
    rng = np.random.RandomState(0)
    base = rng.randn(P, n).astype(np.float32)
    vols, wires = [], []
    comp_errs, eff_dens, res_norms = [], [], []
    for i in range(13):
        grads = base + 0.3 * rng.randn(P, n).astype(np.float32)
        # offline dense-vs-sparse oracle (mirrors the in-jit quality tap,
        # obs/quality.py): what an exact allreduce of gradient + carried
        # residual would have delivered this step
        res_before = np.asarray(state.residual, dtype=np.float64)
        dense = (grads.astype(np.float64) + res_before).mean(0)
        reduced, state = step(jnp.asarray(grads), state)
        if i % 4 != 0:   # steady-state predicted steps
            vols.append(float(state.last_volume[0]))
            wires.append(float(state.last_wire_bytes[0]))
            r = np.asarray(reduced[0], dtype=np.float64)
            comp_errs.append(float(((r - dense) ** 2).sum()
                                   / ((dense ** 2).sum() + 1e-30)))
            eff_dens.append(float((r != 0).sum()) / n)
            res_norms.append(float(np.mean(np.sqrt(
                (np.asarray(state.residual, np.float64) ** 2).sum(-1)))))
    from oktopk_tpu.obs.volume import budget_bytes
    budget = budget_bytes("oktopk", cfg)
    mean_wire = sum(wires) / len(wires)
    out = {"n": n, "k": cfg.k, "mean_volume_elems": sum(vols) / len(vols),
           "dense_volume_elems": 2.0 * n,
           # bytes per transmitted (index, value) pair: int32 index + the
           # configured wire value dtype (bf16 wire = 6, f32 wire = 8)
           "wire_pair_bytes": cfg.wire_pair_bytes,
           "wire_dtype": cfg.wire_dtype,
           # realised bytes on the wire (SparseState accounting) vs the
           # paper's 6k-scalar analytic budget (obs/volume.py): <= 1.0
           # means the O(k) volume claim held on the wire
           "wire_bytes": mean_wire,
           "volume_budget_bytes": budget,
           "conformance_ratio": mean_wire / budget,
           # signal fidelity (steady-state means, offline oracle — the
           # same definitions the in-jit taps journal; watchable via
           # RegressionDetector.quality_limits)
           "quality_comp_err": sum(comp_errs) / len(comp_errs),
           "quality_eff_density": sum(eff_dens) / len(eff_dens),
           "quality_res_norm": sum(res_norms) / len(res_norms)}
    print("VOLUME_PROBE " + json.dumps(out))


def _time_steps(trainer, batch, iters):
    """Per-step wall times (s); each ends when the new state is ready."""
    import jax
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        m = trainer.train_step(batch)
        jax.block_until_ready((trainer.state, m))
        times.append(time.perf_counter() - t0)
    return times


def step_time_probe(iters=10):
    """VGG-16/CIFAR oktopk vs dense train-step time + MFU on one TPU chip
    (single-chip mesh: measures the compute+selection path). Fails without
    a TPU; any config that fails to compile or run fails the probe."""
    import jax
    import numpy as np

    from oktopk_tpu.comm.mesh import get_mesh
    from oktopk_tpu.config import TrainConfig
    from oktopk_tpu.data.synthetic import synthetic_batch
    from oktopk_tpu.train.trainer import Trainer
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache
    from oktopk_tpu.utils.flops import model_complexity

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"step-time probe needs a TPU; jax found platform "
            f"{dev.platform!r} ({dev.device_kind})")
    peak = peak_flops(dev.device_kind)
    ensure_compile_cache()
    mesh = get_mesh((1,), ("data",), devices=[dev])
    rng = np.random.RandomState(0)
    # place batches once: host->device transfer is not part of the step
    # (real runs use the prefetching loader)
    batches = {16: jax.device_put(synthetic_batch("vgg16", 16, rng)),
               256: jax.device_put(synthetic_batch("vgg16", 256, rng))}

    out = {"device": dev.platform, "device_kind": dev.device_kind,
           "device_count": len(jax.devices()), "peak_flops_assumed": peak}
    flops_by_bs = {}
    # oktopk_b4 = 4 reverse-layer-order buckets (comm/backward overlap,
    # reference VGG/allreducer.py:27) — the delta vs single-bucket oktopk
    # is the measured overlap benefit
    # dense_bf16 = mixed-precision compute — the TPU-first headroom above
    # the reference's f32 VGG workload
    for name, comp, buckets, dt, bs in (
            ("dense", "dense", 1, "float32", 16),
            ("oktopk", "oktopk", 1, "float32", 16),
            ("dense_bs256", "dense", 1, "float32", 256),
            ("oktopk_bs256", "oktopk", 1, "float32", 256),
            ("dense_bf16_bs256", "dense", 1, "bfloat16", 256),
            ("oktopk_b4", "oktopk", 4, "float32", 16),
            ("dense_bf16", "dense", 1, "bfloat16", 16)):
        batch = batches[bs]
        cfg = TrainConfig(dnn="vgg16", dataset="cifar10", batch_size=bs,
                          lr=0.1, compressor=comp, density=0.02,
                          num_workers=1, num_buckets=buckets,
                          compute_dtype=dt)
        trainer = Trainer(cfg, mesh=mesh, warmup=False)
        if comp == "oktopk":
            out.setdefault("threshold_method",
                           trainer.algo_cfg.threshold_method)
        _time_steps(trainer, batch, 2)            # compile + warm
        # bs-256 steps carry ~16x the work per timing sample and exist to
        # amortize the dispatch floor, not to build a variance estimate —
        # half the samples suffice
        ms = [t * 1e3 for t in _time_steps(
            trainer, batch, iters if bs == 16 else max(3, iters // 2))]
        out[f"{name}_ms"] = statistics.median(ms)
        out[f"{name}_ms_std"] = statistics.pstdev(ms)
        if comp == "dense" and dt == "float32":
            flops = model_complexity(
                lambda s, b, r: trainer.step_fn(s, b, r),
                trainer.state, batch, jax.random.PRNGKey(0))["flops"]
            if flops <= 0:
                raise RuntimeError("XLA cost analysis reported no flops")
            flops_by_bs[bs] = flops
            out["flops_per_step" if bs == 16
                else "flops_per_step_bs256"] = flops
        if name in ("dense", "oktopk", "dense_bs256", "oktopk_bs256",
                    "dense_bf16_bs256"):
            out[f"mfu_{name}"] = (flops_by_bs[bs]
                                  / (out[f"{name}_ms"] / 1e3) / peak)
        print("STEP_PROBE " + json.dumps(out), flush=True)
    return out


def _child(flag, prefix, env):
    """Run ``bench.py <flag>`` and return the JSON of its last ``prefix``
    line. The child's stderr passes through; a non-zero exit, a timeout or
    a missing line raises."""
    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag],
        stdout=subprocess.PIPE, text=True, env=env, cwd=here,
        timeout=CHILD_TIMEOUT_S, check=True)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith(prefix)]
    if not lines:
        raise RuntimeError(f"bench.py {flag} printed no {prefix.strip()} "
                           f"line:\n{proc.stdout[-2000:]}")
    return json.loads(lines[-1][len(prefix):])


def _record(probe, steps):
    # volume_elems counts transmitted scalars (2 per (index, value) pair);
    # bytes follow the wire format: int32 index + bf16/f32 value per pair,
    # dense baseline = 2n f32 values (ring allreduce), no indices
    pairs = probe["mean_volume_elems"] / 2.0
    value = pairs * probe["wire_pair_bytes"]
    dense = probe["dense_volume_elems"] * BYTES_PER_ELEM
    rec = {
        "metric": "oktopk_sparse_allreduce_volume_bytes_per_step",
        "value": round(value, 1),
        "unit": "bytes/step/worker",
        "vs_baseline": round(dense / value, 2),
        "volume_elems": round(probe["mean_volume_elems"], 1),
        "wire_dtype": probe["wire_dtype"],
    }
    # measured-on-the-wire conformance (obs/volume.py) and the offline
    # signal-fidelity oracle (same definitions as the in-jit quality taps)
    for key, digits in (("wire_bytes", 3), ("volume_budget_bytes", 3),
                        ("conformance_ratio", 3), ("quality_comp_err", 6),
                        ("quality_eff_density", 6), ("quality_res_norm", 6)):
        rec[key] = round(float(probe[key]), digits)
    for key, val in steps.items():
        rec[key] = round(val, 3) if isinstance(val, float) else val
    return rec


def main():
    if "--volume-probe" in sys.argv:
        volume_probe()
        return
    if "--step-probe" in sys.argv:
        step_time_probe()
        return

    probe = _child("--volume-probe", "VOLUME_PROBE ",
                   dict(os.environ, JAX_PLATFORMS="cpu"))
    # the volume record first: the driver takes the last JSON line, and a
    # failed step child (non-zero exit below) still leaves this one
    print(json.dumps(_record(probe, {})), flush=True)
    steps = _child("--step-probe", "STEP_PROBE ", dict(os.environ))
    if not any(k.endswith("_ms") for k in steps):
        raise RuntimeError(f"step probe produced no time: {steps}")
    print(json.dumps(_record(probe, steps)))


if __name__ == "__main__":
    main()
