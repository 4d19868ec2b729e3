"""Chip smoke: the main training path, once, on every visible TPU chip.

    python chip_smoke.py

One process. Drives ``oktopk_tpu.train.main_trainer.main(argv)`` — the
entry point a user calls — three times on VGG-16 at the width the repo
ships (n = 14,728,266, CIFAR 32x32 input, per-worker batch 16, density
0.02, SGD momentum 0.9, synthetic data from a seed): ``--compressor dense``,
``--compressor oktopk`` and ``--compressor oktopk --num-buckets 4``, each
``--warmup-steps 2 --max-iters 10`` (oktopk steps 1-2 dense warmup, step 3
the first-sparse exact recompute, steps 4-10 the predicted-threshold path).

It refuses to run (non-zero exit, no result line) unless jax's first device
is a TPU, and with ``OKTOPK_PALLAS_INTERPRET`` set. It fails on: any
exception; a non-finite loss; on sparse steps ``comm_volume`` outside
(0, 2n) or ``local_k == 0``; a compiled oktopk step whose HLO holds no
Mosaic custom call (the proof the Pallas path, not the portable one, was
built); a compiled fused kernel that disagrees with the portable reference
by one bit; with several chips, a residual not sharded over all of them or
params not replicated.

Every number it prints is a reading from this one run on the device named
on the line — compile time apart from step time — and none of them is a
benchmark metric. The last stdout line is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--rehearse`` is for the CPU sandbox, so chip time is not spent on typos:
it drops the TPU requirement, forces the Pallas path on over 4 virtual CPU
devices with the kernels interpreted, and shrinks the model. It is a mode
of this script, not of the program.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_VGG16 = 14_728_266
WARMUP_STEPS = 2
MAX_ITERS = 10
RUNS = (("dense", ["--compressor", "dense"]),
        ("oktopk", ["--compressor", "oktopk"]),
        ("oktopk_b4", ["--compressor", "oktopk", "--num-buckets", "4"]))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class Fail(Exception):
    """A smoke check did not hold."""


def check(cond, msg):
    if not cond:
        raise Fail(msg)


class Counters:
    """Seconds spent compiling (or loading from the persistent cache) and
    cache hits/misses, from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.compile_s += secs

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def snapshot(self):
        return (self.compile_s, self.hits, self.misses)

    def since(self, snap):
        return (self.compile_s - snap[0], self.hits - snap[1],
                self.misses - snap[2])


def peak_memory(devices):
    from oktopk_tpu.utils.profiling import device_memory_stats
    return [device_memory_stats(d).get("peak_bytes_in_use") for d in devices]


def fmt_mem(peaks):
    return "[" + ", ".join("n/a" if p is None else f"{p / 2**20:.0f} MiB"
                           for p in peaks) + "]"


def kernel_parity(interpret, tag):
    """Compiled fused kernel vs the portable reference, bit for bit — the
    shapes of tests/test_tpu_hw.py::test_fused_select_parity_on_chip."""
    import jax.numpy as jnp
    import numpy as np

    from oktopk_tpu.ops.fused_select import (fused_select_pallas,
                                             fused_select_reference)

    rng = np.random.RandomState(21)
    n = 1 << 18
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    r = jnp.asarray((0.1 * rng.randn(n)).astype(np.float32))
    bounds = jnp.asarray(np.array([0, n // 3, n], np.int32))
    got = fused_select_pallas(g, r, 2.0, 2.5, bounds, 2, 4096,
                              interpret=interpret)
    want = fused_select_reference(g, r, 2.0, 2.5, bounds, 2, 4096)
    for nm, a, b in zip(("acc", "values", "indices", "counts", "local_count",
                         "probe_count"), got, want):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"fused kernel != portable reference in {nm!r}")
    print(f"{tag} fused_select parity: 6 outputs bit-identical "
          f"(n={n}, interpret={interpret})", flush=True)


def run_main(name, extra, size, counters, tag):
    """One ``main_trainer.main(argv)`` run with a Trainer that records each
    step; returns the trainer and the records."""
    import jax
    import numpy as np

    from oktopk_tpu.train import main_trainer
    from oktopk_tpu.train import trainer as trainer_mod

    dnn, batch_size, n_model = size
    rec = {"steps": [], "trainer": None, "batch": None}

    class RecordingTrainer(trainer_mod.Trainer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            rec["trainer"] = self
            rec["mem_at_init"] = peak_memory(jax.devices())

        def train_step(self, batch):
            snap = counters.snapshot()
            t0 = time.perf_counter()
            m = super().train_step(batch)
            jax.block_until_ready((self.state, m))
            wall = time.perf_counter() - t0
            rec["batch"] = batch
            rec["steps"].append(
                (wall, counters.since(snap)[0],
                 {k: float(np.asarray(v).mean()) for k, v in m.items()}))
            return m

    argv = ["--dnn", dnn, "--dataset", "cifar10",
            "--batch-size", str(batch_size), "--density", "0.02",
            "--momentum", "0.9", "--warmup-steps", str(WARMUP_STEPS),
            "--max-iters", str(MAX_ITERS), "--log-every", "5",
            "--logdir", os.path.join("chiprun_out", "chip_smoke_logs"),
            *extra]
    print(f"{tag} run {name}: main_trainer.main({' '.join(argv)})",
          flush=True)
    snap = counters.snapshot()
    original = trainer_mod.Trainer
    trainer_mod.Trainer = RecordingTrainer
    try:
        rc = main_trainer.main(argv)
    finally:
        trainer_mod.Trainer = original
    check(rc == 0, f"{name}: main_trainer.main returned {rc}")
    check(len(rec["steps"]) == MAX_ITERS,
          f"{name}: {len(rec['steps'])} steps ran, expected {MAX_ITERS}")
    trainer = rec["trainer"]
    check(trainer.algo_cfg.n == n_model,
          f"{name}: n = {trainer.algo_cfg.n}, expected {n_model}")

    sparse = "oktopk" in name
    for i, (wall, comp, m) in enumerate(rec["steps"], start=1):
        check(np.isfinite(m["loss"]), f"{name} step {i}: loss {m['loss']}")
        if sparse and i > WARMUP_STEPS:
            check(0 < m["comm_volume"] < 2 * n_model,
                  f"{name} step {i}: comm_volume {m['comm_volume']} not in "
                  f"(0, {2 * n_model})")
            check(m["local_k"] > 0, f"{name} step {i}: local_k == 0")
        print(f"{tag}   {name} step {i:2d}: wall {wall * 1e3:9.1f} ms"
              f" (compile/load {comp:6.2f} s)  loss {m['loss']:.4f}"
              f"  comm_volume {m['comm_volume']:.0f}"
              f"  local_k {m['local_k']:.0f}", flush=True)
    comp, hits, misses = counters.since(snap)
    steady = sorted(w for w, c, _ in rec["steps"] if c == 0.0)
    print(f"{tag} run {name}: compile/load {comp:.1f} s, cache hits {hits} "
          f"misses {misses}; steps with no compile: n={len(steady)} "
          f"median {steady[len(steady) // 2] * 1e3:.1f} ms "
          f"(smoke reading, not a metric)", flush=True)
    return trainer, rec


def check_mosaic(name, trainer, rec, tag):
    """Lower the trainer's own step and look for the Mosaic custom call."""
    import jax
    hlo = trainer.step_fn.lower(trainer.state, rec["batch"],
                                jax.random.PRNGKey(0)).compile().as_text()
    ncalls = hlo.count('custom_call_target="tpu_custom_call"')
    names = [k for k in ("oktopk_fused_select", "oktopk_stage_w128",
                         "oktopk_repair", "oktopk_stage_w1024") if k in hlo]
    print(f"{tag} {name} step HLO: {ncalls} Mosaic custom calls {names}",
          flush=True)
    check(ncalls > 0, f"{name}: compiled step holds no Mosaic custom call — "
          "the portable selection path was built")


def check_placement(name, trainer, devices, tag):
    import jax
    sps = trainer.state.sparse_state
    for sp in (sps if isinstance(sps, tuple) else (sps,)):
        res = sp.residual
        check(res.sharding.device_set == set(devices),
              f"{name}: residual on {len(res.sharding.device_set)} of "
              f"{len(devices)} devices")
        shard_shapes = {s.data.shape for s in res.addressable_shards}
        check(shard_shapes == {(1, res.shape[1])},
              f"{name}: residual shards {shard_shapes}, expected one "
              f"[1, {res.shape[1]}] row per device")
    for leaf in jax.tree.leaves(trainer.state.params):
        check(leaf.sharding.is_fully_replicated
              and leaf.sharding.device_set == set(devices),
              f"{name}: a params leaf is not replicated over all devices")
    print(f"{tag} {name} placement: residual one row per device over "
          f"{len(devices)} device(s), params replicated", flush=True)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal at a tiny size, kernels interpreted")
    args = p.parse_args(argv)
    size = ("vgg16", 16, N_VGG16)          # dnn, per-worker batch, n
    if args.rehearse:
        size = ("caffe_cifar", 2, 145_578)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
        os.environ["OKTOPK_PALLAS_INTERPRET"] = "1"
    elif os.environ.get("OKTOPK_PALLAS_INTERPRET"):
        print("chip_smoke: OKTOPK_PALLAS_INTERPRET is set; the smoke runs "
              "compiled kernels only. Unset it.", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU; jax found platform "
              f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
              "device(s)). --rehearse runs the CPU rehearsal.",
              file=sys.stderr)
        return 2

    import jaxlib

    from oktopk_tpu.ops import compaction
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    tag = f"[{dev.platform}:{dev.device_kind} x{len(devices)}]"
    print(f"{tag} platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    print(f"{tag} compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR="
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '')!r})", flush=True)
    if args.rehearse:
        # the program turns the Pallas path on for TPU meshes only; the
        # rehearsal wants the same code on the CPU mesh, interpreted
        compaction.mesh_supports_pallas = lambda mesh: True

    counters = Counters()
    t_start = time.perf_counter()
    snap = counters.snapshot()
    kernel_parity(interpret=args.rehearse, tag=tag)
    comp, hits, misses = counters.since(snap)
    print(f"{tag} parity phase: compile/load {comp:.1f} s, cache hits {hits} "
          f"misses {misses}", flush=True)

    for name, extra in RUNS:
        trainer, rec = run_main(name, extra, size, counters, tag)
        check_placement(name, trainer, devices, tag)
        if "oktopk" in name and not args.rehearse:
            check_mosaic(name, trainer, rec, tag)
        print(f"{tag} {name} peak device memory: at Trainer init "
              f"{fmt_mem(rec['mem_at_init'])}, after the run "
              f"{fmt_mem(peak_memory(devices))}", flush=True)
        del trainer, rec        # free this run's state before the next

    native = sys.modules.get("oktopk_tpu.native")
    check(native is None or native._lib is None,
          "the native library was loaded on the smoke path")
    comp, hits, misses = counters.snapshot()
    print(f"{tag} total: wall {time.perf_counter() - t_start:.0f} s, "
          f"compile/load {comp:.0f} s, cache hits {hits} misses {misses}")
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(devices)}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Fail as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
