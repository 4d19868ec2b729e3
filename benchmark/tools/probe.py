"""Size a cell before it is fixed: one process, one batch size, a few
steps; prints step time and the device's peak memory as readings.

    python benchmark/tools/probe.py --workload CELL --batch B [--steps N]
        [--benchmark-json FILE] [--set train.compute_dtype=bfloat16]
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--benchmark-json", default=None)
    a = p.parse_args()
    import jax
    from benchlib import discover
    from benchlib.harness import Harness
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    bench = discover.Bench(a.benchmark_json)
    cell = bench.cell(a.workload)
    config = bench.config(cell["config"])
    if a.batch:
        config["train"]["batch_size"] = a.batch
    t0 = time.perf_counter()
    h = Harness(cell, config, bench.traffic(cell["traffic"]), a.seed)
    h.seed_state(a.seed)
    t1 = time.perf_counter()
    m = h.step(next(h.feed))
    jax.block_until_ready(m["loss"])
    t2 = time.perf_counter()
    for _ in range(8):
        m = h.step(next(h.feed))
    jax.block_until_ready(m["loss"])
    w = h.window(0.0, max_steps=a.steps)
    dt = (w.stamps[-1] - w.t0) / a.steps
    dev = jax.devices()[0]
    tr = h.trainer
    ma = tr.step_fn.lower(tr.state, next(h.feed), h.key0).compile(
        ).memory_analysis()
    print("MEMORY stats", dev.memory_stats(), "; step program:", ma, flush=True)
    print("PROBE " + json.dumps({
        "cell": a.workload, "batch": h.train_cfg.batch_size,
        "device": f"{dev.platform}:{dev.device_kind}x{cell['chips']}",
        "build_s": t1 - t0, "first_step_s": t2 - t1, "step_ms": dt * 1e3,
        "loss_first_last": [float(w.losses[0]), float(w.losses[-1])],
        "volume_max": float(w.volumes.max()),
        "peak_mib": h.memory_peak_bytes() / 2 ** 20,
        "limit_mib": (dev.memory_stats() or {}).get("bytes_limit", 0) / 2 ** 20,
    }), flush=True)


if __name__ == "__main__":
    main()
