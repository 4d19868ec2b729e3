"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that sets up (``setup_s``: from process start to the first
timed step), measures for ``--seconds`` (or, where the cell's traffic file
asks for them, for whole controller periods inside it), decides ``correct``
against the plain reference, prints each number compared beside its limit
and, as its last line, one JSON object. ``--trace 0`` reports the cell's
end-to-end metrics with the profiler off; ``--trace 1`` reports its
per-layer metrics from a short ``jax.profiler`` window round the same loop.

It refuses to run (exit 2, no result line) without a TPU or with fewer
chips than the cell asks for. ``--rehearse`` is for the sandbox: four
virtual CPU devices, kernels interpreted, the configuration's ``rehearse``
sizes; it says so on every line it prints and its result line is marked
``"rehearsal": true`` — none of its numbers is a device number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import discover, window as window_lib  # noqa: E402

TRACE_STEPS = 30     # a traced window is this many steps ...
TRACE_SECONDS = 4.0  # ... or this long, whichever is less


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal at the configuration's tiny sizes")
    p.add_argument("--benchmark-json", default=None,
                   help="another BENCHMARK.json (to try a cell before it "
                        "is added); files are found beside it")
    return p.parse_args(argv)


def rehearsal_env():
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
    os.environ["OKTOPK_PALLAS_INTERPRET"] = "1"


def traced_window(harness, out_dir):
    """A short profiled window round the same loop, reduced to a Trace."""
    import jax
    from benchlib import xtrace
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    tr = harness.trainer
    # a few untraced steps say how many steps the traced window may hold
    probe = harness.window(0.0, max_steps=4)
    step_s = (probe.stamps[-1] - probe.t0) / len(probe.stamps)
    steps = max(3, min(TRACE_STEPS, int(TRACE_SECONDS / max(step_s, 1e-6))))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1   # the harness's own spans, not every DMA
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            win = harness.window(0.0, max_steps=steps, annotate=True)
    finally:
        jax.profiler.stop_trace()
    batch = harness.feed.batches[0]
    hlo = tr.step_fn.lower(tr.state, batch, harness.key0).compile().as_text()
    with open(os.path.join(out_dir, "step.hlo.txt"), "w") as f:
        f.write(hlo)
    paths = [os.path.join(d, n) for d, _, names in os.walk(out_dir)
             for n in names if n.endswith(".xplane.pb")]
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {out_dir}, "
                           f"found {paths}")
    profile = jax.profiler.ProfileData.from_file(paths[0])
    trace = xtrace.read(profile, xtrace.hlo_paths(hlo), steps,
                        stand_in_host_ops=harness.rehearsal)
    return win, trace


def main(argv=None) -> int:
    args = parse(argv)
    bench = discover.Bench(args.benchmark_json)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    limits = bench.limits(cell["name"])
    if args.rehearse:
        rehearsal_env()
        config = discover.merge(config, config["rehearse"])
    elif os.environ.get("OKTOPK_PALLAS_INTERPRET"):
        print("run.py: OKTOPK_PALLAS_INTERPRET is set; a run times compiled "
              "kernels only. Unset it.", file=sys.stderr)
        return 2
    sys.path.insert(0, bench.root)
    try:
        import oktopk_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not in {bench.root}: {e}",
              file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU chip(s);"
              f" jax found {len(devices)} x {dev.platform!r} "
              f"({dev.device_kind}). --rehearse runs the CPU rehearsal.",
              file=sys.stderr)
        return 2
    tag = (f"[REHEARSAL on {dev.platform}, no device number] "
           if args.rehearse else "")

    from oktopk_tpu.utils.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    # every program of the step goes to the cache, the short compiles too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if args.rehearse:
        # the program turns the Pallas path on for TPU meshes only; the
        # rehearsal wants the same code on the CPU mesh, interpreted
        from oktopk_tpu.ops import compaction
        compaction.mesh_supports_pallas = lambda mesh: True

    from benchlib import check, peaks
    from benchlib.harness import Harness

    marks = [("imports", time.perf_counter())]
    harness = Harness(cell, config, traffic, args.seed, args.rehearse)
    marks.append(("trainer", time.perf_counter()))
    harness.seed_state(args.seed)
    marks.append(("seeded weights and traffic", time.perf_counter()))
    harness.first_steps()
    marks.append(("first three steps", time.perf_counter()))
    harness.settle()
    marks.append(("settling", time.perf_counter()))
    setup_s = marks[-1][1] - T_START
    parts = ", ".join(f"{n} {b - a:.1f}" for (n, b), a in zip(
        marks, [T_START] + [t for _, t in marks]))
    print(f"{tag}setup parts (s): {parts}", flush=True)
    print(f"{tag}device platform={dev.platform} kind={dev.device_kind} "
          f"count={cell['chips']} of {len(devices)}; compile cache "
          f"{cache_dir}; n={harness.n} workers={harness.workers} "
          f"global_batch={harness.global_batch}; setup {setup_s:.2f} s, "
          f"{harness.compiles.count} compilations in it", flush=True)

    trace = None
    if args.trace:
        out_dir = os.path.join(bench.root, ".bench_out", "trace",
                               cell["name"])
        win, trace = traced_window(harness, out_dir)
    else:
        win = harness.window(args.seconds)
    peak = harness.memory_peak_bytes()

    numbers = harness.numbers(bench.reference(config["reference"]), win,
                              config.get("reference_precision"))
    correct, lines = check.judge(numbers, limits)
    for line in lines:
        print(tag + line, flush=True)

    ctx = discover.Context(
        cell=cell, config=config, traffic=traffic, window=win, trace=trace,
        setup_s=setup_s, memory_peak_bytes=peak, workers=harness.workers,
        n=harness.n, global_batch=harness.global_batch,
        train_cfg=harness.train_cfg, algo_cfg=harness.trainer.algo_cfg,
        # the rehearsal has no chip: the table's first kind stands in, so
        # that the readers run; their values are dropped below
        device_kind=(next(iter(peaks.PEAKS)) if args.rehearse
                     else dev.device_kind))
    metrics = {}
    for m in bench.metrics("per_layer" if args.trace else "end_to_end",
                           cell["name"]):
        value = m["read"](ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    steps, span, readings = window_lib.summary(win.t0, win.stamps)
    print(f"{tag}window: {steps} steps in {span:.3f} s from job step "
          f"{win.first_step}, {readings} step-time readings, one a step",
          flush=True)
    # every stamp of the window, for whoever wants another statistic of it
    stamps_dir = os.path.join(bench.root, ".bench_out", "stamps")
    os.makedirs(stamps_dir, exist_ok=True)
    with open(os.path.join(stamps_dir, f"{cell['name']}.{args.seed}."
                           f"trace{args.trace}.json"), "w") as f:
        json.dump({"t0": win.t0, "stamps": win.stamps,
                   "delivered": win.delivered.tolist(),
                   "first_step": win.first_step}, f)

    if args.rehearse:
        # a sandbox number is never written under a device metric's name
        print(f"{tag}readers ran for: {sorted(metrics)}", flush=True)
        metrics = {}
    result = {
        "correct": bool(correct),
        "attempted": int(len(win.losses)),
        "failed": int(numbers["nonfinite_steps"]),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": int(cell["chips"]), "memory_peak_bytes": peak},
    }
    if trace is not None:
        busy = sum(trace.busy_s(c) for c in trace.chips) / len(trace.chips)
        result["device"].update(busy_s=busy, window_s=trace.window_s)
        result["breakdown"] = trace.breakdown()
    if args.rehearse:
        result["rehearsal"] = True
    # the driver's record of a run that is not correct keeps the end of
    # standard error: each number beside its limit goes there too, last
    print("\n".join(tag + line for line in lines), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
