"""One untraced window of a cell with the program's recorder on: what
each step did, a step a line.

    python benchmark/tools/step_counters.py --workload <cell> --seed <n> \
        [--seconds 30] [--benchmark-json FILE] [--off-on PAIRS]

Set-up as ``run.py``'s (first three steps, settling), then one window of
``--seconds`` with no profiler. Printed for every step of the window: its
``step_num``, the gap between its completion stamp and the one before
(ms), the program's ``oktopk/step`` and ``oktopk/dispatch`` spans (ms) and
its ``counters`` vector (``oktopk_tpu/collectives/state.COUNTERS``). The
window's last line is one JSON object: its rate and step times and, by
overflow branch (fast / repair / wide), the count of steps and their
median time. ``--off-on PAIRS`` runs that many pairs of windows in the one
process, the first of each pair with no recorder attached (its spans are
then not printed) and the second with one: what the recorder costs when
on. Needs a TPU unless ``--rehearse``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchlib import discover, window as window_lib  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--off-on", type=int, default=0, metavar="PAIRS")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--benchmark-json", default=None)
    args = p.parse_args(argv)
    bench = discover.Bench(args.benchmark_json)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    if args.rehearse:
        import run
        run.rehearsal_env()
        config = discover.merge(config, config["rehearse"])
    sys.path.insert(0, bench.root)

    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("step_counters.py: needs a TPU (or --rehearse)",
              file=sys.stderr)
        return 2
    from oktopk_tpu.utils import profiling
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    if args.rehearse:
        from oktopk_tpu.ops import compaction
        compaction.mesh_supports_pallas = lambda mesh: True
    from benchlib.harness import Harness

    harness = Harness(cell, config, bench.traffic(cell["traffic"]),
                      args.seed, args.rehearse)
    harness.seed_state(args.seed)
    harness.first_steps()
    harness.settle()
    for on in ((False, True) * args.off_on or (True,)):
        window(harness, args.seconds, on, args.seed)
    return 0


def window(harness, seconds, recorder_on, seed) -> None:
    from oktopk_tpu.utils import profiling
    first = harness.trainer.step_num + 1
    recorder = profiling.PhaseTimers(every=0) if recorder_on else None
    profiling.attach(recorder)
    win = harness.window(seconds)
    profiling.attach(None)

    snap = profiling.snapshot()
    names, BRANCH = snap["counter_names"], snap["branch_names"]
    counters = {r["step"]: r["counters"] for r in snap["step_counters"]}
    spans = {}
    for name, start, end, step, _ in (recorder.records if recorder else ()):
        spans.setdefault(step, {})[name] = (end - start) * 1e-6
    gaps = [None] + [1e3 * t for t in window_lib.step_times(win.stamps)]
    stage, select = names.index("stage_branch"), names.index("select_branch")
    print("counters: " + " ".join(names))
    by_branch = {}
    for i, gap in enumerate(gaps):
        step = first + i
        c = counters.get(step)
        s = spans.get(step, {})
        print(f"step {step} gap_ms {'-' if gap is None else f'{gap:.3f}'} "
              f"host_ms {s.get('oktopk/step', float('nan')):.3f} "
              f"dispatch_ms {s.get('oktopk/dispatch', float('nan')):.3f} "
              f"counters {c}")
        if c is not None and gap is not None:
            by_branch.setdefault(max(c[stage], c[select]), []).append(gap)
    times = window_lib.step_times(win.stamps)
    print(json.dumps({
        "cell": harness.cell["name"], "seed": seed,
        "recorder": recorder_on, "steps": len(win.stamps),
        "samples_per_s": window_lib.rate(win.t0, win.stamps,
                                         harness.global_batch),
        "step_ms_p50": 1e3 * window_lib.percentile(times, 50),
        "step_ms_p95": 1e3 * window_lib.percentile(times, 95),
        "window_compiles": win.compiles,
        "steps_by_branch": {BRANCH[b]: len(v) for b, v in by_branch.items()},
        "median_ms_by_branch": {BRANCH[b]: statistics.median(v)
                                for b, v in by_branch.items()}}))


if __name__ == "__main__":
    sys.exit(main())
