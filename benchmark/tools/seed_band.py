"""Where a sparse cell's seed-to-seed spread comes from: every step's stamp
and delivered count over a long run of several seeds in one process (one
compiled step, the state put back before each seed, as ``calibrate.py``),
and the windows that could be cut from them.

    python benchmark/tools/seed_band.py run --workload CELL --seeds a,b,...
        --steps 285 [--distinct-batches N] --out chiprun_out/band.jsonl
    python benchmark/tools/seed_band.py table band.jsonl
        [--start 64 --length 96 | --seconds 30] [--period 32]

``run`` drives the first three steps and then ``--steps`` more through the
harness's own window loop (queue one deep) with no check, and appends one
JSON line a seed: the gap of every step in ms and its delivered count, by
the job's step counter. ``table`` cuts the window [start, start + length)
of the job's steps out of each line and prints, a seed: the window's
median, mean and rate, the fit ``step = a + b x delivered`` over its
predicted steps with the residual, its exact-recompute steps, and the same
by controller period; then the spread of each column between seeds.
"""

import argparse
import itertools
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from spread import tight  # noqa: E402  (beside this file: the driver's rule)


def run(a):
    import jax
    from benchlib import discover, weights
    from benchlib.harness import FIRST_STEPS, Harness
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache
    bench = discover.Bench(a.benchmark_json)
    cell = bench.cell(a.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    if a.rehearse:
        config = discover.merge(config, config["rehearse"])
    if a.distinct_batches:
        traffic = discover.merge(
            traffic, {"stream": {"distinct_batches": a.distinct_batches}})
    ensure_compile_cache()
    seeds = [int(s) for s in a.seeds.split(",") if s]
    h = Harness(cell, config, traffic, seeds[0], a.rehearse)
    tr = h.trainer
    fresh = jax.device_get(tr.state)
    places = jax.tree.map(lambda x: x.sharding, tr.state)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    for seed in seeds:
        tr.state = None
        tr.state = jax.device_put(fresh, places)
        h.seed_state(seed)
        h.key0 = tr._rng = jax.random.PRNGKey(weights.seed32(seed) + 1)
        h.first_steps()
        jax.block_until_ready(tr.state.params)
        win = h.window(0.0, max_steps=a.steps)
        gaps = [win.stamps[0] - win.t0] + [
            y - x for x, y in zip(win.stamps, win.stamps[1:])]
        line = {"cell": cell["name"], "seed": seed,
                "distinct_batches": traffic["stream"]["distinct_batches"],
                "first_job_step": FIRST_STEPS,   # steps done before gaps[0]
                "ms": [1e3 * g for g in gaps],
                "delivered": win.delivered.tolist(),
                "loss": win.losses.tolist(),
                "compiles": win.compiles,
                "global_batch": h.global_batch,
                "device": jax.devices()[0].device_kind}
        with open(a.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(f"BAND seed {seed}: {len(gaps)} steps, median "
              f"{statistics.median(line['ms']):.2f} ms, compiles "
              f"{win.compiles}", flush=True)


def fit(xs, ys):
    """Least squares y = a + b x; (a, b, root mean square residual)."""
    n = len(xs)
    mx, my = sum(xs) / n, sum(ys) / n
    sxx = sum((x - mx) ** 2 for x in xs)
    b = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sxx if sxx else 0.0
    a0 = my - b * mx
    res = (sum((y - a0 - b * x) ** 2 for x, y in zip(xs, ys)) / n) ** 0.5
    return a0, b, res


def cut(line, start, length, period):
    """The window of job steps [start, start + length): step i here is the
    one that ran with i steps done before it."""
    lo = start - line["first_job_step"]
    if lo < 0 or lo + length > len(line["ms"]):
        raise ValueError(f"seed {line['seed']}: steps {start}..{start + length}"
                         f" are not in a run of {len(line['ms'])}")
    ms = line["ms"][lo:lo + length]
    dl = [d / 1e6 for d in line["delivered"][lo:lo + length]]
    exact = [i for i in range(length) if (start + i) % period == 0]
    pred = [i for i in range(length) if (start + i) % period]
    a0, b, res = fit([dl[i] for i in pred], [ms[i] for i in pred])
    out = {"seed": line["seed"],
           "p50": statistics.median(ms[1:]), "mean": sum(ms) / length,
           "rate": line["global_batch"] * length / (sum(ms) / 1e3),
           "a": a0, "b": b, "res": res,
           "dl_med": statistics.median(dl[i] for i in pred),
           "dl_mean": sum(dl[i] for i in pred) / len(pred),
           "exact_n": len(exact),
           "exact_ms": sum(ms[i] for i in exact) / max(len(exact), 1),
           "exact_share": sum(ms[i] for i in exact) / sum(ms)}
    for p in sorted({(start + i) // period for i in pred}):
        # by controller period of the job: P2 is job steps 64..95
        seg = [i for i in pred if (start + i) // period == p]
        out[f"P{p}_mean"] = sum(ms[i] for i in seg) / len(seg)
        out[f"P{p}_dl"] = sum(dl[i] for i in seg) / len(seg)
    # the pattern of the repeating batches: mean of the predicted steps by
    # position in a cycle of four, less the mean of all of them
    allp = sum(ms[i] for i in pred) / len(pred)
    for r in range(4):
        seg = [ms[i] for i in pred if (start + i) % 4 == r]
        out[f"mod4_{r}"] = sum(seg) / len(seg) - allp
    return out


def clock_length(line, start, seconds):
    """Steps of the clock's window from job step ``start``: up to the
    first completion past ``seconds``, and the step in flight behind it."""
    t, lo = 0.0, start - line["first_job_step"]
    for i, ms in enumerate(line["ms"][lo:]):
        t += ms / 1e3
        if t >= seconds:
            return i + 2
    raise ValueError(f"seed {line['seed']}: the run ends before {seconds} s")


def sets_of_six(values):
    """The driver's rule for tightness (``spread.tight``) over every set of
    six of the seeds read: (median over the sets, largest)."""
    got = sorted(abs(tight(c)) for c in itertools.combinations(values, 6)
                 if statistics.median(c))
    return (statistics.median(got), got[-1]) if got else (0.0, 0.0)


def table(a):
    lines = [json.loads(l) for path in a.files for l in open(path)
             if l.startswith("{")]
    rows = [cut(l, a.start, clock_length(l, a.start, a.seconds)
                if a.seconds else a.length, a.period) for l in lines]
    cols = [k for k in rows[0] if k != "seed"]
    print("seed " + " ".join(f"{c:>10s}" for c in cols))
    for r in rows:
        print(f"{r['seed']} " + " ".join(f"{r[c]:10.3f}" for c in cols))
    print("-- between seeds: median, min, max, max-min, quartile spread "
          "(IQR/median), max-min with the farthest left out over the "
          "median; the same over every set of six: median, largest")
    for c in cols:
        v = [r[c] for r in rows]
        med = statistics.median(v)
        q1, _, q3 = statistics.quantiles(v, n=4) if len(v) > 1 else (0, 0, 0)
        rel = (lambda x: x / abs(med) if med else float("nan"))
        print(f"{c:>12s} {med:10.3f} {min(v):10.3f} {max(v):10.3f} "
              f"{max(v) - min(v):9.3f} {rel(q3 - q1):9.4%} "
              f"{abs(tight(v)) if med and len(v) > 2 else 0:9.4%} "
              + ("{:9.4%} {:9.4%}".format(*sets_of_six(v))
                 if len(v) >= 6 else ""))


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--steps", type=int, default=285)
    r.add_argument("--distinct-batches", type=int, default=0)
    r.add_argument("--out", required=True)
    r.add_argument("--benchmark-json", default=None)
    r.add_argument("--rehearse", action="store_true")
    t = sub.add_parser("table")
    t.add_argument("files", nargs="+")
    t.add_argument("--start", type=int, default=67)
    t.add_argument("--length", type=int, default=108)
    t.add_argument("--period", type=int, default=32)
    t.add_argument("--seconds", type=float, default=0.0,
                   help="cut the clock's window instead of --length steps")
    a = p.parse_args()
    if a.cmd == "table":
        return table(a)
    if a.rehearse:
        import run as run_py
        run_py.rehearsal_env()
        from oktopk_tpu.ops import compaction
        compaction.mesh_supports_pallas = lambda mesh: True
    run(a)


if __name__ == "__main__":
    main()
