"""Readings that the limits of ``correct`` are set from, in one process:
the program's numbers over a dozen seeds, then the control's (the program
with its own lower-precision path on: ``compute_dtype=bfloat16``) over a
few. Training needs no measured window for these.

    python benchmark/tools/calibrate.py --workload CELL
        --seeds 101,102,... --control-seeds 201,202,203
        [--precision highest]   # also read against a 'highest' reference

Prints one ``CALIB`` JSON line a seed; writes nothing.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

CONTROL = {"train": {"compute_dtype": "bfloat16"}}


def read(bench, cell, config, seeds, label, precisions):
    import jax
    from benchlib import weights
    from benchlib.harness import Harness
    ref = bench.reference(config["reference"])
    h = Harness(cell, config, bench.traffic(cell["traffic"]), seeds[0])
    tr = h.trainer
    # one compiled step for all the seeds: the trainer's state as it made
    # it is kept on the host and put back before each seed
    fresh = jax.device_get(tr.state)
    places = jax.tree.map(lambda x: x.sharding, tr.state)
    for seed in seeds:
        tr.state = None
        tr.state = jax.device_put(fresh, places)
        h.seed_state(seed)
        # a tool's liberty: the step key is the trainer's private field
        h.key0 = tr._rng = jax.random.PRNGKey(weights.seed32(seed) + 1)
        h.first_steps()
        h.settle()      # the exchange is compared in the settled regime
        for prec in precisions:
            detail = {}
            nums = h.numbers(ref, None, prec, detail)
            names = [jax.tree_util.keystr(p) for p, _ in
                     jax.tree_util.tree_flatten_with_path(
                         h.snaps.states[0][0])[0]]
            worst = {k: names[max(range(len(v)), key=v.__getitem__)]
                     for k, v in detail.items() if k.endswith("leaf_gaps")}
            print("CALIB " + json.dumps(
                {"cell": cell["name"], "side": label, "seed": seed,
                 "reference_precision": prec or "default",
                 "device": jax.devices()[0].device_kind,
                 "losses": detail["losses"], "worst_leaf": worst,
                 "grad1_leaf_diffs": detail["grad1_leaf_diffs"],
                 "dparam3_leaf_diffs": detail["dparam3_leaf_diffs"],
                 "grad1_norms": detail["grad1_norms"],
                 "dparam3_norms": detail["dparam3_norms"],
                 **nums}), flush=True)
    h.trainer.state = None
    del h


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--precision", default=None)
    p.add_argument("--benchmark-json", default=None)
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args()
    if a.rehearse:
        import run
        run.rehearsal_env()
    from benchlib import discover
    bench = discover.Bench(a.benchmark_json)
    cell = bench.cell(a.workload)
    config = bench.config(cell["config"])
    if a.rehearse:
        config = discover.merge(config, config["rehearse"])
        from oktopk_tpu.ops import compaction
        compaction.mesh_supports_pallas = lambda mesh: True
    from oktopk_tpu.utils.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    stated = config.get("reference_precision")
    precisions = [stated] + ([a.precision] if a.precision else [])
    ints = lambda s: [int(x) for x in s.split(",") if x]
    read(bench, cell, config, ints(a.seeds), "program", precisions)
    if a.control_seeds:
        read(bench, cell, discover.merge(config, CONTROL),
             ints(a.control_seeds), "control", [stated])


if __name__ == "__main__":
    main()
