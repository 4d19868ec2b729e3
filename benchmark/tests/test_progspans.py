"""The program's own spans and counters joined to the device trace
(``benchlib/progspans.py``): the join by ``step_num`` on a synthesised
``.xplane``, what a program without spans reads as, and the new readers
under ``--rehearse``."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchlib import discover, progspans, xtrace

MS = 1_000_000   # ns
NAMES = ["stage_branch", "stage_overflow_blocks", "select_branch",
         "select_overflow_blocks", "recompute_local", "recompute_global",
         "repartition", "local_k", "global_k"]
NEW = {"train_step_host_ms", "dispatch_ms", "step_gap_ms",
       "setup_compile_s", "select_sweep_ms", "select_global_ms",
       "stage_finalize_ms", "select_stage_unscoped_ms",
       "repair_branch_steps_pct", "overflow_blocks_per_step",
       "local_k_share"}
SUBS = {"select": ["threshold", "sweep", "global", "feedback"],
        "stage": ["repartition", "finalize"]}


def ev(name, start_ms, dur_ms, **stats):
    return types.SimpleNamespace(name=name, start_ns=start_ms * MS,
                                 duration_ns=dur_ms * MS,
                                 stats=list(stats.items()))


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n.replace("_", " "), events=evs)
        for n, evs in lines.items()])


def profile(with_program=True, steps=(11, 12, 13)):
    """Three steps of 100 ms, 4 ms apart: 1 ms of that under the program's
    ``oktopk/step`` span (the key split's program runs for 0.25 ms of it),
    3 ms under the harness's ``bench/data`` alone."""
    mods, ops, host = [], [], [ev("bench/window", 0, 320)]
    for i, step in enumerate(steps):
        t = 4 + 104 * i
        # the key split runs as often as the step, just before it
        mods += [ev("jit__threefry_split(2)", t - 0.5, 0.25),
                 ev("jit_step(1)", t, 100)]
        ops += [ev("%split.9 = u32[2] fusion()", t - 0.5, 0.25),
                ev("%fusion.1 = f32[8] fusion()", t, 30),
                ev("%fusion.2 = f32[8] fusion()", t + 30, 50),
                ev("%gather.3 = f32[8] gather()", t + 80, 4.75),
                ev("%cumsum.5 = s32[8] fusion()", t + 84.75, 0.25),
                ev("%oktopk_repair.4 = f32[8] custom-call()", t + 85, 15)]
        host += [ev("bench/data", t - 4, 3), ev("bench/dispatch", t - 1, 2)]
        if with_program:
            host += [ev("oktopk/step", t - 1, 2, step_num=step),
                     ev("oktopk/rng", t - 1, 0.5),
                     ev("oktopk/dispatch", t - 0.5, 1.5)]
    return types.SimpleNamespace(planes=[
        plane("/device:TPU:0", XLA_Modules=mods, XLA_Ops=ops),
        plane("/host:CPU", python=host)])


HLO = {"fusion.1": ("jit(step)/anat/b000/select/sweep/mul", ""),
       "fusion.2": ("jit(step)/anat/b000/stage/finalize/gather", ""),
       "cumsum.5": ("jit(step)/anat/b000/stage/repartition/cumsum", ""),
       "gather.3": ("jit(step)/anat/b000/stage/gather", ""),
       "oktopk_repair.4": ("jit(step)/anat/b000/stage/finalize/pallas",
                           "custom_call_target=\"tpu_custom_call\"")}


def snapshot(steps=(11, 12, 13)):
    rows = {11: [0, 0, 0, 0, 0, 0, 0, 900, 1000],
            12: [1, 5, 0, 0, 0, 0, 0, 1100, 1000],
            13: [0, 0, 2, 9, 1, 1, 0, 1000, 1000]}
    return {"counter_names": NAMES,
            "branch_names": ["fast", "repair", "wide"], "sub_scopes": SUBS,
            "step_counters": [{"step": s, "counters": rows[s]}
                              for s in steps],
            "host_counters": {"by_step": {
                "0": {"trace": 1.0, "lower": 0.5, "compile": 2.0},
                "3": {"trace": 0.25, "lower": 0.0, "compile": 4.0},
                "14": {"trace": 9.0, "lower": 9.0, "compile": 9.0}}}}


def context(with_program=True, snap_steps=(11, 12, 13)):
    prof = profile(with_program)
    trace = xtrace.read(prof, HLO, 3)
    snap = snapshot(snap_steps) if with_program else {}
    v = progspans.build(trace, progspans.host_spans(prof), snap)
    ctx = discover.Context(
        cell={"name": "synthetic"}, trace=trace, n=100_000,
        algo_cfg=types.SimpleNamespace(density=0.01), progspans_view=v,
        program_snapshot=snap)
    return ctx, v


def reader(name):
    return discover.load_module(os.path.join(
        discover.HERE, "metrics", name + ".py")).read


def test_join_by_step_num():
    ctx, v = context()
    assert v.steps == [11, 12, 13] and v.window_steps == 3
    assert v.joined()[1] == [1, 5, 0, 0, 0, 0, 0, 1100, 1000]
    assert v.column("local_k") == [900, 1100, 1000]
    assert reader("train_step_host_ms")(ctx) == pytest.approx(2.0)
    assert reader("dispatch_ms")(ctx) == pytest.approx(1.5)
    # steps 12 (staging repaired) and 13 (the select went wide)
    assert reader("repair_branch_steps_pct")(ctx) == pytest.approx(200 / 3)
    assert reader("overflow_blocks_per_step")(ctx) == pytest.approx(14 / 3)
    assert reader("local_k_share")(ctx) == pytest.approx(1.0)
    # host steps before the window's first (11): step 0 and step 3
    assert reader("setup_compile_s")(ctx) == pytest.approx(7.75)


def test_gaps_between_step_programs_and_who_held_the_host():
    ctx, v = context()
    # 4 ms between two steps, less the key split's 0.25 ms
    assert [round(1e3 * g, 6) for g in v.gap_s] == [3.75, 3.75]
    assert reader("step_gap_ms")(ctx) == pytest.approx(3.75)
    # the whole window: 3 x 4 ms before the steps, 8 ms after the last
    assert v.shares["oktopk"] == pytest.approx(0.00225)
    assert v.shares["bench"] == pytest.approx(0.009)
    assert v.shares["none"] == pytest.approx(0.008)
    assert sum(v.shares.values()) == pytest.approx(
        ctx.trace.window_s - ctx.trace.busy_s(ctx.trace.chips[0]))


def test_sub_scopes_sum_to_the_phase_without_the_kernels():
    ctx, _ = context()
    assert reader("select_sweep_ms")(ctx) == pytest.approx(30.0)
    assert reader("stage_finalize_ms")(ctx) == pytest.approx(50.0)
    assert reader("select_global_ms")(ctx) == pytest.approx(0.0)
    # in no sub-scope at all, or in one with no metric of its own
    ms = progspans.sub_scope_ms(ctx)
    assert ms["stage_unscoped"] == pytest.approx(4.75)
    assert ms["stage_repartition"] == pytest.approx(0.25)
    assert not progspans.has_reader("stage_repartition")
    assert reader("select_stage_unscoped_ms")(ctx) == pytest.approx(5.0)
    assert ms["kernel:oktopk_repair"] == pytest.approx(15.0)
    whole = reader("select_stage_ms")(ctx)
    assert sum(ms.values()) == pytest.approx(whole) == pytest.approx(100.0)
    # the metrics and the kernels account for all of it
    listed = ["select_sweep_ms", "select_global_ms", "stage_finalize_ms",
              "select_stage_unscoped_ms"]
    assert sum(reader(n)(ctx) for n in listed) + 15.0 == pytest.approx(whole)


def test_sub_of_keeps_the_phase_and_names_the_step():
    path = ("jit(step)/jit(shmap_body)/anat/b000/anat/b000/select/sweep/"
            "jit(fused_select_stage)/anat/select/sweep/reduce_sum")
    assert xtrace.phase_of(path) == "select"
    assert progspans.sub_of(path, SUBS) == "select_sweep"
    assert progspans.sub_of("jit(step)/anat/b000/stage/x",
                            SUBS) == "stage_unscoped"
    assert progspans.sub_of("jit(step)/anat/fwd_bwd/sweep/x", SUBS) is None
    # a name of the other phase's is no sub-scope here
    assert progspans.sub_of(
        "jit(step)/anat/b000/stage/sweep/x", SUBS) == "stage_unscoped"
    # the names are the program's: one it does not name is no sub-scope
    assert progspans.sub_of(path, {"select": ["global"]}) == "select_unscoped"


def test_a_step_without_its_counters_is_no_join():
    ctx, v = context(snap_steps=(11, 13))
    assert v.joined() is None
    assert reader("repair_branch_steps_pct")(ctx) is None
    assert reader("local_k_share")(ctx) is None
    assert reader("train_step_host_ms")(ctx) == pytest.approx(2.0)


def test_a_program_without_spans_reads_as_nothing():
    """The parent of the PR that added the spans: every new reader returns
    None and none raises; its step has no sub-scope either."""
    ctx, v = context(with_program=False)
    assert v is None
    hlo = {k: (p.replace("/sweep", "").replace("/finalize", "")
               .replace("/repartition", ""), t)
           for k, (p, t) in HLO.items()}
    ctx.trace = xtrace.read(profile(False), hlo, 3)
    for name in NEW:
        assert reader(name)(ctx) is None, name
    assert reader("select_stage_ms")(ctx) == pytest.approx(100.0)


def test_unscoped_counts_the_two_phases_only():
    """The model's sub-scopes under ``fwd_bwd`` (the program names them
    since PR 28) are not selection's unscoped time."""
    ctx, _ = context()
    ctx.sub_scope_ms = {"fwd_bwd_unscoped": 77.5, "fwd_bwd_attention": 3.0,
                        "select_sweep": 30.0, "select_threshold": 0.25,
                        "stage_unscoped": 4.75, "kernel:oktopk_repair": 15.0}
    assert reader("select_stage_unscoped_ms")(ctx) == pytest.approx(5.0)


def test_new_entries_are_appended_and_listed():
    """The eleven are in the list, each for named cells, and after the
    entries the list had before them; later PRs appended theirs behind."""
    per_layer = discover.Bench().spec["per_layer"]
    names = [m["name"] for m in per_layer]
    assert NEW <= set(names)
    first = min(names.index(n) for n in NEW)
    assert first > 0 and not NEW & set(names[:first])
    assert {names.index(n) for n in NEW} == set(
        range(first, first + len(NEW)))
    for m in per_layer:
        if m["name"] in NEW:
            assert m["workloads"], m["name"]


@pytest.mark.parametrize("cell", ["lstm_ptb_dense_x1", "lstm_ptb_oktopk_x1",
                                  "vgg16_dense_x1"])
def test_new_readers_run_in_the_rehearsal(cell):
    """``run.py --rehearse --trace 1``: every new metric listed for the
    cell finds something to read."""
    run = subprocess.run(
        [sys.executable, os.path.join(discover.HERE, "run.py"), "--workload",
         cell, "--seed", "2147483999", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1800)
    assert run.returncode == 0, run.stderr[-2000:]
    line = next(ln for ln in run.stdout.splitlines()
                if "readers ran for:" in ln)
    ran = set(json.loads(line.split("readers ran for:")[1].replace("'", '"')))
    listed = {m["name"] for m in discover.Bench().metrics("per_layer", cell)}
    assert listed & NEW and listed & NEW <= ran, (listed & NEW) - ran
    with open(os.path.join(discover.ROOT, ".bench_out", "progspans",
                           cell + ".json")) as f:
        kept = json.load(f)
    assert all(r["counters"] is not None for r in kept["steps"])
