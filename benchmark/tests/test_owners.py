"""The owners' table (``benchlib/owners.py``) and its eight readers: numbers
on a stand-in trace, None for a program without ``anatomy.owners`` (the
parent of the PR that added it), and the readers under ``--rehearse``."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchlib import discover, owners, xtrace

MS = 1_000_000   # ns
NEW = {"unowned_ms", "inherited_ms", "fwd_bwd_owned_ms", "combine_owned_ms",
       "attention_owned_ms", "experts_owned_ms",
       "linear_attention_owned_ms", "optimizer_owned_ms"}
EVERY_CELL = {"unowned_ms", "inherited_ms", "fwd_bwd_owned_ms",
              "optimizer_owned_ms"}

HLO = """HloModule jit_step

%body (arg: (f32[8])) -> (f32[8]) {
  %arg = (f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%arg), index=0
  %copy-start.1 = (f32[8], f32[8], u32[]) copy-start(%x)
  %copy-done.1 = f32[8] copy-done(%copy-start.1)
  %scores.2 = f32[8] fusion(%copy-done.1), kind=kLoop, calls=%f, metadata={op_name="jit(step)/anat/fwd_bwd/jvp(M)/anat/fwd_bwd/attention/mul"}
  ROOT %out = (f32[8]) tuple(%scores.2)
}

%rows (pa: f32[8]) -> f32[8] {
  %pa = f32[8] parameter(0)
  %gather.3 = f32[8] fusion(%pa), kind=kLoop, calls=%g, metadata={op_name="jit(step)/anat/fwd_bwd/experts/gather"}
  ROOT %ragged-dot-none.4 = f32[8] custom-call(%gather.3), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}

ENTRY %main (w: f32[8], lonely: f32[8]) -> f32[8] {
  %w = f32[8] parameter(0)
  %lonely = f32[8] parameter(1)
  %orphan.9 = f32[8] copy(%lonely)
  %init = (f32[8]) tuple(%w)
  %while.5 = (f32[8]) while(%init), condition=%c, body=%body, metadata={op_name="jit(step)/anat/fwd_bwd/attention/while"}
  %y = f32[8] get-tuple-element(%while.5), index=0
  %router.6 = f32[8] fusion(%y), kind=kLoop, calls=%r, metadata={op_name="jit(step)/anat/fwd_bwd/router/dot_general"}
  %cond.7 = f32[8] conditional(%p, %router.6, %router.6), branch_computations={%rows, %rows}, metadata={op_name="jit(step)/anat/fwd_bwd/experts/cond"}
  %delta.8 = f32[8] fusion(%cond.7), kind=kLoop, calls=%d, metadata={op_name="jit(step)/anat/fwd_bwd/linear_attention/x/anat/fwd_bwd/delta_rule/dot"}
  %scatter.10 = f32[8] fusion(%delta.8), kind=kLoop, calls=%s, metadata={op_name="jit(step)/anat/b000/combine/scatter-add"}
  ROOT %sgd.11 = f32[8] fusion(%scatter.10), kind=kLoop, calls=%o, metadata={op_name="jit(step)/anat/optimizer/sub"}
}
"""


def ev(name, start_ms, dur_ms):
    return types.SimpleNamespace(name=name, start_ns=int(start_ms * MS),
                                 duration_ns=int(dur_ms * MS), stats=[])


def profile(steps=2):
    """Steps of 100 ms, 4 ms apart. The loop spans a copy and the scores
    and 2 ms of its own; the branch spans a gather and the kernel."""
    mods, ops, host = [], [], [ev("bench/window", 0, 104 * steps + 4)]
    for i in range(steps):
        t = 4 + 104 * i
        mods.append(ev("jit_step(1)", t, 100))
        ops += [ev("%while.5 = (f32[8]) while(%init)", t, 32),
                ev("%copy-done.1 = f32[8] copy-done(%copy-start.1)", t, 10),
                ev("%scores.2 = f32[8] fusion(%copy-done.1)", t + 10, 20),
                ev("%router.6 = f32[8] fusion(%y)", t + 32, 8),
                ev("%cond.7 = f32[8] conditional(%p)", t + 40, 30),
                ev("%gather.3 = f32[8] fusion(%pa)", t + 40, 5),
                ev("%ragged-dot-none.4 = f32[8] custom-call(%gather.3)",
                   t + 45, 25),
                ev("%delta.8 = f32[8] fusion(%cond.7)", t + 70, 15),
                ev("%scatter.10 = f32[8] fusion(%delta.8)", t + 85, 6),
                ev("%sgd.11 = f32[8] fusion(%scatter.10)", t + 91, 5),
                ev("%orphan.9 = f32[8] copy(%lonely)", t + 96, 4)]
        host += [ev("bench/data", t - 4, 3), ev("bench/dispatch", t - 1, 2)]
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            types.SimpleNamespace(name="XLA Modules", events=mods),
            types.SimpleNamespace(name="XLA Ops", events=ops)]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            types.SimpleNamespace(name="python", events=host)])])


def reader(name):
    return discover.load_module(os.path.join(
        discover.HERE, "metrics", name + ".py")).read


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    monkeypatch.setattr(discover, "ROOT", str(tmp_path))
    d = tmp_path / ".bench_out" / "trace" / "synthetic"
    d.mkdir(parents=True)
    (d / "step.hlo.txt").write_text(HLO)
    trace = xtrace.read(profile(), xtrace.hlo_paths(HLO), 2)
    return discover.Context(cell={"name": "synthetic"}, trace=trace)


def test_the_readers_on_a_stand_in_trace(ctx):
    t = owners.table(ctx)
    assert t["steps"] == 2 and t["instructions"] == 20
    # the copy (pair), the kernel (operand) and nothing else inherit
    assert reader("inherited_ms")(ctx) == pytest.approx(10 + 25)
    assert reader("unowned_ms")(ctx) == pytest.approx(4.0)
    # own 20 + the copy 10 + the loop's own 2
    assert reader("attention_owned_ms")(ctx) == pytest.approx(32.0)
    # router 8, gather 5, the kernel by rule 25; the branch has no time of
    # its own here
    assert reader("experts_owned_ms")(ctx) == pytest.approx(38.0)
    assert reader("linear_attention_owned_ms")(ctx) == pytest.approx(15.0)
    assert reader("combine_owned_ms")(ctx) == pytest.approx(6.0)
    assert reader("optimizer_owned_ms")(ctx) == pytest.approx(5.0)
    assert reader("fwd_bwd_owned_ms")(ctx) == pytest.approx(85.0)
    assert [r["name"] for r in t["largest_inherited"]] == [
        "ragged-dot-none.4", "copy-done.1"]
    assert [r["how"] for r in t["largest_inherited"]] == ["operand", "pair"]


def test_the_table_closes_and_its_own_part_is_the_old_readers(ctx):
    t = owners.table(ctx)
    busy = 1e3 * ctx.trace.busy_s(ctx.trace.chips[0]) / ctx.trace.steps
    total = sum(sum(r[k] for k in owners.KINDS)
                for r in t["owners"].values()) + t["unowned_ms"]
    assert total == pytest.approx(busy) == pytest.approx(t["busy_ms"])
    own = sum(r["own_ms"] for k, r in t["owners"].items()
              if k.split("/")[0] == "fwd_bwd")
    assert own == pytest.approx(reader("fwd_bwd_ms")(ctx))
    assert t["owners"]["optimizer"]["own_ms"] == pytest.approx(
        reader("optimizer_ms")(ctx))
    kept = json.load(open(os.path.join(
        discover.ROOT, ".bench_out", "owners", "synthetic.json")))
    assert kept["owners"] == t["owners"]
    assert kept["owners_s"] >= 0 and kept["table_s"] >= 0


def test_a_program_without_the_map_reads_as_nothing(ctx, monkeypatch):
    """The parent of the PR that added ``anatomy.owners``: every new reader
    returns None and none raises; the old readers read as before."""
    from oktopk_tpu.obs import anatomy
    monkeypatch.delattr(anatomy, "owners")
    for name in sorted(NEW):
        assert reader(name)(ctx) is None, name
    assert owners.table(ctx) is None
    assert reader("fwd_bwd_ms")(ctx) == pytest.approx(48.0)
    assert not os.path.exists(os.path.join(discover.ROOT, ".bench_out",
                                           "owners"))


def test_no_trace_or_no_step_text_reads_as_nothing(ctx):
    os.remove(os.path.join(discover.ROOT, ".bench_out", "trace", "synthetic",
                           "step.hlo.txt"))
    assert all(reader(n)(ctx) is None for n in NEW)
    bare = discover.Context(cell={"name": "synthetic"}, trace=None)
    assert all(reader(n)(bare) is None for n in NEW)


def test_the_eight_entries_and_their_cells():
    spec = discover.load_json(os.path.join(discover.ROOT, "BENCHMARK.json"))
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in NEW}
    assert set(mine) == NEW
    cells = [w["name"] for w in spec["workloads"]]
    for name, m in mine.items():
        assert (m["unit"], m["better"], m["source"]) == (
            "ms", "lower", "device_trace")
        assert (set(m["workloads"]) == set(cells)) == (name in EVERY_CELL)


@pytest.mark.parametrize("cell,expect", [
    ("lstm_ptb_dense_x1", EVERY_CELL),
    ("lstm_ptb_oktopk_x1", EVERY_CELL | {"combine_owned_ms"}),
])
def test_rehearsal_runs_the_readers(cell, expect):
    root = os.path.dirname(discover.HERE)
    cmd = [sys.executable, os.path.join(discover.HERE, "run.py"),
           "--workload", cell, "--seed", "2147483700",
           "--seconds", "1", "--trace", "1", "--rehearse"]
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=root,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = next(ln for ln in p.stdout.splitlines() if "readers ran for:" in ln)
    assert expect <= set(eval(line.split("readers ran for:")[1]))
    assert json.loads(p.stdout.strip().splitlines()[-1])["metrics"] == {}
    kept = json.load(open(os.path.join(root, ".bench_out", "owners",
                                       cell + ".json")))
    total = sum(sum(r[k] for k in owners.KINDS)
                for r in kept["owners"].values()) + kept["unowned_ms"]
    assert total == pytest.approx(kept["busy_ms"], rel=1e-9)
