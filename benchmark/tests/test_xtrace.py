"""The trace reduction on a small recorded TPU trace: four steps of VGG-16
oktopk on one v5e chip (256 a worker, PR 23's first chip call), cut by
``tools/record_trace.py``: steps 8-11 of the traced window, of which the
second and the fourth took the wide staging branch."""

import gzip
import json
import os
import types

import pytest

from benchlib import intervals, xtrace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "vgg16_oktopk_x1_4steps.json.gz")


def replay(rec):
    """The recording behind the few attributes of ``ProfileData`` that the
    reader uses."""
    def event(e):
        return types.SimpleNamespace(name=e[0], start_ns=e[1],
                                     duration_ns=e[2], stats=[])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p["name"], lines=[
            types.SimpleNamespace(name=ln["name"],
                                  events=[event(e) for e in ln["events"]])
            for ln in p["lines"]]) for p in rec["planes"]])


@pytest.fixture(scope="module")
def trace():
    with gzip.open(DATA, "rt") as f:
        rec = json.load(f)
    hlo = {k: tuple(v) for k, v in rec["hlo"].items()}
    return xtrace.read(replay(rec), hlo, rec["steps"])


def test_one_chip_four_steps(trace):
    assert len(trace.chips) == 1 and trace.steps == 4
    runs = trace.chips[0].step_runs(trace.window)
    assert len(runs) == 4
    # the wide steps are the long ones: ~207 ms against ~125 ms
    ms = [round((e - s) * 1e3) for s, e in runs]
    assert ms[1] > 190 and ms[3] > 190 and ms[0] < 135 and ms[2] < 135


def test_idle_share_is_one_minus_the_union_over_the_window(trace):
    chip = trace.chips[0]
    busy = trace.busy_s(chip)
    assert 0 < busy < trace.window_s
    assert trace.idle_share() == pytest.approx(1 - busy / trace.window_s)
    # a container (a conditional) and its body are not counted twice
    naive = sum(o.end - o.start for o in chip.ops)
    assert naive > 1.5 * busy
    assert 0.0 < trace.idle_share() < 0.05


def test_idle_share_is_not_cut_off(trace):
    """A window with nothing in it reads 100 %, one filled reads what is
    left: no clamp hides a term that does not belong."""
    import copy
    t = copy.deepcopy(trace)
    t.chips[0].ops = []
    assert t.idle_share() == 1.0


def test_kernel_time_by_name(trace):
    hit = lambda o: o.mentions("oktopk_fused_select")
    assert trace.count(hit) == 4                      # once a step
    per_call = trace.seconds(hit) / trace.count(hit)
    assert 0.020 < per_call < 0.027                   # 23.6 ms in the run
    assert trace.steps_with(
        lambda o: o.mentions("oktopk_stage_w1024")) == pytest.approx(0.5)


def test_phases_are_joined_from_the_compiled_steps_text(trace):
    chip = trace.chips[0]
    fused = [o for o in chip.ops if o.name.startswith("oktopk_fused_select")]
    assert fused and all(o.phase == "select" for o in fused)
    assert all(xtrace.is_kernel(o) for o in fused)
    per_step = {ph: 1e3 * trace.seconds(lambda o: o.phase == ph) / trace.steps
                for ph in ("fwd_bwd", "select", "stage", "optimizer")}
    # VGG-16 at 256 a worker: a few ms of model, tens of ms of selection
    assert 2.0 < per_step["fwd_bwd"] < 6.0
    assert per_step["select"] > 50 and per_step["stage"] > 25
    both = 1e3 * trace.seconds(
        lambda o: o.phase in ("select", "stage")) / trace.steps
    assert both == pytest.approx(per_step["select"] + per_step["stage"],
                                 rel=1e-6)   # phases do not overlap on a chip


def test_phase_of():
    f = xtrace.phase_of
    assert f("jit(f)/anat/b000/cond/branch_0_fun/anat/b000/select/"
             "jit(g)/anat/select/oktopk_fused_select/pallas_call") == "select"
    assert f("jit(f)/anat/fwd_bwd/jvp(VGG)/Conv_0/conv") == "fwd_bwd"
    assert f("jit(f)/anat/b003/lvl1/exchange/all_to_all") == "exchange"
    assert f("jit(f)/anat/b000/psum") is None
    assert f("jit(f)/mul") is None


def test_exposed_collective_arithmetic():
    """all-to-all 0-4 ms with compute under 1-2 ms of it; all-gather 6-7 ms
    wholly under compute: 3 ms are exposed."""
    ms = 1e-3
    ops = [xtrace.Op("fusion.1", ms, 2 * ms), xtrace.Op("fusion.2", 5 * ms, 8 * ms),
           xtrace.Op("all-reduce.3", 8 * ms, 8.5 * ms)]
    beside = [xtrace.Op("all-to-all.1", 0, 4 * ms),
              xtrace.Op("all-gather.2", 6 * ms, 7 * ms)]
    xtrace.mark_containers(ops)
    t = xtrace.Trace([xtrace.Chip("c", ops, [], beside)], [],
                     (0.0, 10 * ms), 1)
    assert t.exposed_collective_s() == pytest.approx(3.5 * ms)
    assert t.busy_s(t.chips[0]) == pytest.approx(4.5 * ms)


def test_breakdown_names_kernels_and_labels_gaps():
    ms = 1e-3
    ops = [xtrace.Op("oktopk_fused_select.2", 0, 4 * ms,
                     "jit(f)/anat/select/x/pallas_call", "tpu_custom_call"),
           xtrace.Op("fusion.7", 6 * ms, 7 * ms, "jit(f)/anat/fwd_bwd/conv/c")]
    t = xtrace.Trace([xtrace.Chip("c", ops)],
                     [("block", 0.0, 5.5 * ms), ("data", 5.5 * ms, 6 * ms)],
                     (0.0, 8 * ms), 1)
    b = t.breakdown()
    assert b["device_ops"][0] == ["oktopk_fused_select", pytest.approx(4 * ms)]
    assert b["device_ops"][1] == ["anat/fwd_bwd", pytest.approx(ms)]
    gaps = dict(b["idle_gaps"])
    assert gaps["block"] == pytest.approx(2 * ms)   # 4-6 ms, mostly block
    assert gaps["none"] == pytest.approx(ms)        # 7-8 ms, no host span
    assert intervals.length([(o.start, o.end) for o in ops]) == pytest.approx(
        5 * ms)


def test_the_step_program_is_picked_by_time_not_by_count():
    """The key split runs once a step too, a few microseconds each, and a
    third program once: the step is the one that took most of the window,
    whatever the order the names come in."""
    ms = 1e-3
    for first in ("jit__threefry_split", "jit_step"):
        second = ({"jit__threefry_split", "jit_step"} - {first}).pop()
        span = lambda name, s: (name, s, s + (100 * ms if name == "jit_step"
                                              else 0.005 * ms))
        modules = [span(n, i * 120 * ms + (0 if n == first else 101 * ms))
                   for i in range(4) for n in (first, second)]
        modules.append(("jit_other", 490 * ms, 495 * ms))
        chip = xtrace.Chip("c", [], modules)
        runs = chip.step_runs((0.0, 500 * ms))
        assert len(runs) == 4, first
        assert all(e - s == pytest.approx(100 * ms) for s, e in runs)
        assert xtrace.Chip("c", []).step_runs((0.0, 1.0)) == []
        # an execution that starts before the window is not of it
        assert len(chip.step_runs((50 * ms, 500 * ms))) == (
            3 if first == "jit_step" else 4)
