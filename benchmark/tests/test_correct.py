"""``correct`` at a size a test run can hold (``lstm_tiny``, ``caffe_cifar``,
on the CPU): the plain references agree with the program; the control
(the program's own bfloat16 compute path) does not; and a run whose timed
path is broken underneath comes out as not correct."""

import json

import jax
import numpy as np
import pytest

import run
from benchlib import discover
from benchlib.harness import Harness

CELLS = ["vgg16_dense_x1", "lstm_ptb_dense_x1", "lstm_ptb_oktopk_x1"]
# four workers on the CPU's four virtual devices: no cell of BENCHMARK.json
# has them yet, the exchange's reference is held to them here
X4 = {"name": "scratch_oktopk_x4", "config": "vgg16_cifar10",
      "traffic": "oktopk", "chips": 4}
# float32 on both sides on the CPU: what is left is summation order
CPU_SOUND = {"loss_gap": 1e-5, "grad1_gap": 1e-5, "dparam3_gap": 1e-5}
# a coordinate at the edge of a threshold may flip with the rounding
CPU_SOUND_EXCHANGE = {"delivered_gap": 1e-3, "residual_gap": 1e-3,
                      "support_mismatch": 1e-3}
SETTLE = 8      # steps before the compared one: a test need not settle


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    from oktopk_tpu.ops import compaction
    monkeypatch.setattr(compaction, "mesh_supports_pallas", lambda mesh: True)


@pytest.fixture(autouse=True)
def short_settling(monkeypatch):
    real = discover.Bench.traffic

    def short(self, name):
        # nor does it go on to where a window of whole periods starts
        spec = dict(real(self, name), settle_steps=SETTLE)
        spec.pop("window", None)
        return spec
    monkeypatch.setattr(discover.Bench, "traffic", short)


def settled(cell, seed, over=None):
    """The harness of ``cell`` at the rehearsal's sizes, past its first
    three steps and its settling; with the files it was built from."""
    bench = discover.Bench()
    if isinstance(cell, str):
        cell = bench.cell(cell)
    config = bench.config(cell["config"])
    config = discover.merge(config, config["rehearse"])
    if over:
        config = discover.merge(config, over)
    h = Harness(cell, config, bench.traffic(cell["traffic"]), seed, True)
    h.seed_state(seed)
    h.first_steps()
    h.settle()
    return bench, config, h


def numbers(cell, seed, over=None, window_steps=0):
    bench, config, h = settled(cell, seed, over)
    win = h.window(0.0, max_steps=window_steps) if window_steps else None
    return h.numbers(bench.reference(config["reference"]), win,
                     config.get("reference_precision"))


@pytest.mark.parametrize("cell", CELLS + [X4], ids=lambda c: str(
    c if isinstance(c, str) else c["name"]))
def test_reference_agrees_with_the_program(cell):
    got = numbers(cell, seed=2 ** 31 + 7)
    sparse = "delivered_gap" in got
    assert sparse == (cell is X4 or "oktopk" in str(cell))
    assert ("replica_gap" in got) == (cell is X4)
    for name, limit in dict(CPU_SOUND, **(CPU_SOUND_EXCHANGE if sparse
                                          else {})).items():
        assert got[name] < limit, (name, got)


def drop_one_workers_payload(x, cfg, step):
    """The values worker 1 puts on the wire, zeroed (its indices still
    travel): its share of every sum is lost."""
    from jax import lax
    return x * (lax.axis_index("data") != 1).astype(x.dtype)


def a_tenth_of_k(monkeypatch):
    """The collective selects for a tenth of the k that the configuration
    states: less to stage and to send, the rest stays in the residual."""
    from oktopk_tpu.collectives import oktopk
    real = oktopk.scheduled_k
    monkeypatch.setattr(oktopk, "scheduled_k",
                        lambda cfg, step: max(1, real(cfg, step) // 10))


def test_a_dropped_payload_fails_the_exchange(monkeypatch):
    from oktopk_tpu.collectives import wire
    monkeypatch.setattr(wire, "_WIRE_FAULT", drop_one_workers_payload)
    got = numbers(X4, seed=11)
    # about a quarter of what should arrive does not
    assert got["delivered_gap"] > 0.1, got
    assert got["loss_gap"] < 1e-5          # the model's math is untouched


@pytest.mark.parametrize("cell", ["lstm_ptb_oktopk_x1", X4], ids=str)
def test_a_tenth_of_the_density_fails_the_delivered_share(monkeypatch, cell):
    from benchlib import check
    a_tenth_of_k(monkeypatch)
    got = numbers(cell, seed=12, window_steps=6)
    limits = discover.Bench().limits("lstm_ptb_oktopk_x1")
    assert got["delivered_share_min"] < 0.3, got
    ok, lines = check.judge(
        {"delivered_share_min": got["delivered_share_min"]},
        {"delivered_share_min": limits["delivered_share_min"]})
    assert not ok, lines
    # the thresholds it selected with are the state's: the step itself is
    # a sound step at a tenth of the density, and only the count tells
    assert got["delivered_gap"] < 1e-5 and got["residual_gap"] < 1e-5, got


def test_batch_norm_reference_agrees_with_vgg16_itself():
    """The rehearsal's ``caffe_cifar`` has no batch normalisation, so the
    plain CNN reference is also held against the full VGG-16, eight images
    on the CPU. Float32 on both sides; the gradient of thirteen
    batch-normalised layers at seeded weights amplifies rounding some
    thousandfold (PERF.md, Findings of PR 23), hence the wider limit."""
    bench = discover.Bench()
    cell = bench.cell("vgg16_dense_x1")
    config = discover.merge(bench.config(cell["config"]),
                            {"train": {"batch_size": 8}})
    h = Harness(cell, config, bench.traffic(cell["traffic"]), 3)
    h.seed_state(3)
    h.first_steps()
    got = h.numbers(bench.reference(config["reference"]), None, None)
    assert got["loss_gap"] < 1e-5, got
    assert got["grad1_gap"] < 5e-3 and got["dparam3_gap"] < 5e-3, got


@pytest.mark.parametrize("cell", ["vgg16_dense_x1", "lstm_ptb_dense_x1"])
def test_the_control_fails_the_cells_limits(cell):
    """bfloat16 compute, the step a later PR would be tempted by, is
    outside the limits that the cell's file sets from the chip's readings."""
    from benchlib import check
    got = numbers(cell, seed=5, over={"train": {"compute_dtype": "bfloat16"}})
    limits = discover.Bench().limits(cell)
    five = {k: got[k] for k in ("loss_gap", "grad1_gap", "dparam3_gap",
                                "grad1_diff_q1", "dparam3_diff_q1")}
    ok, lines = check.judge(five, {k: limits[k] for k in five})
    assert not ok, lines


def run_main(capsys, cell, seconds="0.5"):
    rc = run.main(["--workload", cell, "--seed", "9", "--seconds", seconds,
                   "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_a_sound_rehearsal_is_correct(capsys):
    rc, result, out = run_main(capsys, "vgg16_dense_x1")
    assert rc == 0 and result["correct"] is True, out
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"


def test_a_sound_sparse_rehearsal_is_correct(capsys):
    rc, result, out = run_main(capsys, "lstm_ptb_oktopk_x1", "3")
    assert rc == 0 and result["correct"] is True, out


@pytest.mark.parametrize("fault", ["nothing_delivered", "a_tenth_of_k"])
def test_a_broken_sparse_step_is_not_correct(capsys, monkeypatch, fault):
    """The rest of a run of a sparse cell with the collective broken
    underneath: the values that cross the wire zeroed where they are
    produced, or a tenth of the stated density selected."""
    if fault == "a_tenth_of_k":
        a_tenth_of_k(monkeypatch)
    else:
        from oktopk_tpu.collectives import wire
        monkeypatch.setattr(wire, "_WIRE_FAULT",
                            lambda x, cfg, step: x * 0)
    rc, result, out = run_main(capsys, "lstm_ptb_oktopk_x1", "3")
    assert rc == 0 and result["correct"] is False, out
    bad = [l for l in out if "NOT ok" in l]
    want = "delivered_share_min" if fault == "a_tenth_of_k" else "delivered_gap"
    assert any(want in l for l in bad), bad


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, fault):
    """The rest of a run, the look for a chip skipped, with the step
    broken underneath: a step that returns its state unchanged, or one
    that leaves out half of its rows."""
    from oktopk_tpu.train import trainer as trainer_mod
    real = trainer_mod.Trainer.train_step

    def state_unchanged(self, batch):
        kept = jax.tree.map(np.asarray, self.state)
        metrics = real(self, batch)
        self.state = jax.device_put(
            kept, jax.tree.map(lambda x: x.sharding, self.state))
        return metrics

    def half_the_batch(self, batch):
        half = {k: np.concatenate([v[:len(v) // 2]] * 2)
                for k, v in batch.items()}
        return real(self, half)

    monkeypatch.setattr(trainer_mod.Trainer, "train_step",
                        {"state_unchanged": state_unchanged,
                         "half_the_batch": half_the_batch}[fault])
    rc, result, out = run_main(capsys, "vgg16_dense_x1")
    assert rc == 0 and result["correct"] is False, out


@pytest.fixture
def scratch_x4(monkeypatch):
    """``X4`` as a cell that ``run.py`` finds: no cell of BENCHMARK.json
    has four workers, so the scratch cell borrows the sparse cell's limits
    and holds its replicas equal."""
    cell, limits = discover.Bench.cell, discover.Bench.limits
    monkeypatch.setattr(
        discover.Bench, "cell",
        lambda self, name: X4 if name == X4["name"] else cell(self, name))
    monkeypatch.setattr(
        discover.Bench, "limits",
        lambda self, name: dict(limits(self, "lstm_ptb_oktopk_x1"),
                                replica_gap=0.0)
        if name == X4["name"] else limits(self, name))


FAULTS_X4 = ["dropped_payload", "replica_off_by_an_ulp",
             "a_workers_rows_left_out"]


@pytest.mark.parametrize("fault", ["none"] + FAULTS_X4)
def test_a_broken_path_on_four_workers_is_not_correct(capsys, monkeypatch,
                                                      scratch_x4, fault):
    """The rest of a run on four workers, sound and then with the path
    broken underneath: one worker's values zeroed on the wire, one
    replica's parameters an ulp off after a step, the last worker's rows
    replaced by the first's."""
    from oktopk_tpu.train import trainer as trainer_mod
    real = trainer_mod.Trainer.train_step
    state = {"steps": 0}

    def replica_off_by_an_ulp(self, batch):
        metrics = real(self, batch)
        state["steps"] += 1
        if state["steps"] == 5:
            def nudge(x):
                shards = [np.asarray(s.data) for s in x.addressable_shards]
                shards[1] = np.nextafter(shards[1], np.float32(np.inf))
                return jax.make_array_from_single_device_arrays(
                    x.shape, x.sharding,
                    [jax.device_put(a, s.device) for a, s in
                     zip(shards, x.addressable_shards)])
            leaves, treedef = jax.tree.flatten(self.state.params)
            leaves[0] = nudge(leaves[0])
            self.state = self.state.replace(
                params=jax.tree.unflatten(treedef, leaves))
        return metrics

    def a_workers_rows_left_out(self, batch):
        rows = len(batch["label"]) // 4
        return real(self, {k: np.concatenate([v[:3 * rows], v[:rows]])
                           for k, v in batch.items()})

    want = {"none": None, "dropped_payload": "delivered_gap",
            "replica_off_by_an_ulp": "replica_gap",
            "a_workers_rows_left_out": "_gap"}[fault]
    if fault == "dropped_payload":
        from oktopk_tpu.collectives import wire
        monkeypatch.setattr(wire, "_WIRE_FAULT", drop_one_workers_payload)
    elif fault != "none":
        monkeypatch.setattr(trainer_mod.Trainer, "train_step", locals()[fault])
    rc, result, out = run_main(capsys, X4["name"], "1")
    bad = [l for l in out if "NOT ok" in l]
    if fault == "none":
        assert rc == 0 and result["correct"] is True, out
        assert any("replica_gap" in l for l in out) and not bad, out
        return
    assert rc == 0 and result["correct"] is False, out
    assert any(want in l for l in bad), bad
