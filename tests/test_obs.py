"""Observability: wire-level volume conformance + unified run journal.

Two halves:

1. Conformance — every algorithm's REALISED wire bytes (the
   SparseState accounting threaded through the collectives) must fit
   under its analytic budget (obs/volume.py). For oktopk this is the
   paper's 6k-scalar O(k) claim measured on the wire; for topkA the
   budget is exactly kP pairs; for the capacity-bound family it is the
   fixed buffers' hard ceiling. Plus the headline separation: oktopk's
   measured traffic must sit well under topkA's O(kP).

2. Integration — a real 30-step mnistnet training run with autotune,
   resilience, an injected wire fault and anomaly tracing produces ONE
   journal carrying every stream behind one header, with guard_trip
   followed by trace_captured, and scripts/obs_report.py renders it.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.collectives import wire
from oktopk_tpu.collectives.api import batched_init_state, \
    build_allreduce_step
from oktopk_tpu.config import OkTopkConfig, TrainConfig
from oktopk_tpu.data.synthetic import synthetic_batch
from oktopk_tpu.obs import volume as obs_volume
from oktopk_tpu.obs.events import validate_journal
from oktopk_tpu.resilience.faults import FaultPlan, FaultSpec, make_wire_hook
from oktopk_tpu.train.trainer import Trainer

pytestmark = pytest.mark.obs

# every distinct implementation; registry aliases (gaussiankconcat,
# topkDSA) share these wires and are covered by the budget-equality test
# without paying another jit compile
ALGOS = ["dense", "topkA", "topkA2", "topkAopt", "gtopk", "gaussiank",
         "gaussiankSA", "topkSA", "oktopk"]

# every conformance test uses the identical config, so measure each
# algorithm once per session instead of recompiling per test
_WIRE_CACHE = {}


def _measure_wire_bytes(name, cfg, mesh, rng, steps=9):
    """Per-step mean realised wire bytes (averaged over workers) in
    steady state: oktopk's every-4th-step exact recomputes draw from the
    larger cap_exact pool and are excluded, exactly like bench.py's
    volume probe."""
    if name in _WIRE_CACHE:
        return _WIRE_CACHE[name]
    step = build_allreduce_step(name, cfg, mesh, warmup=False)
    state = batched_init_state(cfg)
    base = rng.randn(cfg.num_workers, cfg.n).astype(np.float32)
    wires = []
    for i in range(steps):
        grads = jnp.asarray(
            base + 0.3 * rng.randn(cfg.num_workers, cfg.n).astype(np.float32))
        _, state = step(grads, state)
        if name != "oktopk" or i % cfg.global_recompute_every != 0:
            wires.append(float(np.asarray(state.last_wire_bytes).mean()))
    _WIRE_CACHE[name] = sum(wires) / len(wires)
    return _WIRE_CACHE[name]


class TestWireConformance:
    N = 1 << 16

    def _cfg(self):
        return OkTopkConfig(n=self.N, num_workers=8, density=0.01,
                            warmup_steps=0, local_recompute_every=1,
                            global_recompute_every=4)

    @pytest.mark.parametrize("name", ALGOS)
    def test_measured_bytes_within_budget(self, name, mesh8, rng):
        cfg = self._cfg()
        mean_wire = _measure_wire_bytes(name, cfg, mesh8, rng)
        assert mean_wire > 0, f"{name} reported no wire traffic"
        ratio = obs_volume.conformance_ratio(name, cfg, mean_wire)
        assert ratio <= 1.0 + 1e-6, (
            f"{name}: measured {mean_wire:.0f} B/step exceeds analytic "
            f"budget {obs_volume.budget_bytes(name, cfg):.0f} B "
            f"(ratio {ratio:.3f})")

    def test_budget_never_exceeds_capacity(self):
        cfg = self._cfg()
        for name in ALGOS:
            assert (obs_volume.budget_bytes(name, cfg)
                    <= obs_volume.capacity_bytes(name, cfg) * (1 + 1e-9))

    def test_aliases_share_budgets(self):
        cfg = self._cfg()
        assert (obs_volume.budget_bytes("gaussiankconcat", cfg)
                == obs_volume.budget_bytes("gaussiank", cfg))
        assert (obs_volume.budget_bytes("topkDSA", cfg)
                == obs_volume.budget_bytes("topkSA", cfg))

    def test_unknown_algorithm_raises(self):
        with pytest.raises(ValueError, match="no wire-byte budget"):
            obs_volume.budget_bytes("warp_drive", self._cfg())

    def test_oktopk_vs_topka_separation(self, mesh8, rng):
        """The paper's headline: oktopk moves O(k) scalars where the
        allgather baseline moves O(kP) — on the wire, not on paper."""
        cfg = self._cfg()
        ok = _measure_wire_bytes("oktopk", cfg, mesh8, rng)
        ta = _measure_wire_bytes("topkA", cfg, mesh8, rng)
        assert ta / ok >= 2.0, (
            f"expected O(kP) vs O(k) separation at P=8, got "
            f"topkA={ta:.0f} B vs oktopk={ok:.0f} B ({ta / ok:.2f}x)")

    def test_dense_psum_bytes_are_f32_values_only(self, mesh8, rng):
        """The dense baseline's psum moves 2n f32 values — no indices,
        no wire rounding — so its bytes are exactly 8n."""
        cfg = self._cfg()
        mean_wire = _measure_wire_bytes("dense", cfg, mesh8, rng)
        assert mean_wire == pytest.approx(8.0 * self.N)


def _load_obs_report():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "obs_report.py")
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestRunJournalIntegration:
    STEPS = 30

    def test_unified_journal_end_to_end(self, mesh4, tmp_path, monkeypatch):
        """One real training run -> one journal with every stream:
        autotune decision, per-step metrics, planned fault, guard trips,
        the anomaly-armed trace capture AFTER the trip, per-bucket
        volume report — all behind a single header — and the report CLI
        renders it."""
        # CPU device tracing of full mnistnet train steps takes minutes
        # and its serialized trace is enormous; stub the profiler seam
        # (the AnomalyTracer arm/open/close logic under test is all
        # host-side) — the real jax.profiler path is exercised on a tiny
        # region in test_obs_schema.py.
        prof_calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: prof_calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: prof_calls.append(("stop", None)))
        journal_path = str(tmp_path / "run_journal.jsonl")
        plan = FaultPlan((FaultSpec("wire_bitflip", step=5, duration=2,
                                    worker=2),))
        prev = wire.install_wire_fault(make_wire_hook(plan))
        try:
            cfg = TrainConfig(
                dnn="mnistnet", dataset="mnist", batch_size=8, lr=0.05,
                compressor="oktopk", density=0.05,
                resilience=True, resilience_cooldown=0,
                autotune=True,
                obs=True, obs_journal=journal_path,
                obs_quality=True, obs_quality_every=8,
                obs_trace_on_anomaly=True, obs_trace_steps=2,
                obs_trace_dir=str(tmp_path / "traces"),
                obs_regress_key="oktopk_ms")
            acfg = OkTopkConfig(warmup_steps=0, local_recompute_every=2,
                                global_recompute_every=4,
                                repartition_every=4)
            tr = Trainer(cfg, mesh=mesh4, warmup=False, algo_cfg=acfg,
                         fault_plan=plan)
            # synthetic trial timings keep the tuner on the sparse plan
            # so the wire fault has a payload to corrupt
            tr.autotune(step=0, fake_ms=lambda algo, n, d:
                        5.0 if algo == "dense" else 1.0)
            rng = np.random.RandomState(9)
            batches = iter([synthetic_batch("mnistnet", 8, rng)
                            for _ in range(self.STEPS)])
            tr.train(batches, self.STEPS, log_every=10)
        finally:
            wire.install_wire_fault(prev)

        from oktopk_tpu.autotune.journal import read_journal
        entries = read_journal(journal_path)
        events = [e["event"] for e in entries]

        # one journal, one header, schema-clean
        assert events[0] == "header"
        assert events.count("header") == 1
        assert validate_journal(entries) == []

        # every stream is present
        assert "autotune_decision" in events
        assert "step" in events
        assert "fault_seen" in events
        assert "guard_trip" in events
        assert "volume_report" in events

        # the injected wire fault tripped the guard, and the trip armed
        # a trace window that closed IN THE SAME JOURNAL, after it
        assert "trace_captured" in events
        assert events.index("guard_trip") < events.index("trace_captured")
        cap = next(e for e in entries if e["event"] == "trace_captured")
        assert cap["trigger"].startswith("guard_trip@")
        assert cap["logdir"] is not None
        assert prof_calls and prof_calls[0][0] == "start"
        assert prof_calls[-1][0] == "stop"

        # per-step metrics carry the wire-byte accounting
        steps = [e for e in entries if e["event"] == "step"]
        assert len(steps) == self.STEPS
        assert all(e.get("wire_bytes", 0) > 0 for e in steps)

        # volume report covers the single bucket with a real budget
        rep = next(e for e in entries if e["event"] == "volume_report")
        assert rep["algo"] == "oktopk"
        assert rep["budget_bytes"] > 0
        assert rep["mean_wire_bytes"] > 0

        # the signal-fidelity plane journalled alongside: per-window
        # quality flushes, each immediately rolled up, faulted run
        # included — and the whole journal is still schema-clean
        quality = [e for e in entries if e["event"] == "quality"]
        rollups = [e for e in entries if e["event"] == "quality_rollup"]
        assert quality and len(rollups) == len(quality)
        assert sum(e["count"] for e in quality) == self.STEPS
        assert all(e["algo"] == "oktopk" for e in quality)

        # the report CLI renders this exact journal
        mod = _load_obs_report()
        text = mod.render_report(entries)
        assert "run journal report" in text
        assert "incident timeline" in text
        assert "volume conformance" in text
        assert "signal fidelity" in text
        assert "schema: OK" in text

    def test_sa_split_skips_keep_wire_and_quality_consistent(
            self, mesh4, tmp_path):
        """A nan_grad fault through the split-allreduce path with the
        guard armed: skipped steps must advance BOTH accounting planes —
        every step event still carries wire bytes, and the quality ring
        still journals one row per step with the skips flagged, in an
        unbroken step sequence."""
        STEPS = 12
        journal_path = str(tmp_path / "run_journal.jsonl")
        plan = FaultPlan((FaultSpec("nan_grad", step=4, duration=2,
                                    worker=1),))
        cfg = TrainConfig(
            dnn="mnistnet", dataset="mnist", batch_size=8, lr=0.05,
            compressor="topkSA", density=0.05,
            resilience=True, resilience_cooldown=0,
            obs=True, obs_journal=journal_path,
            obs_quality=True, obs_quality_every=4)
        acfg = OkTopkConfig(warmup_steps=0)
        tr = Trainer(cfg, mesh=mesh4, warmup=False, algo_cfg=acfg,
                     fault_plan=plan)
        rng = np.random.RandomState(11)
        batches = iter([synthetic_batch("mnistnet", 8, rng)
                        for _ in range(STEPS)])
        tr.train(batches, STEPS, log_every=100)

        from oktopk_tpu.autotune.journal import read_journal
        entries = read_journal(journal_path)
        events = [e["event"] for e in entries]
        assert validate_journal(entries) == []
        assert "guard_trip" in events

        # wire accounting advanced on every step, skips included
        steps = [e for e in entries if e["event"] == "step"]
        assert len(steps) == STEPS
        assert all(e.get("wire_bytes", 0) > 0 for e in steps)

        # quality accounting matches: one ring row per step, the guard
        # skips flagged rather than dropped, step sequence unbroken
        quality = [e for e in entries if e["event"] == "quality"]
        all_steps = [s for e in quality for s in e["steps"]]
        assert all_steps == list(range(1, STEPS + 1))
        skipped = sum(s for e in quality for s in e["skipped"])
        assert skipped >= 1, "guard never skipped — fault not exercised"
        assert skipped < STEPS

    def test_journal_default_off_is_free(self, mesh4):
        """obs=False leaves no bus/journal/tracer on the trainer."""
        cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                          lr=0.05, compressor="dense", density=1.0)
        acfg = OkTopkConfig(warmup_steps=0)
        tr = Trainer(cfg, mesh=mesh4, warmup=False, algo_cfg=acfg)
        assert tr.bus is None and tr.run_journal is None
        assert tr.tracer is None and tr.regress is None
