"""Signal-fidelity telemetry plane (obs/quality.py + friends).

Four layers, matching the plane's own:

1. Device ring + tap primitives — push/drain semantics, monotonic
   cursor, skip-frozen baselines, signature churn.
2. The two acceptance properties of the in-jit taps: the traced step
   contains NO host callback (device→host movement happens only at the
   trainer's flush boundary), and the training trajectory is
   BIT-IDENTICAL taps-on vs taps-off.
3. Oracle conformance (slow) — on the emulated 8-worker mesh the
   journalled compression error / effective density match an offline
   dense-vs-sparse numpy oracle, for oktopk, topkA, gaussiank and the
   fused-select Pallas path, through the exact tap code the trainer
   threads (``build_quality_allreduce_step``).
4. The reporting/closed-loop surfaces — rollups + breach detection,
   seam routing (tracer / feedback / density backoff), Prometheus
   export, ``obs_report --strict/--json`` exit codes, and the bench
   baseline hardening in obs/regress.py.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.config import OkTopkConfig, TrainConfig
from oktopk_tpu.data.synthetic import synthetic_batch
from oktopk_tpu.obs.events import validate_event, validate_journal
from oktopk_tpu.obs.journal import EventBus
from oktopk_tpu.obs.metrics_buffer import (COLUMNS, NUM_COLS, init_buffer,
                                           push_row, rows_since)
from oktopk_tpu.obs.quality import (QualityConfig, quality_event,
                                    winner_signature)
from oktopk_tpu.obs.rollup import RollupEngine, rollup_quality_event
from oktopk_tpu.train.trainer import Trainer

pytestmark = [pytest.mark.obs, pytest.mark.quality]

_COL = {c: i for i, c in enumerate(COLUMNS)}


def _row(step, **kw):
    r = np.zeros(NUM_COLS, np.float32)
    r[_COL["step"]] = step
    for k, v in kw.items():
        r[_COL[k]] = v
    return jnp.asarray(r)


# ---------------------------------------------------------------------------
# 1. ring + tap primitives
# ---------------------------------------------------------------------------

class TestQualityBuffer:
    def test_push_and_drain_in_order(self):
        buf = init_buffer(4, 8)
        sig = jnp.zeros((8,), jnp.float32)
        for s in range(3):
            buf = push_row(buf, _row(s + 1, comp_err=0.1 * (s + 1)), sig,
                           jnp.asarray(1.0), jnp.asarray(False))
        assert int(buf.cursor) == 3
        rows = rows_since(np.asarray(buf.ring), 3, 0)
        assert rows.shape == (3, NUM_COLS)
        np.testing.assert_allclose(rows[:, _COL["step"]], [1, 2, 3])
        np.testing.assert_allclose(rows[:, _COL["comp_err"]],
                                   [0.1, 0.2, 0.3], rtol=1e-6)

    def test_cursor_is_monotonic_and_wraps_ring_only(self):
        buf = init_buffer(3, 8)
        sig = jnp.zeros((8,), jnp.float32)
        for s in range(7):
            buf = push_row(buf, _row(s + 1), sig, jnp.asarray(1.0),
                           jnp.asarray(False))
        assert int(buf.cursor) == 7          # never wraps
        rows = rows_since(np.asarray(buf.ring), 7, 4)
        np.testing.assert_allclose(rows[:, _COL["step"]], [5, 6, 7])

    def test_overfull_drain_degrades_to_newest_capacity_rows(self):
        buf = init_buffer(3, 8)
        sig = jnp.zeros((8,), jnp.float32)
        for s in range(6):
            buf = push_row(buf, _row(s + 1), sig, jnp.asarray(1.0),
                           jnp.asarray(False))
        # host fell behind: asked for 6 rows, ring only holds 3
        rows = rows_since(np.asarray(buf.ring), 6, 0)
        np.testing.assert_allclose(rows[:, _COL["step"]], [4, 5, 6])

    def test_empty_drain(self):
        buf = init_buffer(4, 8)
        assert rows_since(np.asarray(buf.ring), 0, 0).shape == (0, NUM_COLS)

    def test_skip_freezes_baselines_but_pushes_row(self):
        buf = init_buffer(4, 8)
        good_sig = jnp.ones((8,), jnp.float32)
        buf = push_row(buf, _row(1), good_sig, jnp.asarray(5.0),
                       jnp.asarray(False))
        # skipped step: row lands, cursor advances, baselines freeze
        bad_sig = jnp.full((8,), 0.5, jnp.float32)
        buf = push_row(buf, _row(2, skipped=1.0), bad_sig,
                       jnp.asarray(99.0), jnp.asarray(True))
        assert int(buf.cursor) == 2
        assert float(buf.prev_res_norm) == 5.0
        np.testing.assert_array_equal(np.asarray(buf.prev_sig),
                                      np.ones(8, np.float32))
        rows = rows_since(np.asarray(buf.ring), 2, 0)
        assert rows[1, _COL["skipped"]] == 1.0

    def test_worker_axis_is_averaged(self):
        ring = np.zeros((2, 4, NUM_COLS))       # [P=2, cap, cols]
        ring[0, 0, _COL["res_norm"]] = 1.0
        ring[1, 0, _COL["res_norm"]] = 3.0
        rows = rows_since(ring, 1, 0)
        assert rows[0, _COL["res_norm"]] == 2.0


class TestQualityConfig:
    def test_defaults_valid(self):
        q = QualityConfig()
        assert q.every == 32 and q.sig_bins == 512

    @pytest.mark.parametrize("kw", [{"every": 0}, {"sig_bins": 0},
                                    {"sig_bins": 1}, {"sig_bins": 48}])
    def test_invalid_rejected(self, kw):
        with pytest.raises(ValueError):
            QualityConfig(**kw)


class TestWinnerSignature:
    def test_identical_selection_zero_churn(self):
        v = np.zeros(1024, np.float32)
        v[[3, 77, 500]] = 1.0
        s1 = np.asarray(winner_signature(jnp.asarray(v), 64))
        s2 = np.asarray(winner_signature(jnp.asarray(v), 64))
        np.testing.assert_array_equal(s1, s2)
        inter = np.minimum(s1, s2).sum()
        union = max(np.maximum(s1, s2).sum(), 1.0)
        assert 1.0 - inter / union == 0.0

    def test_disjoint_selection_high_churn(self):
        a = np.zeros(1 << 14, np.float32)
        b = np.zeros(1 << 14, np.float32)
        a[:200] = 1.0
        b[-200:] = 1.0
        sa = np.asarray(winner_signature(jnp.asarray(a), 512))
        sb = np.asarray(winner_signature(jnp.asarray(b), 512))
        inter = np.minimum(sa, sb).sum()
        union = max(np.maximum(sa, sb).sum(), 1.0)
        assert 1.0 - inter / union > 0.5

    def test_empty_selection_empty_signature(self):
        s = np.asarray(winner_signature(jnp.zeros(256), 32))
        assert s.sum() == 0


class TestQualityEvent:
    def test_nonfinite_becomes_null(self):
        rows = np.zeros((2, NUM_COLS))
        rows[:, _COL["step"]] = [1, 2]
        rows[0, _COL["comp_err"]] = np.nan
        rows[1, _COL["comp_err"]] = np.inf
        ev = quality_event(2, 0, "oktopk", rows)
        assert ev["comp_err"] == [None, None]
        assert ev["steps"] == [1, 2]
        assert json.loads(json.dumps(ev)) == ev       # JSON-safe
        assert validate_event({"event": "quality", **ev}) == []


# ---------------------------------------------------------------------------
# 2. in-jit acceptance properties
# ---------------------------------------------------------------------------

def _mk_trainer(mesh, quality: bool, every: int = 4, journal=None,
                **cfg_kw):
    cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                      lr=0.05, compressor="oktopk", density=0.05,
                      obs=quality or journal is not None,
                      obs_journal=journal,
                      obs_quality=quality, obs_quality_every=every,
                      **cfg_kw)
    acfg = OkTopkConfig(warmup_steps=0, local_recompute_every=2,
                        global_recompute_every=4)
    return Trainer(cfg, mesh=mesh, warmup=False, algo_cfg=acfg)


def _batches(steps, seed=3):
    rng = np.random.RandomState(seed)
    return iter([synthetic_batch("mnistnet", 8, rng) for _ in range(steps)])


class TestInJitAcceptance:
    def test_traced_step_has_no_host_callback(self, mesh4):
        """The tap must stay on-device: the lowered step program with
        taps enabled carries no callback/infeed — device→host movement
        can only happen at the trainer's flush boundary."""
        tr = _mk_trainer(mesh4, quality=True)
        batch = synthetic_batch("mnistnet", 8, np.random.RandomState(0))
        lowered = tr.step_fn.lower(tr.state, batch,
                                   jax.random.PRNGKey(0)).as_text()
        for needle in ("callback", "infeed", "outfeed"):
            assert needle not in lowered
        # and the step's output state actually carries the ring
        assert tr.state.quality is not None

    def test_trajectory_bit_identical_and_flush_cadence(self, mesh4):
        """The tap is read-only on the training computation (bit-equal
        final params taps-on vs taps-off over the same data), and the
        host drains the ring only on the flush cadence — 6 steps at
        every=4 is one in-loop flush plus the final partial drain, never
        one per step."""
        finals = {}
        for quality in (False, True):
            tr = _mk_trainer(mesh4, quality=quality, every=4)
            tr.train(_batches(6), 6, log_every=100)
            finals[quality] = jax.tree.map(np.asarray, tr.state.params)
        assert tr.quality_flushes == 2      # step 4 + final partial
        assert tr._q_cursors[0] == 6        # everything drained once
        buf = (tr.state.quality if tr.cfg.num_buckets <= 1
               else tr.state.quality[0])
        assert int(np.asarray(buf.cursor).reshape(-1)[0]) == 6
        flat_off = jax.tree.leaves(finals[False])
        flat_on = jax.tree.leaves(finals[True])
        assert len(flat_off) == len(flat_on)
        for a, b in zip(flat_off, flat_on):
            np.testing.assert_array_equal(a.view(np.int32),
                                          b.view(np.int32))

    def test_state_without_rings_fails_loudly(self, mesh4):
        from oktopk_tpu.optim.distributed import init_dist_state
        tr = _mk_trainer(mesh4, quality=True)
        bad = init_dist_state(
            tr.state.params, tr.state.model_state, tr.optimizer,
            tr.algo_cfg, num_buckets=tr.cfg.num_buckets)
        batch = synthetic_batch("mnistnet", 8, np.random.RandomState(0))
        with pytest.raises(ValueError, match="state.quality"):
            tr.step_fn(bad, batch, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# 3. oracle conformance (slow: full sparse steps on the 8-worker mesh)
# ---------------------------------------------------------------------------

def _oracle_run(name, cfg, mesh, steps=6, check_vma=True):
    """Run build_quality_allreduce_step and return per-step
    (tap_row, oracle_comp_err, oracle_eff_density, oracle_res_norm)."""
    from oktopk_tpu.collectives.api import (batched_init_state,
                                            build_quality_allreduce_step)
    q = QualityConfig(every=steps, sig_bins=256)
    step = build_quality_allreduce_step(name, cfg, mesh, q, warmup=False,
                                        check_vma=check_vma)
    state = batched_init_state(cfg)
    P = cfg.num_workers
    qb = jax.tree.map(lambda x: jnp.broadcast_to(x, (P,) + x.shape),
                      init_buffer(q.every, q.sig_bins))
    rng = np.random.RandomState(7)
    base = rng.randn(P, cfg.n).astype(np.float32)
    out_rows = []
    for i in range(steps):
        grads = base + 0.3 * rng.randn(P, cfg.n).astype(np.float32)
        res_before = np.asarray(state.residual, np.float64)
        dense = (grads.astype(np.float64) + res_before).mean(0)
        out, state, qb = step(jnp.asarray(grads), state, qb)
        r = np.asarray(out[0], np.float64)
        o_ce = ((r - dense) ** 2).sum() / ((dense ** 2).sum() + 1e-30)
        o_ed = float((r != 0).sum()) / cfg.n
        o_rn = float(np.mean(np.sqrt(
            (np.asarray(state.residual, np.float64) ** 2).sum(-1))))
        hb = jax.device_get(qb)
        cur = int(np.asarray(hb.cursor).reshape(-1)[0])
        assert cur == i + 1
        row = rows_since(np.asarray(hb.ring), cur, cur - 1)[-1]
        out_rows.append((row, o_ce, o_ed, o_rn))
    return out_rows


def _assert_oracle(rows, name):
    for i, (row, o_ce, o_ed, o_rn) in enumerate(rows):
        t_ce = row[_COL["comp_err"]]
        t_ed = row[_COL["eff_density"]]
        t_rn = row[_COL["res_norm"]]
        assert t_ce == pytest.approx(o_ce, rel=5e-3, abs=1e-6), (
            f"{name} step {i}: tap comp_err {t_ce} vs oracle {o_ce}")
        assert t_ed == pytest.approx(o_ed, abs=1e-9), (
            f"{name} step {i}: tap eff_density {t_ed} vs oracle {o_ed}")
        # res_norm tap is per-worker f32; oracle is the worker mean
        assert t_rn == pytest.approx(o_rn, rel=1e-3), (
            f"{name} step {i}: tap res_norm {t_rn} vs oracle {o_rn}")


@pytest.mark.slow
class TestDenseVsSparseOracle:
    N = 1 << 14

    def _cfg(self, **kw):
        return OkTopkConfig(n=self.N, num_workers=8, density=0.01,
                            warmup_steps=0, local_recompute_every=1,
                            global_recompute_every=4, **kw)

    @pytest.mark.parametrize("name", ["oktopk", "topkA", "gaussiank"])
    def test_tap_matches_offline_oracle(self, name, mesh8):
        _assert_oracle(_oracle_run(name, self._cfg(), mesh8), name)

    def test_fused_select_path_matches_oracle(self, mesh8, monkeypatch):
        """The Pallas fused-select branch journals the same fidelity
        the unfused path does (interpret mode on the CPU mesh)."""
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        cfg = self._cfg(use_pallas=True, wire_dtype="float32")
        rows = _oracle_run("oktopk", cfg, mesh8, check_vma=False)
        _assert_oracle(rows, "oktopk[fused]")

    def test_dense_scores_zero_error_full_density(self, mesh8):
        rows = _oracle_run("dense", self._cfg(), mesh8, steps=3)
        for row, _, _, _ in rows:
            assert row[_COL["comp_err"]] == pytest.approx(0.0, abs=1e-9)
            assert row[_COL["eff_density"]] > 0.99
            assert row[_COL["res_norm"]] == 0.0


# ---------------------------------------------------------------------------
# 4. rollups, breaches, seams, export, report, regress
# ---------------------------------------------------------------------------

def _flush_event(step=8, bucket=0, n=4, **over):
    ev = {"step": step, "bucket": bucket, "algo": "oktopk", "count": n,
          "steps": list(range(step - n + 1, step + 1)),
          "comp_err": [0.3] * n, "res_norm": [10.0] * n,
          "res_growth": [1.0] * n, "eff_density": [0.01] * n,
          "thr_drift": [1.0] * n, "churn": [0.1] * n,
          "skipped": [0] * n}
    ev.update(over)
    return ev


class TestRollup:
    def test_aggregates(self):
        ev = _flush_event(comp_err=[0.1, 0.2, 0.3, 0.4])
        r = rollup_quality_event(ev)
        assert r["window"] == 4 and r["skipped"] == 0
        assert r["comp_err_mean"] == pytest.approx(0.25)
        assert r["comp_err_max"] == pytest.approx(0.4)
        assert r["res_norm_last"] == 10.0
        assert r["breaches"] == []
        assert validate_event({"event": "quality_rollup", **r}) == []

    def test_skipped_rows_excluded_from_aggregates(self):
        ev = _flush_event(comp_err=[0.1, 99.0, 0.3, 0.1],
                          skipped=[0, 1, 0, 0])
        r = rollup_quality_event(ev)
        assert r["skipped"] == 1
        assert r["comp_err_max"] == pytest.approx(0.3)

    def test_null_samples_skipped(self):
        ev = _flush_event(comp_err=[0.1, None, 0.3, None])
        r = rollup_quality_event(ev)
        assert r["comp_err_mean"] == pytest.approx(0.2)

    def test_breach_residual_growth(self):
        ev = _flush_event(res_growth=[2.0] * 4)
        assert "residual_growth" in rollup_quality_event(
            ev, growth_limit=1.5)["breaches"]

    def test_breach_density_collapse_needs_target(self):
        ev = _flush_event(eff_density=[0.001] * 4)
        assert rollup_quality_event(ev)["breaches"] == []
        r = rollup_quality_event(ev, target_density=0.01,
                                 collapse_ratio=0.25)
        assert "density_collapse" in r["breaches"]

    def test_density_collapse_exempts_lossless_windows(self):
        """Dense-warmup steps deliver the exact dense gradient, whose
        own nonzero fraction can sit far below the selection target —
        comp_err ~ 0 means nothing was dropped, so no collapse."""
        ev = _flush_event(eff_density=[0.001] * 4, comp_err=[0.0] * 4)
        r = rollup_quality_event(ev, target_density=0.01,
                                 collapse_ratio=0.25)
        assert r["breaches"] == []

    def test_breach_churn_and_comp_err(self):
        ev = _flush_event(churn=[0.95] * 4, comp_err=[2.0] * 4)
        br = rollup_quality_event(ev, churn_limit=0.9,
                                  comp_err_limit=1.0)["breaches"]
        assert "churn_spike" in br and "comp_err" in br

    def test_engine_emits_rollup_and_calls_on_breach(self):
        bus = EventBus()
        hits = []
        eng = RollupEngine(bus, growth_limit=1.5,
                           on_breach=lambda s, b, k: hits.append((s, b, k)))
        bus.emit("quality", **_flush_event(res_growth=[9.0] * 4))
        assert len(eng.rollups) == 1
        assert eng.breached == 1
        assert hits == [(8, 0, ["residual_growth"])]
        assert bus.dropped == 0

    def test_engine_uses_per_bucket_target_density(self):
        bus = EventBus()
        eng = RollupEngine(bus, collapse_ratio=0.25)
        eng.target_densities = [0.05, 0.01]
        bus.emit("quality", **_flush_event(bucket=0,
                                           eff_density=[0.002] * 4))
        bus.emit("quality", **_flush_event(bucket=1,
                                           eff_density=[0.009] * 4))
        assert "density_collapse" in eng.rollups[0]["breaches"]
        assert eng.rollups[1]["breaches"] == []


class TestClosedLoopSeams:
    def test_tracer_arms_on_breached_rollup_only(self, tmp_path):
        from oktopk_tpu.obs.tracing import AnomalyTracer
        bus = EventBus()
        tracer = AnomalyTracer(str(tmp_path), bus=bus)
        bus.emit("quality_rollup", step=8, bucket=0, breaches=[])
        assert tracer._armed is None
        bus.emit("quality_rollup", step=16, bucket=0,
                 breaches=["residual_growth"])
        assert tracer._armed == "quality_rollup@step16"

    def test_feedback_votes_on_breached_rollups_only(self):
        from oktopk_tpu.resilience.feedback import AutotuneFeedback
        bus = EventBus()
        fb = AutotuneFeedback(bus, window_steps=32, min_signals=2,
                              cooldown_steps=0,
                              kinds=("regression", "guard_trip",
                                     "quality_rollup"))
        bus.emit("quality_rollup", step=8, bucket=0, breaches=[])
        assert fb.signals == []
        bus.emit("quality_rollup", step=8, bucket=0, breaches=["comp_err"])
        bus.emit("quality_rollup", step=16, bucket=0,
                 breaches=["churn_spike"])
        trig = fb.should_retune(17)
        assert trig is not None and trig["trigger"] == "quality_rollup"

    def test_density_backoff_quality_breach_advances_level(self):
        from oktopk_tpu.resilience.density import DensityBackoff
        db = DensityBackoff(abs_limit=100.0, backoff_steps=2, factor=0.5)
        db.level = 2            # guard pressure pushed density down 4x
        assert db.note_quality_breach(10, "residual_growth") is None
        change = db.note_quality_breach(11, "comp_err")
        assert change == {"direction": "advance", "level": 1,
                          "scale": 0.5, "trigger": "quality_breach"}

    def test_density_backoff_ignores_non_fidelity_kinds_and_level0(self):
        from oktopk_tpu.resilience.density import DensityBackoff
        db = DensityBackoff(abs_limit=100.0, backoff_steps=1)
        assert db.note_quality_breach(1, "churn_spike") is None
        assert db.note_quality_breach(2, "density_collapse") is None
        # fidelity breach at level 0: nothing to advance to
        assert db.note_quality_breach(3, "comp_err") is None
        assert db.level == 0

    def test_trainer_routes_breach_to_backoff(self, mesh4):
        """A sustained fidelity breach through the real trainer hook
        undoes one guard-driven backoff level and journals it."""
        tr = _mk_trainer(mesh4, quality=True, resilience=True,
                         resilience_density_backoff=True)
        tr.density_backoff.level = 1
        tr._density_scale = 0.5
        tr.density_backoff.backoff_steps = 2
        tr._on_quality_breach(8, 0, ["residual_growth"])
        assert tr._density_scale == 0.5       # one signal: no change yet
        tr._on_quality_breach(16, 0, ["residual_growth"])
        assert tr._density_scale == 1.0
        assert tr.density_backoff.level == 0


class TestExport:
    def test_render_and_atomic_write(self, tmp_path):
        from oktopk_tpu.obs.export import render_prometheus, write_textfile
        entries = [
            {"event": "quality_rollup", "step": 8, "bucket": 0,
             "algo": "oktopk", "comp_err_mean": 0.25,
             "eff_density_mean": 0.0098, "breaches": []},
            {"event": "quality_rollup", "step": 16, "bucket": 0,
             "algo": "oktopk", "comp_err_mean": 0.5,
             "eff_density_mean": 0.0105, "breaches": ["comp_err"]},
        ]
        text = render_prometheus(entries)
        assert "# TYPE oktopk_quality_comp_err_mean gauge" in text
        # latest rollup per bucket wins
        assert 'oktopk_quality_comp_err_mean{bucket="0",algo="oktopk"} 0.5' \
            in text
        assert 'oktopk_quality_breaches_total{bucket="0",algo="oktopk"} 1' \
            in text
        assert 'oktopk_quality_last_step{bucket="0",algo="oktopk"} 16' \
            in text
        path = str(tmp_path / "sub" / "q.prom")
        write_textfile(entries, path)
        assert open(path).read() == text
        assert not os.path.exists(path + ".tmp")

    def test_empty_entries_render_empty(self):
        from oktopk_tpu.obs.export import render_prometheus
        assert render_prometheus([{"event": "step", "step": 1}]) == ""


def _load_obs_report():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "obs_report.py")
    spec = importlib.util.spec_from_file_location("obs_report_q", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_journal(path, extra_entries):
    from oktopk_tpu.autotune.journal import environment_header
    entries = [{"event": "header", **environment_header()}] + extra_entries
    with open(path, "w") as f:
        for e in entries:
            f.write(json.dumps(e) + "\n")
    return entries


class TestObsReportExitCodes:
    def test_clean_journal_strict_rc0(self, tmp_path, capsys):
        mod = _load_obs_report()
        p = str(tmp_path / "j.jsonl")
        _write_journal(p, [{"event": "quality_rollup", "step": 8,
                            "bucket": 0, "breaches": []}])
        assert mod.main([p, "--strict"]) == 0
        assert "signal fidelity" in capsys.readouterr().out

    def test_breached_rollup_strict_rc1(self, tmp_path, capsys):
        mod = _load_obs_report()
        p = str(tmp_path / "j.jsonl")
        _write_journal(p, [{"event": "quality_rollup", "step": 8,
                            "bucket": 0, "breaches": ["comp_err"]}])
        assert mod.main([p]) == 0            # non-strict stays advisory
        assert mod.main([p, "--strict"]) == 1
        out = capsys.readouterr().out
        assert "BREACH" in out               # on the incident timeline

    def test_schema_violation_strict_rc1(self, tmp_path, capsys):
        mod = _load_obs_report()
        p = str(tmp_path / "j.jsonl")
        _write_journal(p, [{"event": "quality_rollup", "step": 8}])
        assert mod.main([p, "--strict"]) == 1
        capsys.readouterr()

    def test_unreadable_journal_rc2(self, tmp_path, capsys):
        mod = _load_obs_report()
        assert mod.main([str(tmp_path / "missing.jsonl"),
                         "--strict"]) == 2
        bad = str(tmp_path / "bad.jsonl")
        with open(bad, "w") as f:
            f.write("{not json\n")
        assert mod.main([bad]) == 2
        capsys.readouterr()

    def test_json_output(self, tmp_path, capsys):
        mod = _load_obs_report()
        p = str(tmp_path / "j.jsonl")
        _write_journal(p, [{"event": "quality_rollup", "step": 8,
                            "bucket": 1, "breaches": ["churn_spike"]}])
        assert mod.main([p, "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["quality"]["breached_rollups"] == 1
        assert out["quality"]["breaches"][0] == {
            "step": 8, "bucket": 1, "kinds": ["churn_spike"]}
        assert out["events"]["quality_rollup"] == 1
        assert out["schema_problems"] == []

    def test_prom_flag_writes_textfile(self, tmp_path, capsys):
        mod = _load_obs_report()
        p = str(tmp_path / "j.jsonl")
        _write_journal(p, [{"event": "quality_rollup", "step": 8,
                            "bucket": 0, "algo": "oktopk",
                            "comp_err_mean": 0.1, "breaches": []}])
        prom = str(tmp_path / "q.prom")
        assert mod.main([p, "--prom", prom]) == 0
        assert "oktopk_quality_comp_err_mean" in open(prom).read()
        capsys.readouterr()


class TestRegressHardening:
    def test_scan_tolerates_empty_and_malformed(self, tmp_path):
        from oktopk_tpu.obs.regress import scan_bench_records
        (tmp_path / "BENCH_r1.json").write_text("")           # empty
        (tmp_path / "BENCH_r2.json").write_text("{not json")  # garbled
        (tmp_path / "BENCH_r3.json").write_text("[1, 2]")     # not a dict
        (tmp_path / "BENCH_r4.json").write_text(
            json.dumps({"parsed": {"oktopk_ms": 100.0}}))
        vals, n_files, malformed = scan_bench_records(
            "oktopk_ms", root=str(tmp_path))
        assert vals == [100.0]
        assert n_files == 4
        assert sorted(malformed) == ["BENCH_r1.json", "BENCH_r2.json",
                                     "BENCH_r3.json"]

    def test_top_level_quality_keys_found(self, tmp_path):
        from oktopk_tpu.obs.regress import scan_bench_records
        (tmp_path / "BENCH_r1.json").write_text(
            json.dumps({"quality_comp_err": 0.4}))
        vals, _, _ = scan_bench_records("quality_comp_err",
                                       root=str(tmp_path))
        assert vals == [0.4]

    def test_missing_baseline_journals_warning(self, tmp_path):
        from oktopk_tpu.obs.regress import RegressionDetector
        (tmp_path / "BENCH_r1.json").write_text("{broken")
        bus = EventBus()
        seen = []
        bus.subscribe(seen.append)
        det = RegressionDetector.from_bench_records(
            key="oktopk_ms", root=str(tmp_path), bus=bus)
        assert det.baseline_ms is None
        warns = [e for e in seen if e["event"] == "baseline_warning"]
        assert len(warns) == 1
        assert warns[0]["key"] == "oktopk_ms"
        assert warns[0]["malformed"] == ["BENCH_r1.json"]
        assert validate_event(warns[0]) == []
        # and the detector stays advisory: observe never flags
        assert det.observe(10, 1e9) is None

    def test_baseline_present_no_warning(self, tmp_path):
        from oktopk_tpu.obs.regress import RegressionDetector
        (tmp_path / "BENCH_r1.json").write_text(
            json.dumps({"parsed": {"oktopk_ms": 50.0}}))
        bus = EventBus()
        seen = []
        bus.subscribe(seen.append)
        det = RegressionDetector.from_bench_records(
            key="oktopk_ms", root=str(tmp_path), bus=bus)
        assert det.baseline_ms == 50.0
        assert not [e for e in seen if e["event"] == "baseline_warning"]

    def test_observe_quality_flags_over_limit(self):
        from oktopk_tpu.obs.regress import RegressionDetector
        bus = EventBus()
        seen = []
        bus.subscribe(seen.append)
        det = RegressionDetector(baseline_ms=None, bus=bus,
                                 quality_limits={"comp_err_mean": 0.5,
                                                 "churn_mean": 0.9})
        flagged = det.observe_quality(
            8, {"comp_err_mean": 0.75, "churn_mean": 0.2,
                "eff_density_mean": 0.01})
        assert len(flagged) == 1
        rec = flagged[0]
        assert rec["key"] == "quality:comp_err_mean"
        assert rec["ratio"] == pytest.approx(1.5)
        evs = [e for e in seen if e["event"] == "regression"]
        assert len(evs) == 1 and validate_event(evs[0]) == []
        # within-limit, missing and NaN fields never flag
        assert det.observe_quality(9, {"comp_err_mean": 0.4}) == []
        assert det.observe_quality(10, {"churn_mean": float("nan")}) == []
