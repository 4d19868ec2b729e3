"""Autotuner tests on the CPU mesh: calibration fit, policy crossover,
hysteresis, journal schema, and the trainer integration.

The decision logic is exercised against injected fake timings
(``TrialRunner(fake_ms=...)``) — the tier-1 suite must verify tuner
behaviour without a TPU — plus one small real-timing end-to-end pass over
the virtual 8-worker mesh.
"""

import json
import os

import numpy as np
import pytest

from oktopk_tpu.autotune import (Autotuner, AutotunePolicy, DecisionJournal,
                                 TrialRunner, fit_alpha_beta, probe_fabric,
                                 read_journal)
from oktopk_tpu.autotune.calibrate import FabricCoefficients
from oktopk_tpu.autotune.policy import Candidate, make_candidates, predict_ms
from oktopk_tpu.config import OkTopkConfig
from oktopk_tpu.utils.cost_model import allreduce_cost

SMALL, LARGE = 10_000, 4_000_000


def crossover_fake_ms(algo, n, density):
    """Synthetic fabric: dense wins small buckets, oktopk wins large ones
    (the regime dependence of arXiv 2103.00543). Crossover sits at
    n ~ 1.56M elements for density 0.02."""
    if algo == "dense":
        return 0.5 + n * 1e-6            # cheap latency, linear in n
    return 2.0 + density * n * 2e-6      # selection floor, scales with k


class TestCalibration:
    def test_fit_recovers_alpha_beta(self):
        alpha, beta, p = 5e-6, 2e-9, 8
        sizes = [1 << 14, 1 << 16, 1 << 18, 1 << 20]
        times = [allreduce_cost(n, p, alpha, beta) for n in sizes]
        c = fit_alpha_beta(sizes, times, p)
        assert c.alpha == pytest.approx(alpha, rel=1e-6)
        assert c.beta == pytest.approx(beta, rel=1e-6)
        assert c.residual < 1e-9
        assert c.nsamples == len(sizes)

    def test_fit_single_worker_degenerate_law(self):
        # P == 1: design matrix (1, n) — alpha absorbs the dispatch floor
        alpha, beta = 3e-3, 1e-9
        sizes = [1 << 12, 1 << 16, 1 << 20]
        times = [alpha + beta * n for n in sizes]
        c = fit_alpha_beta(sizes, times, 1)
        assert c.alpha == pytest.approx(alpha, rel=1e-6)
        assert c.beta == pytest.approx(beta, rel=1e-6)

    def test_fit_clamps_noise_driven_negative(self):
        # noise can drive lstsq negative; costs must stay positive
        c = fit_alpha_beta([1000, 2000, 4000], [5e-3, 3e-3, 1e-3], 8)
        assert c.alpha > 0 and c.beta > 0

    def test_probe_with_injected_measure(self):
        alpha, beta, p = 1e-5, 5e-9, 8

        def measure(n):
            return [allreduce_cost(n, p, alpha, beta)] * 3

        c = probe_fabric(measure=measure, num_workers=p,
                         sizes=(1 << 14, 1 << 18, 1 << 20))
        assert c.source == "injected"
        assert c.alpha == pytest.approx(alpha, rel=1e-5)
        assert c.beta == pytest.approx(beta, rel=1e-5)

    def test_probe_real_mesh(self, mesh8):
        c = probe_fabric(mesh8, sizes=(1 << 10, 1 << 14), repeats=2)
        assert c.source == "measured"
        assert c.alpha > 0 and c.beta > 0


def _tuner(bucket_sizes, fake_ms, policy=None, journal=None):
    policy = policy or AutotunePolicy(
        candidates=make_candidates(("dense", "oktopk"), (0.02,)),
        hysteresis=0.15, retune_every=100)
    runner = TrialRunner(fake_ms=fake_ms,
                         base_cfg=OkTopkConfig(num_workers=8))
    return Autotuner(bucket_sizes, 8, policy, runner,
                     coeffs=FabricCoefficients(1e-6, 1e-11,
                                               source="injected"),
                     journal=journal)


class TestPolicy:
    def test_predict_ms_orders_regimes(self):
        c = FabricCoefficients(1e-6, 1e-9)
        # at low density and large n, oktopk's O(k) wire beats dense's O(n)
        assert predict_ms("oktopk", 0.01, LARGE, 8, c) \
            < predict_ms("dense", 1.0, LARGE, 8, c)
        assert predict_ms("topkA", 0.01, LARGE, 8, c) > 0

    def test_plan_crossover_per_bucket(self, tmp_path):
        journal = DecisionJournal(str(tmp_path / "journal.jsonl"))
        tuner = _tuner([SMALL, LARGE], crossover_fake_ms, journal=journal)
        plans = tuner.tune(step=0)
        assert [p.algo for p in plans] == ["dense", "oktopk"]
        assert plans[0].n == SMALL and plans[1].n == LARGE
        assert plans[1].density == 0.02
        # measured posterior is what decided, and it is recorded
        assert plans[0].measured_ms < crossover_fake_ms("oktopk", SMALL, .02)

    def test_hysteresis_holds_on_small_delta(self):
        timings = {"scale": 1.0}

        def fake(algo, n, density):
            base = crossover_fake_ms(algo, n, density)
            # after the flip, dense gets 5% cheaper than oktopk on the
            # large bucket — inside the 15% hysteresis margin
            if timings["scale"] != 1.0 and algo == "dense" and n == LARGE:
                return crossover_fake_ms("oktopk", n, density) * 0.95
            return base

        tuner = _tuner([LARGE], fake)
        first = tuner.tune(step=0)
        assert first[0].algo == "oktopk"
        timings["scale"] = 0.95
        second = tuner.tune(step=100)
        assert second[0].algo == "oktopk", "plan flipped inside hysteresis"
        assert not Autotuner.plans_changed(second, first)
        assert tuner.journal.entries[-1]["reason"] == "hold"

    def test_retune_switches_on_large_delta(self):
        flipped = {"on": False}

        def fake(algo, n, density):
            if flipped["on"] and algo == "dense":
                return 0.01          # dense became overwhelmingly cheaper
            return crossover_fake_ms(algo, n, density)

        tuner = _tuner([LARGE], fake)
        assert tuner.tune(step=0)[0].algo == "oktopk"
        flipped["on"] = True
        plans = tuner.tune(step=100)
        assert plans[0].algo == "dense"
        assert tuner.journal.entries[-1]["reason"] == "trial"

    def test_should_retune_cadence(self):
        tuner = _tuner([SMALL], crossover_fake_ms)
        assert tuner.should_retune(0)          # never tuned
        tuner.tune(step=0)
        assert not tuner.should_retune(50)     # inside the period
        assert tuner.should_retune(100)
        # retune_every=0 tunes exactly once
        once = _tuner([SMALL], crossover_fake_ms,
                      policy=AutotunePolicy(
                          candidates=(Candidate("dense"),),
                          retune_every=0))
        once.tune(step=0)
        assert not once.should_retune(10_000)

    def test_prior_pruning_still_measures_incumbent(self):
        calls = []

        def fake(algo, n, density):
            calls.append(algo)
            return crossover_fake_ms(algo, n, density)

        from oktopk_tpu.autotune.policy import BucketPlan

        policy = AutotunePolicy(
            candidates=make_candidates(("dense", "oktopk", "topkA"), (0.02,)),
            hysteresis=0.15, retune_every=1, max_trials=1)
        tuner = _tuner([LARGE], fake, policy=policy)
        # seed an incumbent the cost-model prior would prune (the α-β
        # prior ranks dense first at these coefficients)
        tuner.plans = [BucketPlan(bucket=0, n=LARGE, algo="oktopk",
                                  density=0.02, predicted_ms=1.0,
                                  measured_ms=1.0)]
        tuner.last_tune_step = 0
        tuner.tune(step=1)
        # top-1 by prior is measured, plus the incumbent even though the
        # prior would have pruned it; the third candidate stays untrialed
        assert set(calls) == {"dense", "oktopk"}

    def test_candidate_validation(self):
        with pytest.raises(ValueError):
            AutotunePolicy(candidates=())
        with pytest.raises(ValueError):
            AutotunePolicy(candidates=(Candidate("dense"),), hysteresis=1.5)
        with pytest.raises(ValueError):
            predict_ms("nosuch", 0.1, 100, 8, FabricCoefficients(1e-6, 1e-9))


class TestJournal:
    def test_jsonl_schema_roundtrip(self, tmp_path):
        path = str(tmp_path / "decisions.jsonl")
        tuner = _tuner([SMALL, LARGE], crossover_fake_ms,
                       journal=DecisionJournal(path))
        tuner.calibrate(step=0)
        tuner.tune(step=0)
        with open(path) as f:
            for line in f:
                json.loads(line)                 # every line parses alone
        entries = read_journal(path)
        # every journal leads with the environment header so decision
        # logs are comparable across machines
        assert entries[0]["event"] == "header"
        assert {"jax", "jaxlib", "device_kind", "world_size"} \
            <= set(entries[0])
        assert entries[1]["event"] == "calibration"
        assert {"alpha", "beta", "source"} <= set(entries[1])
        decisions = [e for e in entries if e["event"] == "decision"]
        assert len(decisions) == 2
        for d in decisions:
            assert {"step", "bucket", "n", "num_workers", "candidates",
                    "chosen", "incumbent", "reason"} <= set(d)
            for c in d["candidates"]:
                assert {"algo", "density", "predicted_ms",
                        "measured_ms"} <= set(c)
            assert d["chosen"]["algo"] in ("dense", "oktopk")

    def test_memory_only_journal(self):
        j = DecisionJournal()
        j.record("calibration", step=0, alpha=1e-6)
        assert j.entries[0]["event"] == "header"
        assert j.entries[-1]["alpha"] == 1e-6


class TestTrainerIntegration:
    @pytest.fixture(scope="class")
    def trainer(self, mesh8):
        from oktopk_tpu.config import TrainConfig
        from oktopk_tpu.train.trainer import Trainer

        cfg = TrainConfig(
            dnn="mnistnet", dataset="mnist", batch_size=8, lr=0.1,
            compressor="oktopk", density=0.02, num_workers=8,
            num_buckets=2, autotune=True,
            autotune_candidates=("dense", "oktopk"),
            autotune_trial_steps=1, autotune_retune_every=50)
        return Trainer(cfg, mesh=mesh8, warmup=False)

    def test_fake_timed_plan_reaches_step_fn(self, trainer):
        plans = trainer.autotune(step=0, fake_ms=crossover_fake_ms)
        assert len(plans) == 2

        def expected(n):
            return min(
                [("dense", crossover_fake_ms("dense", n, 1.0)),
                 ("oktopk", crossover_fake_ms("oktopk", n, 0.02))],
                key=lambda t: t[1])[0]

        # the plan must match the synthetic fabric's crossover bucket by
        # bucket (mnistnet's big FC bucket sits above the ~1.56M
        # crossover -> oktopk; the small tail bucket -> dense)
        assert [p.algo for p in plans] == [expected(p.n) for p in plans]
        assert len({p.algo for p in plans}) == 2, (
            "expected a mixed per-bucket plan, got " +
            repr([(p.n, p.algo) for p in plans]))
        fn = trainer.step_fn
        # re-tune with identical timings: no plan change, no step rebuild
        trainer.autotune(step=50, fake_ms=crossover_fake_ms)
        assert trainer.step_fn is fn, "re-tune thrashed the jitted step"

    def test_autotuned_step_trains(self, trainer, rng):
        from oktopk_tpu.data.synthetic import synthetic_batch

        batch = synthetic_batch("mnistnet", 8, rng)
        m = trainer.train_step(batch)
        assert np.isfinite(float(np.asarray(m["loss"])))

    def test_real_trial_timings_end_to_end(self, mesh8):
        """Real (not injected) trial pass over the CPU mesh: calibration,
        trials, plan, and a training step through the planned collectives."""
        from oktopk_tpu.config import TrainConfig
        from oktopk_tpu.data.synthetic import synthetic_batch
        from oktopk_tpu.train.trainer import Trainer

        cfg = TrainConfig(
            dnn="mnistnet", dataset="mnist", batch_size=8, lr=0.1,
            compressor="oktopk", density=0.02, num_workers=8,
            num_buckets=1, autotune=True,
            autotune_candidates=("dense", "oktopk"),
            autotune_trial_steps=1)
        t = Trainer(cfg, mesh=mesh8, warmup=False)
        plans = t.autotune(step=0)
        assert len(plans) == 1
        assert plans[0].algo in ("dense", "oktopk")
        assert plans[0].measured_ms > 0
        assert t.autotuner.coeffs.source == "measured"
        batch = synthetic_batch("mnistnet", 8, np.random.RandomState(0))
        m = t.train_step(batch)
        assert np.isfinite(float(np.asarray(m["loss"])))


class TestBucketDensityPlumbing:
    def test_step_accepts_per_bucket_plan(self, mesh8):
        """build_sparse_grad_step takes a mixed per-bucket plan and the
        volumes reflect it (dense bucket moves 2n, sparse bucket O(k))."""
        import jax.numpy as jnp

        from oktopk_tpu.collectives.api import batched_init_state, \
            build_allreduce_step
        from oktopk_tpu.config import OkTopkConfig

        # direct per-bucket check at the collective level: one dense, one
        # oktopk program over different sizes — the same pair the planner
        # hands build_sparse_grad_step
        for algo, n in (("dense", 4096), ("oktopk", 8192)):
            cfg = OkTopkConfig(n=n, num_workers=8, density=0.05,
                               warmup_steps=0)
            step = build_allreduce_step(algo, cfg, mesh8, warmup=False)
            state = batched_init_state(cfg)
            g = jnp.asarray(np.random.RandomState(0)
                            .randn(8, n).astype(np.float32))
            out, st = step(g, state)
            assert out.shape == (8, n)
            vol = float(np.asarray(st.last_volume)[0])
            if algo == "dense":
                assert vol == 2.0 * n
            else:
                assert vol < 2.0 * n

    def test_plan_length_validation(self, mesh8):
        from oktopk_tpu.optim.distributed import build_sparse_grad_step
        from oktopk_tpu.config import OkTopkConfig
        from oktopk_tpu.optim import sgd

        with pytest.raises(ValueError, match="compressor plan"):
            build_sparse_grad_step(
                lambda *a: None, sgd(0.1), OkTopkConfig(n=8, num_workers=8),
                mesh8, compressor=["dense"], num_buckets=2)
        with pytest.raises(ValueError, match="bucket_densities"):
            build_sparse_grad_step(
                lambda *a: None, sgd(0.1), OkTopkConfig(n=8, num_workers=8),
                mesh8, compressor="dense", num_buckets=2,
                bucket_densities=[0.1])
