"""What the program says about each step (utils/profiling.py,
utils/compile_cache.py, collectives/state.COUNTERS): host spans, host
counters, the per-step ``counters`` vector, and the sub-scopes under
``select`` and ``stage``."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.collectives.state import BRANCH_COUNTERS, COUNTERS
from oktopk_tpu.config import OkTopkConfig, TrainConfig
from oktopk_tpu.data.synthetic import synthetic_iterator
from oktopk_tpu.obs import anatomy
from oktopk_tpu.ops.compaction import BLK
from oktopk_tpu.train.trainer import Trainer
from oktopk_tpu.utils import profiling
from oktopk_tpu.utils.compile_cache import compile_counters
from oktopk_tpu.utils.profiling import PhaseTimers, span

COL = {name: i for i, name in enumerate(COUNTERS)}


def batches(seed=0):
    return synthetic_iterator("mnistnet", 8, seed)


@pytest.fixture(scope="module")
def trainer(mesh4):
    cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                      lr=0.02, compressor="oktopk", density=0.05)
    return Trainer(cfg, mesh=mesh4, warmup=False)


@pytest.fixture
def recorder():
    rec = PhaseTimers(every=0)
    prev = profiling.attach(rec)
    yield rec
    profiling.attach(prev)


class TestSpans:
    def test_step_span_nests_rng_and_dispatch(self, trainer, recorder):
        it = batches()
        before = trainer.step_num
        trainer.train_step(next(it))
        trainer.train_step(next(it))
        recs = list(recorder.records)
        assert [r[0] for r in recs] == [
            "oktopk/rng", "oktopk/dispatch", "oktopk/step"] * 2
        for i, base in enumerate((0, 3)):
            rng, disp, step = recs[base:base + 3]
            assert {rng[3], disp[3], step[3]} == {before + 1 + i}
            assert rng[4] == disp[4] == "oktopk/step" and step[4] is None
            # children inside the parent, in order, on one clock
            assert step[1] <= rng[1] <= rng[2] <= disp[1] <= disp[2] <= step[2]

    def test_no_record_without_a_recorder(self, trainer):
        assert profiling.attach(None) is None
        rec = PhaseTimers(every=0)
        trainer.train_step(next(batches()))
        with span("oktopk/data", step=7):
            pass
        assert not rec.records and profiling.current_step() == 7

    def test_explicit_recorder_and_inherited_step(self):
        rec = PhaseTimers(every=0)
        with span("outer", step=41, recorder=rec):
            with rec.phase("inner"):
                pass
        (inner, outer) = rec.records
        assert inner[0] == "inner" and inner[3] == 41 and inner[4] == "outer"
        assert outer[3] == 41 and outer[4] is None
        assert "inner" in rec.table() and rec.summary()["outer"]["count"] == 1

    def test_setup_spans_are_always_kept(self, trainer):
        names = {r[0] for r in profiling.SETUP.records}
        assert {"oktopk/setup/model_init", "oktopk/setup/state",
                "oktopk/setup/build_step"} <= names

    def test_train_with_timers_does_not_sync_a_step(self, trainer,
                                                    monkeypatch):
        """``Trainer.train(timers=...)`` adds no wait of its own: with no
        logger and no writer nothing blocks, so steps stay in flight."""
        waits = []
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: waits.append(x) or x)
        timers = PhaseTimers(every=0)
        m = trainer.train(batches(), 3,
                          log_every=50, timers=timers)
        assert not waits
        s = timers.summary()
        assert s["oktopk/data"]["count"] == s["oktopk/step"]["count"] == 3
        assert "step" not in s and "data" not in s     # the old phases
        steps = [r[3] for r in timers.records if r[0] == "oktopk/step"]
        assert steps == [1, 2, 3]                      # train()'s own steps
        assert profiling.attach(None) is None          # detached again
        assert np.isfinite(float(m["loss"]))

    def test_phase_limits_keep_their_old_keys(self, mesh4):
        """``obs_phase_limits={"step": ...}``
        goes on firing: ``step`` reads the log window's wall time a step,
        ``data`` the ``oktopk/data`` span; span names work as keys too."""
        from oktopk_tpu.obs.regress import RegressionDetector
        cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                          lr=0.02, compressor="dense", obs=True)
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        tr.regress = RegressionDetector(None, bus=tr.bus, phase_limits={
            "step": 1e-6, "data": 1e-6, "oktopk/dispatch": 1e-6,
            "oktopk/step": 1e9})
        seen = []
        tr.bus.subscribe(lambda e: seen.append(e))
        tr.train(batches(), 2, log_every=2, timers=PhaseTimers(every=0))
        keys = [e["key"] for e in seen if e["event"] == "regression"]
        assert sorted(keys) == ["phase:data", "phase:oktopk/dispatch",
                                "phase:step"]
        step = next(e for e in seen if e.get("key") == "phase:step")
        # wall time of a blocked step, not the microseconds of a dispatch
        disp = next(e for e in seen
                    if e.get("key") == "phase:oktopk/dispatch")
        assert step["ms"] >= disp["ms"] > 0


class TestStepCounters:
    def test_sparse_step_reports_what_it_did(self, mesh4):
        acfg = OkTopkConfig(warmup_steps=2, local_recompute_every=4,
                            global_recompute_every=4, repartition_every=8)
        cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                          lr=0.02, compressor="oktopk", density=0.05)
        tr = Trainer(cfg, mesh=mesh4, algo_cfg=acfg)
        it = batches()
        for _ in range(6):
            m = tr.train_step(next(it))
        # one vector a step, replicated like every other metric: a
        # process of a multi-host run can fetch it
        assert m["counters"].shape == (len(COUNTERS),)
        assert m["counters"].dtype == jnp.int32
        assert m["counters"].sharding.is_fully_replicated
        pairs = tr.step_counters()
        assert [s for s, _ in pairs] == [1, 2, 3, 4, 5, 6]
        c = np.asarray([v for _, v in profiling.fetch_counters(pairs)])
        flags = ("recompute_local", "recompute_global", "repartition")
        cols = [COL[f] for f in flags]
        # steps 1-2 are dense warm-up steps: no branch of the sparse path
        assert not c[:2, :len(BRANCH_COUNTERS)].any()
        # step 3 is the first sparse step (allreduce counter 2 ==
        # warmup_steps): everything is recomputed; step 5 (counter 4) hits
        # the recompute cadence but not the repartition's
        np.testing.assert_array_equal(c[2, cols], [1, 1, 1])
        np.testing.assert_array_equal(c[3, cols], [0, 0, 0])
        np.testing.assert_array_equal(c[4, cols], [1, 1, 0])
        # the realised counts are the metrics' own
        assert c[-1, COL["local_k"]] == int(m["local_k"])
        assert c[-1, COL["global_k"]] == int(m["global_k"])
        # the portable selection path has one branch
        assert not c[:, [COL["stage_branch"], COL["select_branch"]]].any()

    def test_dense_step_reports_zeros(self, mesh4):
        cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                          lr=0.02, compressor="dense")
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        m = tr.train_step(next(batches()))
        c = np.asarray(m["counters"])
        assert c.shape == (len(COUNTERS),)
        assert not c[:len(BRANCH_COUNTERS)].any()

    def test_deque_is_bounded_and_unsynced(self, trainer):
        from oktopk_tpu.train import trainer as trainer_mod
        assert trainer._counters.maxlen == trainer_mod.KEPT_COUNTERS == 512
        step, vec = trainer.step_counters()[-1]
        assert step == trainer.step_num and isinstance(vec, jax.Array)

    # the planted-overflow inputs of tests/test_compaction.py, one a branch
    @staticmethod
    def planted(branch):
        if branch == "fast":
            x = np.random.RandomState(0).randn(4 * BLK).astype(np.float32)
            return x, 2.0, 4 * BLK            # ~2.3 % pass, no block over
        if branch == "repair":
            rng = np.random.RandomState(11)
            x = rng.randn(64 * BLK).astype(np.float32) * 0.1
            for b in (3, 17, 40):
                x[b * BLK:(b + 1) * BLK] = rng.randn(BLK) * 10 + 20
            return x, 1.0, 8 * BLK
        rng = np.random.RandomState(12)
        x = rng.randn(16 * BLK).astype(np.float32) * 0.5 + 20
        return x, 1.0, 16 * BLK               # every block dense

    @pytest.mark.kernels
    @pytest.mark.parametrize("branch,code", [("fast", 0), ("repair", 1),
                                             ("wide", 2)])
    def test_branch_of_planted_overflow(self, branch, code):
        from oktopk_tpu.ops.compaction import (CAPB_FAST,
                                               pack_by_region_pallas,
                                               select_by_threshold_pallas)
        x, thresh, cap = self.planted(branch)
        over = int(((np.abs(x.reshape(-1, BLK)) >= thresh).sum(axis=1)
                    > CAPB_FAST).sum())
        # staging (phase a): every overflowing block counts
        *_, br = pack_by_region_pallas(
            jnp.asarray(x), thresh, jnp.asarray([0, x.size // 2, x.size]),
            2, cap, interpret=True)
        np.testing.assert_array_equal(np.asarray(br), [code, over])
        # the phase-(b) select: the same dispatch on the blocks that matter
        out = select_by_threshold_pallas(jnp.asarray(x), thresh, cap,
                                         interpret=True)
        assert int(out[3][0]) == code and len(out) == 4
        # the portable front gives the branch only where it is asked for
        from oktopk_tpu.ops.select import select_by_threshold
        assert len(select_by_threshold(jnp.asarray(x), thresh, cap)) == 3
        assert not np.asarray(select_by_threshold(
            jnp.asarray(x), thresh, cap, with_branch=True)[3]).any()

    def test_worst_worker_shows_in_the_vector(self, mesh4, monkeypatch):
        """One worker in the wide branch holds the whole step: the vector
        carries the largest of each branch entry over the workers."""
        from jax import lax
        from oktopk_tpu.collectives import registry
        from oktopk_tpu.collectives.dense import dense_allreduce

        def planted(grad, state, cfg, axis_name="data"):
            out, state = dense_allreduce(grad, state, cfg, axis_name)
            me = lax.axis_index(axis_name)
            mine = jnp.zeros_like(state.last_counters)
            mine = mine.at[COL["stage_branch"]].set(
                jnp.where(me == 2, 2, jnp.where(me == 0, 1, 0)))
            mine = mine.at[COL["stage_overflow_blocks"]].set(
                jnp.where(me == 2, 9000, 0))
            return out, state.replace(last_counters=mine)

        monkeypatch.setitem(registry.ALGORITHMS, "planted", planted)
        cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                          lr=0.02, compressor="planted")
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        tr.train_step(next(batches()))
        ((step, v),) = profiling.fetch_counters(tr.step_counters())
        assert step == 1 and all(isinstance(x, int) for x in v)
        assert v[COL["stage_branch"]] == 2         # worker 2 went wide
        assert v[COL["stage_overflow_blocks"]] == 9000
        assert v[COL["select_branch"]] == 0


class TestCompileListener:
    def test_counts_the_step_compile_and_a_forced_recompile(self, mesh4):
        counters = compile_counters()
        assert counters is compile_counters()          # one a process
        cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                          lr=0.02, compressor="dense", obs=True)
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        seen = []
        tr.bus.subscribe(lambda e: seen.append(e))
        it = batches()
        b1, b2 = next(it), next(it)
        tr.train_step(b1)                               # warms the rng split
        jax.block_until_ready(tr.state.params)
        tr.step_fn = tr._build_step()                   # a fresh step_fn
        n0 = counters.counts["compile"]
        tr.train_step(b1)
        assert counters.counts["compile"] == n0 + 1     # its one compile
        at = counters.by_step[tr.step_num]
        assert at["compile"] > 0 and at["trace"] > 0 and at["lower"] > 0
        tr.train_step(b2)
        assert counters.counts["compile"] == n0 + 1     # and no other
        assert not counters.recompiles and not [
            e for e in seen if e["event"] == "recompile"]
        # another batch shape forces a second compile of the same step_fn
        small = jax.tree.map(lambda x: x[:4], b2)
        tr.train_step(small)
        assert counters.counts["compile"] == n0 + 2
        (rec,) = counters.recompiles
        assert rec["step"] == tr.step_num and rec["seconds"] > 0
        (ev,) = [e for e in seen if e["event"] == "recompile"]
        assert ev["step"] == tr.step_num and ev["seconds"] == rec["seconds"]
        from oktopk_tpu.obs.events import validate_event
        assert validate_event(ev) == []
        counters.recompiles.clear()


    def test_nested_spans_add_up_to_wall_time(self):
        """jax times a jitted function traced inside another's trace as a
        span inside a span: only the outermost adds its seconds."""
        import time
        counters = compile_counters()
        inner = jax.jit(lambda x: jnp.sin(x) * 2)
        outer = jax.jit(lambda x: inner(x) + inner(2 * x) + 1)
        s0, n0 = sum(counters.seconds.values()), counters.counts["trace"]
        t0 = time.perf_counter()
        outer(jnp.ones(3)).block_until_ready()
        wall = time.perf_counter() - t0
        assert counters.counts["trace"] >= n0 + 2       # outer and inner
        assert 0 < sum(counters.seconds.values()) - s0 <= wall


class TestSnapshot:
    def test_snapshot_and_dump(self, trainer, recorder, tmp_path):
        trainer.train_step(next(batches()))
        path = profiling.dump(str(tmp_path / "run" / "snap.json"))
        with open(path) as f:
            snap = json.load(f)
        assert snap["counter_names"] == list(COUNTERS)
        assert trainer.capacities()[0] in snap["capacities"]
        names = [s["name"] for s in snap["spans"]]
        assert "oktopk/setup/build_step" in names and "oktopk/step" in names
        last = snap["step_counters"][-1]
        assert last["step"] == trainer.step_num
        assert len(last["counters"]) == len(COUNTERS)
        assert set(snap["host_counters"]) == {"seconds", "counts",
                                              "by_step", "recompiles"}
        step = next(s for s in snap["spans"] if s["name"] == "oktopk/step")
        assert step["step"] == trainer.step_num

    @pytest.mark.parametrize("num_buckets", [1, 3])
    def test_capacities_are_what_the_config_computes(self, mesh4,
                                                     num_buckets):
        """``snapshot()["capacities"]``: the static sizes of the two
        buffers whose live shares (``local_k / cap_pair``, ``global_k /
        cap_gather``) the materialise's cost follows, a bucket each, as
        ``OkTopkConfig`` computes them from the bucket's n, the density
        and the number of workers."""
        cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=8,
                          lr=0.02, compressor="oktopk", density=0.05,
                          num_buckets=num_buckets)
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        mine = profiling.snapshot()["capacities"][-num_buckets:]
        assert mine == tr.capacities() and len(mine) == num_buckets
        sps = ([tr.state.sparse_state] if num_buckets == 1
               else list(tr.state.sparse_state))
        sizes = [sp.residual.shape[-1] for sp in sps]
        assert sum(sizes) == tr.algo_cfg.n
        for caps, n_b in zip(mine, sizes):
            want = OkTopkConfig(n=n_b, num_workers=4, density=0.05)
            assert caps == {"cap_pair": want.cap_pair,
                            "cap_gather": want.cap_gather,
                            "leafwise": False}
            k = int(0.05 * n_b)
            assert caps["cap_pair"] == int(2.0 * k / 4) + 8
            assert caps["cap_gather"] == int(2.5 * k / 4) + 8

    def test_trace_captured_carries_the_counters(self, trainer, tmp_path):
        from oktopk_tpu.obs.journal import EventBus
        from oktopk_tpu.obs.tracing import AnomalyTracer
        bus, seen = EventBus(), []
        bus.subscribe(lambda e: seen.append(e))
        tracer = AnomalyTracer(str(tmp_path), bus=bus, num_steps=2,
                               step_counters=trainer.step_counters)
        it = batches()
        bus.emit("guard_trip", step=trainer.step_num, bucket=0, count=1)
        first = trainer.step_num + 1
        for _ in range(3):
            tracer.on_step(trainer.step_num + 1)
            trainer.train_step(next(it))
        tracer.finish(trainer.step_num)
        (cap,) = [e for e in seen if e["event"] == "trace_captured"]
        assert [s for s, _ in cap["counters"]] == [first, first + 1]
        assert all(len(v) == len(COUNTERS) for _, v in cap["counters"])

    def test_a_failed_fetch_does_not_take_the_capture_down(self, tmp_path):
        """Observability never takes training down: a device that cannot
        be read any more (the incident itself) costs the counters, not
        the ``trace_captured`` record, and raises nothing."""
        from oktopk_tpu.obs.journal import EventBus
        from oktopk_tpu.obs.tracing import AnomalyTracer

        def dead():
            raise RuntimeError("device lost")

        bus, seen = EventBus(), []
        bus.subscribe(lambda e: seen.append(e))
        tracer = AnomalyTracer(str(tmp_path), bus=bus, num_steps=1,
                               step_counters=dead)
        bus.emit("guard_trip", step=4, bucket=0, count=1)
        tracer.on_step(5)
        tracer.finish(6)
        (cap,) = [e for e in seen if e["event"] == "trace_captured"]
        assert "counters" not in cap and cap["start_step"] == 5


class TestSubScopes:
    def test_every_sub_scope_parses_as_its_phase(self):
        for phase, subs in anatomy.SUB_SCOPES.items():
            for sub in subs:
                with_bucket = anatomy.scope_name(phase, 2) + "/" + sub
                assert anatomy.parse_scope(with_bucket) == (phase, 2)
                nested = (f"jit(step)/jit(shmap_body)/anat/b002/{with_bucket}"
                          f"/jit(fused)/anat/{phase}/{sub}/cond/gather")
                assert anatomy.parse_scope(nested) == (phase, 2)
        # the collective's own: six; the model enters those of fwd_bwd
        assert sum(len(anatomy.SUB_SCOPES[ph])
                   for ph in ("select", "stage")) <= 6

    def test_select_and_stage_ops_lie_in_exactly_one_sub_scope(self, mesh4):
        """Compiled text of the sparse step: every op whose scope path is
        under ``select`` or ``stage`` carries one sub-scope after it."""
        from oktopk_tpu.collectives.api import (batched_init_state,
                                                build_allreduce_step)
        cfg = OkTopkConfig(n=4096, num_workers=4, density=0.05,
                           warmup_steps=0)
        step = build_allreduce_step("oktopk", cfg, mesh4, warmup=False)
        text = step.lower(jnp.zeros((4, 4096), jnp.float32),
                          batched_init_state(cfg)).compile().as_text()
        import re
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        inside = [p for p in paths
                  if anatomy.parse_scope(p)
                  and anatomy.parse_scope(p)[0] in anatomy.SUB_SCOPES]
        assert len(inside) > 20
        seen = set()
        for p in inside:
            phase = anatomy.parse_scope(p)[0]
            parts = p.split("/")
            subs = [q for q in parts if q in anatomy.SUB_SCOPES[phase]]
            after = parts[len(parts) - 1 - parts[::-1].index(phase) + 1]
            assert len(set(subs)) == 1 and after == subs[-1], p
            seen.add((phase, subs[0]))
        assert seen == {(ph, s) for ph in ("select", "stage")
                        for s in anatomy.SUB_SCOPES[ph]}
