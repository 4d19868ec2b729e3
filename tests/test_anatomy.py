"""Step-anatomy plane tests (obs/anatomy.py + the in-jit annotations).

Two halves:

- analyzer tests driven by a checked-in synthetic trace-event fixture
  (tests/data/anatomy_trace.json) — phase attribution, the interval-union
  overlap math, critical-path sweep, and malformed/empty tolerance (a
  journalled ``anatomy_warning``, never a crash);
- lowering tests proving the in-jit annotations are free: the contract
  scopes appear in compiled HLO op metadata, the training trajectory is
  bit-identical with annotations on vs off, and no host callback is
  smuggled into the compiled program.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.collectives.api import batched_init_state, \
    build_allreduce_step
from oktopk_tpu.config import OkTopkConfig
from oktopk_tpu.obs import anatomy
from oktopk_tpu.obs.events import validate_journal
from oktopk_tpu.obs.journal import EventBus, RunJournal

pytestmark = pytest.mark.anatomy

N = 512
P = 8

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "anatomy_trace.json")


def make_cfg(**kw):
    kw.setdefault("n", N)
    kw.setdefault("num_workers", P)
    kw.setdefault("warmup_steps", 0)
    return OkTopkConfig(**kw)


@pytest.fixture(scope="module")
def grads():
    return jnp.asarray(
        np.random.RandomState(7).randn(P, N).astype(np.float32))


class TestNamingContract:
    def test_scope_name_forms(self):
        assert anatomy.scope_name() == "anat"
        assert anatomy.scope_name("select") == "anat/select"
        assert anatomy.scope_name(bucket=3) == "anat/b003"
        assert anatomy.scope_name("exchange", 12) == "anat/b012/exchange"

    @pytest.mark.parametrize("phase", anatomy.PHASES)
    def test_roundtrip(self, phase):
        for bucket in (None, 0, 7, 123):
            name = anatomy.scope_name(phase, bucket)
            assert anatomy.parse_scope(name) == (phase, bucket)

    def test_parse_compiled_hlo_style_names(self):
        # compiled HLO op_name metadata nests the container scope from
        # optim/distributed.py under jit frames; the innermost anatomy
        # components win
        got = anatomy.parse_scope(
            "jit(step)/jit(main)/anat/b003/anat/select/add")
        assert got == ("select", 3)
        assert anatomy.parse_scope("jit(f)/transpose/mul") is None

    def test_lanes(self):
        assert anatomy.lane_of("exchange") == "collective"
        assert anatomy.lane_of("select") == "compute"
        # phase-less ops on a collective primitive still land on the
        # collective lane (TPU device traces name the op, not the phase)
        assert anatomy.lane_of(None, "anat/b000/all-to-all.1") == \
            "collective"


class TestAnalyzer:
    def _fixture_events(self):
        with open(FIXTURE) as f:
            return json.load(f)["traceEvents"]

    def test_fixture_attribution(self):
        a = anatomy.analyze_events(self._fixture_events())
        # select b0 [0,10]ms, exchange b0 [5,12]ms, optimizer [12,15]ms;
        # the non-contract 99 ms op and the "B" event must not count
        assert a["events"] == 3
        assert a["buckets"][0]["select"] == {
            "ms": 10.0, "count": 1, "lane": "compute"}
        assert a["buckets"][0]["exchange"]["lane"] == "collective"
        assert a["buckets"][-1]["optimizer"]["ms"] == 3.0
        assert a["compute_ms"] == 13.0
        assert a["comm_ms"] == 7.0
        assert a["overlap_ms"] == 5.0
        assert abs(a["overlap_ratio"] - 5.0 / 7.0) < 1e-6
        assert a["step_ms"] == 15.0
        assert a["ideal_ms"] == 13.0
        assert a["serialization_ms"] == 2.0

    def test_fixture_critical_path(self):
        a = anatomy.analyze_events(self._fixture_events())
        # [0,5] select alone, [5,10] select+exchange split, [10,12]
        # exchange alone, [12,15] optimizer alone
        assert a["critical_path"] == {
            "select": 7.5, "exchange": 4.5, "optimizer": 3.0}
        assert a["critical_phase"] == "select"
        assert anatomy.phase_totals(a) == {
            "select": 10.0, "exchange": 7.0, "optimizer": 3.0}

    def test_loads_fixture_file(self):
        events, resolved, problem = anatomy.load_trace_events(FIXTURE)
        assert problem is None and resolved == FIXTURE
        assert len(events) == 6

    def test_emitted_events_validate(self):
        bus = EventBus()
        journal = RunJournal(None, bus)
        a = anatomy.analyze_capture(FIXTURE, bus=bus, step=7,
                                    source="fixture")
        assert a is not None
        kinds = [e["event"] for e in journal.entries]
        assert kinds.count("step_anatomy") == 2   # buckets -1 and 0
        assert kinds.count("overlap_report") == 1
        assert validate_journal(journal.entries) == []
        rep = next(e for e in journal.entries
                   if e["event"] == "overlap_report")
        assert rep["step"] == 7 and rep["source"] == "fixture"

    @pytest.mark.parametrize("payload", [
        "not json at all {{{",
        '{"traceEvents": "not a list"}',
        '{"traceEvents": []}',
        '[{"name": "no_anatomy_here", "ph": "X", "ts": 0, "dur": 5}]',
    ])
    def test_malformed_trace_warns_never_raises(self, tmp_path, payload):
        p = tmp_path / "broken.trace.json"
        p.write_text(payload)
        bus = EventBus()
        journal = RunJournal(None, bus)
        assert anatomy.analyze_capture(str(p), bus=bus) is None
        warns = [e for e in journal.entries
                 if e["event"] == "anatomy_warning"]
        assert len(warns) == 1 and warns[0]["reason"]
        assert validate_journal(journal.entries) == []

    def test_missing_path_warns(self, tmp_path):
        bus = EventBus()
        journal = RunJournal(None, bus)
        assert anatomy.analyze_capture(
            str(tmp_path / "nope"), bus=bus) is None
        assert any(e["event"] == "anatomy_warning"
                   for e in journal.entries)

    def test_gzip_and_bare_list_accepted(self, tmp_path):
        import gzip
        events = [{"name": "anat/select", "ph": "X", "ts": 0.0,
                   "dur": 2000.0}]
        p = tmp_path / "t.trace.json.gz"
        with gzip.open(p, "wt") as f:
            json.dump(events, f)
        got, resolved, problem = anatomy.load_trace_events(str(tmp_path))
        assert problem is None and got == events
        a = anatomy.analyze_events(got)
        assert a["compute_ms"] == 2.0 and a["comm_ms"] == 0.0
        assert a["overlap_ratio"] == 0.0   # no comm: ratio floors at 0


class TestLowering:
    def _compile_text(self, mesh8, grads, cfg):
        # build_allreduce_step returns the jitted callable — lower it
        # directly; named scopes only surface in COMPILED HLO op
        # metadata, never in the stablehlo of .as_text() pre-compile
        step = build_allreduce_step("oktopk", cfg, mesh8, warmup=False)
        st = batched_init_state(cfg)
        return step.lower(grads, st).compile().as_text()

    def test_scopes_reach_compiled_hlo(self, mesh8, grads):
        cfg = make_cfg(density=0.05)
        text = self._compile_text(mesh8, grads, cfg)
        assert "anat/b000/select" in text
        assert "anat/b000/exchange" in text
        assert "anat/b000/combine" in text

    def test_annotations_add_no_host_callbacks(self, mesh8, grads):
        cfg = make_cfg(density=0.05)
        text = self._compile_text(mesh8, grads, cfg)
        for marker in ("xla_python_cpu_callback",
                       "xla_ffi_python_cpu_callback", "io_callback"):
            assert marker not in text

    def test_trajectory_bit_identical_on_off(self, mesh8):
        cfg = make_cfg(density=0.05)
        rng = np.random.RandomState(3)
        grads = [jnp.asarray(rng.randn(P, N).astype(np.float32))
                 for _ in range(3)]

        def run():
            step = build_allreduce_step("oktopk", cfg, mesh8,
                                        warmup=False)
            st = batched_init_state(cfg)
            outs = []
            for g in grads:
                out, st = step(g, st)
                outs.append(np.asarray(out))
            return outs, np.asarray(st.residual)

        prev = anatomy.set_annotations(True)
        try:
            outs_on, res_on = run()
            anatomy.set_annotations(False)
            outs_off, res_off = run()
        finally:
            anatomy.set_annotations(prev)
        for a, b in zip(outs_on, outs_off):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(res_on, res_off)

    def test_disabled_annotations_leave_no_scopes(self, mesh8, grads):
        cfg = make_cfg(density=0.05)
        prev = anatomy.set_annotations(False)
        try:
            text = self._compile_text(mesh8, grads, cfg)
        finally:
            anatomy.set_annotations(prev)
        assert "anat/b000" not in text


class TestSubScopesKeepThePhase:
    def test_sub_scope_names_parse_as_their_phase(self):
        # took the place of the ChromeTraceSink lane test: what groups a
        # name into its (bucket, phase) family is parse_scope, and it has
        # to go on doing so under the sub-scopes of select and stage
        for phase, subs in anatomy.SUB_SCOPES.items():
            for sub in subs:
                name = f"jit(step)/anat/b001/anat/b001/{phase}/{sub}/gather"
                assert anatomy.parse_scope(name) == (phase, 1)
        assert anatomy.parse_scope("data_wait") is None
        with pytest.raises(ValueError):
            anatomy.phase_scope("stage", 0, sub="threshold")


class TestSummaryPercentiles:
    def test_nearest_rank(self):
        from oktopk_tpu.utils.profiling import PhaseTimers
        t = PhaseTimers()
        for v in range(1, 101):          # 1..100 ms
            t.add("step", v / 1e3)
        s = t.summary()["step"]
        assert s["min_ms"] == 1.0 and s["max_ms"] == 100.0
        assert s["p50_ms"] == 50.0
        assert s["p95_ms"] == 95.0
        assert s["count"] == 100
