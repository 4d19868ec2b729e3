"""Whose each instruction of the compiled step is (obs/anatomy.py:
``owners``, ``analyze_device``, ``analyze_xplane``) and what the anomaly
tracer journals from a device capture.

- ``owners()`` a rule at a time on a small hand-written HLO text, and on an
  excerpt of a real compiled TPU step (tests/data/step_tpu_excerpt.hlo.txt);
- on the CPU-compiled steps of two tiny trainers: every instruction gets an
  owner, the ``own`` set is ``parse_scope``'s, and asking for the map leaves
  the step program as it was;
- ``analyze_device`` closes on the union busy time, with overlaps and
  containers;
- ``AnomalyTracer`` journals ``step_anatomy`` with ``source: "device"`` from
  a stubbed capture, and the window alone when the reducer raises.
"""

import hashlib
import os
import types

import jax
import pytest

from oktopk_tpu.config import TrainConfig
from oktopk_tpu.obs import anatomy
from oktopk_tpu.obs.events import validate_journal
from oktopk_tpu.obs.journal import EventBus, RunJournal
from oktopk_tpu.obs.tracing import AnomalyTracer
from oktopk_tpu.train.trainer import Trainer

pytestmark = pytest.mark.anatomy

EXCERPT = os.path.join(os.path.dirname(__file__), "data",
                       "step_tpu_excerpt.hlo.txt")

ATTN = "jit(step)/anat/fwd_bwd/transpose(jvp(anat/fwd_bwd/attention))/mul"
WIDE = ", ".join(f"%filler.{i}" for i in range(200))
FILLERS = "\n".join(f"  %filler.{i} = f32[8] parameter({i + 3})"
                    for i in range(200))
PAYLOAD = "QUJD" * 1500     # a Mosaic kernel's bytes, 6,000 characters

HLO = f"""HloModule jit_step, is_scheduled=true

FileNames
1 "/work/oktopk_tpu/models/toy.py"
2 "/opt/venv/lib/python3.12/site-packages/flax/linen/linear.py"

FunctionNames
1 "attend"
2 "Dense.__call__"

FileLocations
1 {{file_name_id=1 function_name_id=1 line=155 end_line=155 column=4 end_column=9}}
2 {{file_name_id=2 function_name_id=2 line=10 end_line=10 column=1 end_column=2}}

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}
2 {{file_location_id=2 parent_frame_id=2}}

%fused_sgd (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8] parameter(0)
  ROOT %neg.1 = f32[8] negate(%p0)
}}

%loop_body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %arg = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8]{{0:T(8,128)}} get-tuple-element(%arg), index=1
  %copy-start.1 = (f32[8]{{0:T(8,128)}}, f32[8]{{0:T(8,128)S(1)}}, u32[]{{:S(2)}}) copy-start(%x)
  %copy-done.1 = f32[8]{{0:T(8,128)S(1)}} copy-done(%copy-start.1)
  %scores = f32[8] fusion(%copy-done.1), kind=kLoop, calls=%fused_scores, metadata={{op_name="{ATTN}" stack_frame_id=2}}
  %slice.2 = f32[4] slice(%scores), slice={{[0:4]}}
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  %ping = f32[8] add(%pong)
  %pong = f32[8] add(%ping)
  ROOT %out = (s32[], f32[8]) tuple(%next, %scores)
}}

%loop_cond (arg.1: (s32[], f32[8])) -> pred[] {{
  %arg.1 = (s32[], f32[8]) parameter(0)
  ROOT %lt = pred[] constant(true)
}}

%branch_rows (pa: f32[8]) -> f32[8] {{
  %pa = f32[8] parameter(0)
  ROOT %ragged-dot-none.1 = f32[8] custom-call(%pa), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
}}

%branch_all (pb: f32[8]) -> f32[8] {{
  %pb = f32[8] parameter(0)
  ROOT %dense.1 = f32[8] convolution(%pb, %pb)
}}

%on_true (pt: f32[8]) -> f32[8] {{
  %pt = f32[8] parameter(0)
  ROOT %t.1 = f32[8] negate(%pt)
}}

%on_false (pf: f32[8]) -> f32[8] {{
  %pf = f32[8] parameter(0)
  ROOT %f.1 = f32[8] abs(%pf)
}}

ENTRY %main (w: f32[8], g: f32[8], lonely: f32[8]) -> f32[8] {{
  %w = f32[8] parameter(0)
  %g = f32[8] parameter(1)
  %lonely = f32[8] parameter(2)
{FILLERS}
  %orphan = f32[8] copy(%lonely)
  %init = (s32[], f32[8]) tuple(%w, %g)
  %while.1 = (s32[], f32[8]) while(%init), condition=%loop_cond, body=%loop_body, metadata={{op_name="jit(step)/anat/fwd_bwd/while" stack_frame_id=1}}
  %y = f32[8] get-tuple-element(%while.1), index=1
  %cond.1 = f32[8] conditional(%p, %y, %y), branch_computations={{%branch_rows, %branch_all}}, metadata={{op_name="jit(step)/anat/fwd_bwd/jvp(M)/anat/fwd_bwd/experts/cond"}}
  %cond.2 = f32[8] conditional(%p, %y, %y), true_computation=%on_true, false_computation=%on_false, metadata={{op_name="jit(step)/anat/b001/combine/cond"}}
  %left = f32[8] multiply(%cond.1, %cond.1), metadata={{op_name="jit(step)/anat/b000/anat/b000/select/sweep/mul"}}
  %right = f32[8] multiply(%cond.2, %cond.2), metadata={{op_name="jit(step)/anat/b000/anat/b000/stage/finalize/mul"}}
  %tie = f32[8] add(%left, %right)
  %mosaic.1 = f32[8] custom-call(%tie), custom_call_target="tpu_custom_call", backend_config={{"custom_call_config":{{"body":"{PAYLOAD}"}}}}, metadata={{op_name="jit(step)/anat/b000/select/global/pallas_call"}}
  %wide = (f32[8]) tuple({WIDE}, %mosaic.1)
  ROOT %sgd = f32[8] fusion(%w), kind=kLoop, calls=%fused_sgd, metadata={{op_name="jit(step)/anat/optimizer/sub"}}
}}
"""

# instruction -> (how, phase, sub, bucket)
CASES = {
    "own, a bracketed path": ("scores", "own", "fwd_bwd", "attention", None),
    "pair, a done takes its start's": (
        "copy-done.1", "pair", "fwd_bwd", "attention", None),
    "user, a start whose operands lead nowhere": (
        "copy-start.1", "user", "fwd_bwd", "attention", None),
    "operand": ("slice.2", "operand", "fwd_bwd", "attention", None),
    "user, two levels down": ("x", "user", "fwd_bwd", "attention", None),
    "body, a while body": ("one", "body", "fwd_bwd", None, None),
    "body, a while condition": ("lt", "body", "fwd_bwd", None, None),
    "body, the first of branch_computations": (
        "ragged-dot-none.1", "body", "fwd_bwd", "experts", None),
    "body, the second of branch_computations": (
        "dense.1", "body", "fwd_bwd", "experts", None),
    "body, true_computation": ("t.1", "body", "combine", None, 1),
    "body, false_computation": ("f.1", "body", "combine", None, 1),
    "body, a calls= fusion": ("neg.1", "body", "optimizer", None, None),
    "none": ("orphan", "none", None, None, None),
    "a tie goes to the operand written first": (
        "tie", "operand", "select", "sweep", 0),
    "a cycle ends and falls to the body": (
        "ping", "body", "fwd_bwd", None, None),
    "own, behind a payload of 6,000 characters": (
        "mosaic.1", "own", "select", "global", 0),
    "operand, the last of 201 on one line": (
        "wide", "operand", "select", "global", 0),
}


@pytest.fixture(scope="module")
def owner_map():
    return anatomy.owners(HLO)


class TestOwnerRules:
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_rule(self, owner_map, case):
        name, how, phase, sub, bucket = CASES[case]
        got = owner_map[name]
        assert (got.how, got.phase, got.sub, got.bucket) == (
            how, phase, sub, bucket)

    def test_long_lines_are_long(self):
        lines = {ln.split("=")[0].strip(): len(ln) for ln in HLO.split("\n")}
        assert lines["%mosaic.1"] > 1500 and lines["%wide"] > 1500

    def test_every_instruction_has_an_owner_and_a_known_rule(self, owner_map):
        assert len(owner_map) == 239
        assert {o.how for o in owner_map.values()} == set(anatomy.HOWS)

    def test_two_calls_agree(self, owner_map):
        assert anatomy.owners(HLO) == owner_map

    def test_frame_is_the_innermost_inside_the_repo(self, owner_map):
        # frame 2 is flax's; its parent, frame 1, is the repo's
        assert owner_map["scores"].frame == "models/toy.py:155 attend"
        # what inherits, and has no frame of its own, takes its source's
        assert owner_map["copy-done.1"].frame == "models/toy.py:155 attend"
        assert owner_map["sgd"].frame is None

    def test_a_text_without_tables_or_scopes(self):
        text = HLO[HLO.index("%fused_sgd"):].replace("anat/", "")
        got = anatomy.owners(text)
        assert len(got) == 239
        assert {o.how for o in got.values()} == {"none"}
        assert anatomy.owners("") == {}

    @pytest.mark.parametrize("path,want", [
        ("jit(s)/anat/fwd_bwd/transpose(jvp(anat/fwd_bwd/experts))/dot",
         ("fwd_bwd", None, None, "experts")),
        ("jit(s)/anat/b002/lvl1/exchange/all-to-all",
         ("exchange", 2, 1, None)),
        ("jit(s)/anat/fwd_bwd/linear_attention/x/anat/fwd_bwd/delta_rule/y",
         ("fwd_bwd", None, None, "delta_rule")),
        ("jit(s)/anat/b000/select/sweep/jit(f)/anat/b000/stage/add",
         ("stage", 0, None, None)),
        ("jit(s)/anat/fwd_bwd/sweep/mul", ("fwd_bwd", None, None, None)),
        ("ragged-dot-none", None),
    ])
    def test_parse_op_path(self, path, want):
        assert anatomy.parse_op_path(path) == want


class TestRealExcerpt:
    """Lines of the compiled TPU step of ``dsv2lite_dense_x1`` (a traced
    run on a v5e; Mosaic payloads cut): what XLA:TPU made carries no scope
    and comes out with its surroundings' owner."""

    @pytest.fixture(scope="class")
    def excerpt(self):
        with open(EXCERPT) as f:
            text = f.read()
        assert len(text.split("\n")) <= 300
        return text, anatomy.owners(text)

    def test_copies_in_the_query_block_loop_are_attentions(self, excerpt):
        _, got = excerpt
        done = [o for n, o in got.items() if n.startswith("copy-done")]
        assert done
        for o in done:
            assert (o.phase, o.sub, o.how) == ("fwd_bwd", "attention", "pair")
            assert o.frame.startswith("models/deepseek_v2.py:")

    def test_the_grouped_product_is_the_experts(self, excerpt):
        _, got = excerpt
        kernels = [o for n, o in got.items()
                   if n.startswith("ragged-dot-none")]
        assert kernels
        for o in kernels:
            assert (o.phase, o.sub) == ("fwd_bwd", "experts")
            assert o.how in ("operand", "user", "body")

    def test_the_conditional_and_its_tables(self, excerpt):
        text, got = excerpt
        cond = [o for n, o in got.items() if n.startswith("cond")]
        assert cond and all((o.phase, o.sub, o.how) == (
            "fwd_bwd", "experts", "own") for o in cond)
        assert "\nStackFrames\n" in text
        assert all(len(ln) < 1500 or "tpu_custom_call" not in ln
                   for ln in text.split("\n"))


def _lowered_hash(tr, batch):
    text = tr.step_fn.lower(tr.state, batch, tr._rng).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


TRAINERS = {
    "lstm_tiny": (dict(dnn="lstm_tiny", dataset="ptb", batch_size=2, lr=1.0,
                       compressor="dense"), {}),
    "deepseek_v2_tiny": (dict(dnn="deepseek_v2_tiny", dataset="ptb",
                              batch_size=2, lr=0.05, compressor="dense",
                              grad_clip=1.0),
                         {"held_experts": [0, 1, 2, 3]}),
    # a model that lives in a loop's body (one stack run four times)
    "ouro_tiny": (dict(dnn="ouro_tiny", dataset="ptb", batch_size=2,
                       lr=0.05, compressor="dense", grad_clip=1.0), {}),
    # a sequence mixer that is no attention (short convolutions, three
    # layers in four) beside one attention layer and routed experts
    "lfm2_tiny": (dict(dnn="lfm2_tiny", dataset="ptb", batch_size=2,
                       lr=0.05, compressor="dense", grad_clip=1.0),
                  {"held_experts": [0, 1, 2, 3]}),
}


class TestCompiledStep:
    @pytest.fixture(scope="class", params=sorted(TRAINERS))
    def built(self, request, mesh4):
        cfg, kwargs = TRAINERS[request.param]
        tr = Trainer(TrainConfig(**cfg), mesh=mesh4, warmup=False,
                     model_kwargs=kwargs)
        batch = tr._example_batch(8)
        before = _lowered_hash(tr, batch)
        text = tr.step_hlo(batch)
        got = tr.step_owners(batch)
        return tr, batch, before, text, got

    def test_every_instruction_gets_an_owner(self, built):
        _, _, _, text, got = built
        names = {m.group(1) for ln in text.split("\n")
                 if ln.startswith("  ")
                 and (m := anatomy._INSTRUCTION.match(ln))}
        assert names and set(got) == names
        assert all(o.how in anatomy.HOWS for o in got.values())

    def test_own_is_what_parse_scope_parses(self, built):
        _, _, _, text, got = built
        want = set()
        for ln in text.split("\n"):
            m = anatomy._INSTRUCTION.match(ln) if ln[:2] == "  " else None
            op = anatomy._OP_NAME.search(ln) if m else None
            if op and (anatomy.parse_scope(op.group(1)) or (None,))[0]:
                want.add(m.group(1))
        assert want and {n for n, o in got.items() if o.how == "own"} == want
        assert {got[n].phase for n in want} >= {"fwd_bwd", "optimizer"}

    def test_two_calls_agree(self, built):
        _, _, _, text, got = built
        assert anatomy.owners(text) == got

    def test_the_map_leaves_the_step_program_alone(self, built):
        tr, batch, before, _, _ = built
        assert _lowered_hash(tr, batch) == before

    def test_most_of_the_step_is_owned(self, built):
        _, _, _, _, got = built
        none = sum(o.how == "none" for o in got.values())
        assert none < 0.05 * len(got)

    def test_a_loop_bodys_instructions_carry_their_sub_scope(self, built):
        """``ouro_tiny``: the layers are instructions of a ``while``'s body
        computation; their own ``op_name`` gives the sub-scope, and what
        the compiler put beside them in the body inherits one."""
        tr, _, _, text, got = built
        if tr.cfg.dnn != "ouro_tiny":
            pytest.skip("the model of the other trainers is no loop's body")
        _, insts, caller_of = anatomy._parse_hlo(text)
        loops = {comp for comp, caller in caller_of.items()
                 if insts[caller].opcode == "while"}
        assert len(loops) >= 2      # forward and backward, body and condition
        inside = [n for n, i in insts.items() if i.computation in loops]
        subs = {got[n].sub for n in inside if got[n].phase == "fwd_bwd"}
        assert subs >= {"attention", "full_scores", "mlp", "head",
                        "exit_gate"}
        assert sum(got[n].how == "none" for n in inside) == 0
        owned = [n for n in inside if got[n].how != "own"]
        assert owned and all(got[n].phase for n in owned)

    def test_a_conv_mixers_instructions_carry_their_sub_scopes(self, built):
        """``lfm2_tiny``: the short-convolution operator and the gated
        convolution inside it are named beside the attention layer's, the
        dense layer's and the experts' scopes, and what the compiler fused
        across them still gets an owner."""
        tr, _, _, _, got = built
        if tr.cfg.dnn != "lfm2_tiny":
            pytest.skip("the other trainers' models have no conv mixer")
        mine = [o for o in got.values() if o.phase == "fwd_bwd"]
        assert {o.sub for o in mine} >= {
            "short_conv", "gated_conv", "attention", "full_scores", "mlp",
            "head", "router", "experts"}
        for sub in ("short_conv", "gated_conv"):
            assert any(o.sub == sub and o.how == "own" for o in mine), sub


# (instruction, start, end) in seconds: a loop that spans two leaves and a
# gap, an asynchronous copy that overlaps the loop's end, an orphan that
# overlaps the optimizer, and idle time
TRIPLES = [("while.1", 0.0, 10.0), ("scores", 1.0, 3.0),
           ("copy-done.1", 3.0, 4.0), ("scores", 6.0, 9.0),
           ("copy-start.1", 9.5, 11.0), ("sgd", 12.0, 13.0),
           ("orphan", 12.5, 14.0), ("unknown.7", 20.0, 21.0),
           ("empty", 30.0, 30.0)]


class TestAnalyzeDevice:
    @pytest.mark.parametrize("steps", [1, 4])
    def test_the_table_closes_on_the_union_busy_time(self, owner_map, steps):
        a = anatomy.analyze_device(TRIPLES, owner_map, steps=steps)
        rows = a["owners"]
        total = sum(sum(r.values()) for r in rows.values()) + a["unowned_ms"]
        union = 11.0 + 2.0 + 1.0          # [0, 11], [12, 14], [20, 21]
        assert total == pytest.approx(a["busy_ms"])
        assert a["busy_ms"] == pytest.approx(1e3 * union / steps)
        # own: the leaves whose op_name names the phase
        assert rows["fwd_bwd/attention"]["own_ms"] == pytest.approx(
            5e3 / steps)
        # inherited: the done (pair) and the start (user), whose last
        # 1.0 s lies past the loop's end
        assert rows["fwd_bwd/attention"]["inherited_ms"] == pytest.approx(
            2.5e3 / steps)
        # the loop's own time: [0, 1], [4, 6], [9, 9.5]
        assert rows["fwd_bwd"] == {
            "own_ms": 0.0, "inherited_ms": 0.0,
            "control_ms": pytest.approx(3.5e3 / steps)}
        assert rows["optimizer"]["own_ms"] == pytest.approx(0.5e3 / steps)
        assert a["unowned_ms"] == pytest.approx(2.5e3 / steps)

    def test_largest_instructions_carry_how_and_frame(self, owner_map):
        a = anatomy.analyze_device(TRIPLES, owner_map)
        assert [(r["name"], r["how"], r["owner"]) for r in
                a["largest_inherited"]] == [
            ("copy-start.1", "user", "fwd_bwd/attention"),
            ("copy-done.1", "pair", "fwd_bwd/attention")]
        assert a["largest_inherited"][0]["frame"] == "models/toy.py:155 attend"
        assert [r["name"] for r in a["largest_unowned"]] == [
            "orphan", "unknown.7"]

    def test_the_core_is_analyze_events(self, owner_map):
        """Same scorecard fields as the Chrome-JSON front end, from the
        same core: phases by bucket, lanes, the measured span."""
        a = anatomy.analyze_device(TRIPLES, owner_map)
        events = [{"name": "anat/fwd_bwd", "ph": "X", "ts": 0.0,
                   "dur": 11e6},
                  {"name": "anat/optimizer", "ph": "X", "ts": 12e6,
                   "dur": 0.5e6}]
        b = anatomy.analyze_events(events)
        assert set(b) <= set(a)
        assert a["buckets"][-1]["fwd_bwd"]["ms"] == pytest.approx(
            b["buckets"][-1]["fwd_bwd"]["ms"])
        assert a["buckets"][-1]["fwd_bwd"]["control_ms"] == pytest.approx(3.5e3)
        assert a["buckets"][-1]["other"]["ms"] == pytest.approx(2.5e3)
        assert a["step_ms"] == pytest.approx(21e3)
        assert a["critical_path"]["idle"] == pytest.approx(7e3)
        assert a["events"] == 8

    def test_nothing_to_analyze(self, owner_map):
        assert anatomy.analyze_device([], owner_map) is None
        assert anatomy.analyze_device([("x", 1.0, 1.0)], owner_map) is None


def _ev(name, start_s, dur_s):
    return types.SimpleNamespace(name=name, start_ns=int(start_s * 1e9),
                                 duration_ns=int(dur_s * 1e9))


def _profile(device=True):
    ops = [_ev(f"%{n} = f32[8] fusion(%p)", s, e - s)
           for n, s, e in TRIPLES]
    # the key split's program runs beside the step's and is left out
    ops.append(_ev("%scores = u32[2] fusion()", 40.0, 1.0))
    mods = [_ev("jit_step(1)", 0.0, 31.0), _ev("jit_split(2)", 40.0, 1.0)]
    lines = [types.SimpleNamespace(name="XLA Modules", events=mods),
             types.SimpleNamespace(name="XLA Ops", events=ops)]
    planes = [types.SimpleNamespace(name="/host:CPU", lines=[])]
    if device:
        planes.append(types.SimpleNamespace(name="/device:TPU:0",
                                            lines=lines))
    return types.SimpleNamespace(planes=planes)


class TestAnalyzeXplane:
    def test_reads_the_device_plane(self, tmp_path, monkeypatch):
        path = tmp_path / "plugins" / "profile" / "t" / "h.xplane.pb"
        path.parent.mkdir(parents=True)
        path.write_bytes(b"")
        monkeypatch.setattr(anatomy, "_load_profile", lambda p: _profile())
        a = anatomy.analyze_xplane(str(tmp_path), HLO, steps=2)
        assert a["chips"] == 1 and a["steps"] == 2
        assert a["busy_ms"] == pytest.approx(14e3 / 2)
        assert a["owners"]["fwd_bwd/attention"]["own_ms"] == pytest.approx(
            5e3 / 2)

    def test_no_device_plane_no_file(self, tmp_path, monkeypatch):
        assert anatomy.analyze_xplane(str(tmp_path), HLO) is None
        (tmp_path / "h.xplane.pb").write_bytes(b"")
        monkeypatch.setattr(anatomy, "_load_profile",
                            lambda p: _profile(device=False))
        assert anatomy.analyze_xplane(str(tmp_path), HLO) is None


class TestAnomalyTracerJournalsTheDeviceAnatomy:
    @pytest.fixture
    def captured(self, tmp_path, monkeypatch):
        def start(d, **kw):
            with open(os.path.join(d, "h.xplane.pb"), "wb"):
                pass
        monkeypatch.setattr(jax.profiler, "start_trace", start)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setattr(anatomy, "_load_profile", lambda p: _profile())

        def run(step_hlo):
            bus = EventBus()
            journal = RunJournal(None, bus)
            tracer = AnomalyTracer(str(tmp_path), bus=bus, num_steps=2,
                                   step_hlo=step_hlo)
            bus.emit("guard_trip", step=4, buckets=[0],
                     consecutive_skips=1, strikes=[1])
            for step in (5, 6, 7):
                tracer.on_step(step)
            return tracer, journal.entries
        return run

    def test_step_anatomy_with_source_device(self, captured):
        tracer, entries = captured(lambda: HLO)
        kinds = [e["event"] for e in entries]
        assert kinds.index("trace_captured") < kinds.index("step_anatomy")
        anat = [e for e in entries if e["event"] == "step_anatomy"]
        assert {e["bucket"] for e in anat} == {-1}
        assert all(e["source"] == "device" and e["step"] == 7 for e in anat)
        phases = anat[0]["phases"]
        # milliseconds a captured step: two steps in the window
        assert phases["fwd_bwd"]["own_ms"] == pytest.approx(5e3 / 2)
        assert phases["fwd_bwd"]["inherited_ms"] == pytest.approx(2.5e3 / 2)
        assert phases["other"]["ms"] == pytest.approx(2.5e3 / 2)
        report = next(e for e in entries if e["event"] == "overlap_report")
        assert report["source"] == "device"
        assert validate_journal(entries) == []
        assert len(tracer.captures) == 1

    def test_the_window_is_journalled_when_the_reducer_raises(self, captured):
        def broken():
            raise RuntimeError("no compiler today")
        tracer, entries = captured(broken)
        kinds = [e["event"] for e in entries]
        assert kinds.count("trace_captured") == 1
        assert "step_anatomy" not in kinds and "overlap_report" not in kinds
        assert len(tracer.captures) == 1 and not tracer.active

    def test_a_capture_without_device_planes_warns(self, captured,
                                                   monkeypatch):
        monkeypatch.setattr(anatomy, "_load_profile",
                            lambda p: _profile(device=False))

        def never():
            raise AssertionError("compiled for a capture with no device")
        _, entries = captured(never)
        warn = [e for e in entries if e["event"] == "anatomy_warning"]
        assert len(warn) == 1 and warn[0]["source"] == "device"
        assert validate_journal(entries) == []

    def test_no_step_text_no_anatomy(self, captured):
        _, entries = captured(None)
        kinds = [e["event"] for e in entries]
        assert "trace_captured" in kinds and "step_anatomy" not in kinds
        assert "anatomy_warning" not in kinds
