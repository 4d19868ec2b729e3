"""Step-anatomy plane: the phase naming contract, whose each instruction of
the compiled step is, and the attribution of a trace's time to its phases.

The jitted train step is one opaque XLA program; the reference's
per-phase timers (VGG/allreducer.py:256-262) have no analogue inside
it. This module gives the step a time-domain anatomy in four pieces:

1. **Naming contract** — ``scope_name(phase, bucket)`` produces names
   like ``anat/b003/exchange``. ``phase_scope(...)`` wraps pipeline
   regions in ``jax.named_scope`` so the names reach compiled-HLO op
   metadata (``op_name="jit(step)/.../anat/b000/select/..."``). The
   scopes are pure metadata: computation is bit-identical
   annotations-on vs annotations-off and no host callback is ever
   introduced (tests/test_anatomy.py pins both).

2. **The owners' map** — ``owners(hlo_text)``: for every instruction of
   the compiled step's text an ``Owner`` (phase, sub-scope, bucket, the
   rule that answered, the source frame). A device event of a TPU trace
   is named by its instruction and carries NO scope; the scope is in the
   instruction's ``op_name``. What the compiler made or renamed
   (asynchronous copies and slices, layout changes, its own kernels for
   ``lax.ragged_dot``) has no ``op_name`` of the program's and is given
   the owner of its surroundings: its ``*-start``, its nearest operand,
   its nearest user, the loop or branch it lies in. A pure text pass.

3. **Trace analyzer** — two front ends over one core (interval unions,
   compute and collective lanes, the overlap scorecard, a sweep for the
   critical path). ``analyze_events`` takes Chrome trace-event JSON whose
   events are NAMED by their scope (host annotations, checked-in
   fixtures). ``analyze_device`` takes the instruction-named events of a
   chip's line of operations and the owners' map, gives every instant of
   the busy time to the innermost event that covers it, and returns,
   beside the scorecard, the table that closes on the busy time: for
   every (phase, sub-scope) the time of its own instructions, of those
   that inherit from it and of its loops and branches themselves, and the
   time nobody owns, with the largest instructions of each.
   ``analyze_xplane`` reads them from a ``jax.profiler`` capture.

4. **Journal events** — ``step_anatomy`` (one per bucket; model-level
   unbucketed phases land on bucket -1) and one ``overlap_report``
   carrying the scorecard: measured span vs the ideal fully-overlapped
   lower bound ``max(compute_ms, comm_ms)``; ``source`` says which
   front end read it (``"device"``: the anomaly tracer's capture,
   obs/tracing.py). Malformed or empty traces journal one
   ``anatomy_warning``.

Scorecard semantics (docs/OBSERVABILITY.md "Step anatomy"):
``overlap_ratio = overlap_ms / comm_ms`` — the fraction of collective
time hidden under compute. A fully serial step scores 0.0; the
ROADMAP's bucket-pipelined overlap item is judged by how far it moves
this number toward 1.0 while ``step_ms`` approaches ``ideal_ms``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

SCOPE_PREFIX = "anat"

# the phase vocabulary of the collectives pipeline, in pipeline order
PHASES = ("fwd_bwd", "select", "stage", "exchange", "combine", "optimizer")

# Named steps of the algorithm inside ``select``, ``stage``: plain
# ``jax.named_scope``s UNDER a phase frame (``anat/b000/select/threshold``),
# not contract frames of their own, so ``parse_scope`` and every reader of
# the phase go on answering ``select`` / ``stage`` for the ops inside.
# Every op of those two phases lies in exactly one (collectives/oktopk.py).
SUB_THRESHOLD = "threshold"      # select: local threshold, exact or predicted
SUB_SWEEP = "sweep"              # select: the n-scale sweep and its wrapper
SUB_REPARTITION = "repartition"  # stage: region boundaries
SUB_FINALIZE = "finalize"        # stage: census, prefix, branch, gathers
SUB_GLOBAL = "global"            # select: phase-(b) winner selection
SUB_FEEDBACK = "feedback"        # select: controller feedback
# ... and inside ``fwd_bwd``, entered by the model itself
# (models/deepseek_v2.py, models/qwen3_next.py, models/smallthinker.py,
# models/laguna.py, models/ouro.py; ``router``, ``experts`` and ``shared``
# by the expert layer they share, models/moe.py), so forward, recomputed
# and backward operations alike carry them: a model that enters none leaves
# the phase unscoped. Four lie inside another, and a reader takes the innermost:
# ``delta_rule`` (the chunked recurrence alone) inside
# ``linear_attention`` (its projections, convolution, gates and norm);
# ``window_scores`` (a windowed layer's
# scores, softmax and weighted sum alone) inside ``window_attention`` (its
# projections, rotary and output projection); and, in models/laguna.py,
# ``full_scores`` (a full layer's scores, softmax and weighted sum alone)
# inside ``attention``, and ``attn_gate`` (the per-head output gate's
# projection, sigmoid and product) inside ``attention`` or
# ``window_attention``, whichever the layer is. ``exit_gate`` is a looped
# model's alone (models/ouro.py): the exit gate's product inside a block of
# the head, and the exits' distribution, mixing and entropy after the loop.
# ``short_conv`` is models/lfm2.py's convolution mixer (its two products,
# gates and taps; the norm before it excluded) and ``gated_conv`` inside it
# the elementwise part alone (``B * z``, the taps, ``C *``).
SUB_SCOPES = {
    "select": (SUB_THRESHOLD, SUB_SWEEP, SUB_GLOBAL, SUB_FEEDBACK),
    "stage": (SUB_REPARTITION, SUB_FINALIZE),
    "fwd_bwd": ("attention", "router", "experts", "shared", "mlp", "head",
                "linear_attention", "delta_rule", "window_attention",
                "window_scores", "full_scores", "attn_gate", "exit_gate",
                "short_conv", "gated_conv"),
}

# phases whose time is wire time; everything else in the contract is
# compute. Raw op names matching _COLLECTIVE_OPS inside a contract
# scope are classified collective regardless of phase (a psum inside a
# select region is still wire time).
COLLECTIVE_PHASES = frozenset({"exchange"})
_COLLECTIVE_OPS = re.compile(
    r"all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|alltoall|allreduce|allgather|ppermute\b|\bpsum\b", re.I)

_BUCKET_RE = re.compile(r"^b(\d+)$")
# Optional hierarchy-level lane (collectives/hierarchical.py):
# ``anat/b000/lvl1/exchange`` — level 0 = intra-pod, level 1 = inter-pod.
# Legacy names carry no lvl component and parse exactly as before.
_LEVEL_RE = re.compile(r"^lvl(\d+)$")

# module-level switch for the bit-identity test and for opting the
# annotations out entirely (OKTOPK_ANATOMY=0). Scopes are applied at
# trace time, so flipping this only affects steps built afterwards.
_ENABLED = os.environ.get("OKTOPK_ANATOMY", "1").lower() not in (
    "0", "false", "off")


def set_annotations(enabled: bool) -> bool:
    """Enable/disable the in-jit named scopes; returns the previous
    setting. Affects only steps traced after the call."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(enabled)
    return prev


def annotations_enabled() -> bool:
    return _ENABLED


def scope_name(phase: Optional[str] = None,
               bucket: Optional[int] = None,
               level: Optional[int] = None) -> str:
    """The contract name: ``anat``, ``anat/b003``, ``anat/select``,
    ``anat/b003/select`` or — with a hierarchy level —
    ``anat/b003/lvl1/exchange``."""
    parts = [SCOPE_PREFIX]
    if bucket is not None:
        parts.append(f"b{int(bucket):03d}")
    if level is not None:
        parts.append(f"lvl{int(level)}")
    if phase is not None:
        parts.append(str(phase))
    return "/".join(parts)


def phase_scope(phase: Optional[str] = None, bucket: Optional[int] = None,
                level: Optional[int] = None, sub: Optional[str] = None):
    """``jax.named_scope`` bearing the contract name (nullcontext when
    annotations are disabled). Pure metadata — usable inside jit,
    shard_map and ``lax.cond`` branches. ``sub`` (one of
    ``SUB_SCOPES[phase]``) names the step of the algorithm inside the
    phase: ``anat/b000/select/threshold``."""
    if not _ENABLED:
        return nullcontext()
    import jax
    name = scope_name(phase, bucket, level)
    if sub is not None:
        if sub not in SUB_SCOPES.get(phase, ()):
            raise ValueError(f"{sub!r} is no sub-scope of phase {phase!r}")
        name = f"{name}/{sub}"
    return jax.named_scope(name)


def _contract_frames(parts: List[str]) -> Optional[
        Tuple[Optional[str], Optional[int], Optional[int], Optional[str]]]:
    """``(phase, bucket, level, sub)`` of the ``anat`` frames among the
    parts of a name; None when it holds no ``anat`` part. Nested frames
    merge, the innermost of each kind wins; ``sub`` is the innermost name
    of ``SUB_SCOPES[phase]`` right after a frame of the final phase."""
    phase: Optional[str] = None
    bucket: Optional[int] = None
    level: Optional[int] = None
    sub: Optional[str] = None
    seen = False
    for i, part in enumerate(parts):
        if part != SCOPE_PREFIX:
            continue
        seen = True
        j = i + 1
        if j < len(parts):
            m = _BUCKET_RE.match(parts[j])
            if m:
                bucket = int(m.group(1))
                j += 1
        if j < len(parts):
            m = _LEVEL_RE.match(parts[j])
            if m:
                level = int(m.group(1))
                j += 1
        if j < len(parts) and parts[j] in PHASES:
            if parts[j] != phase:
                sub = None
            phase = parts[j]
            if j + 1 < len(parts) and parts[j + 1] in SUB_SCOPES.get(
                    phase, ()):
                sub = parts[j + 1]
    return (phase, bucket, level, sub) if seen else None


def parse_scope_level(
        name: Any) -> Optional[Tuple[Optional[str], Optional[int],
                                     Optional[int]]]:
    """Extract ``(phase, bucket, level)`` from any name carrying the
    contract — a bare annotation (``anat/b000/select``,
    ``anat/b000/lvl1/exchange``) or a compiled-HLO op path
    (``jit(step)/.../anat/b000/anat/select/add``). Nested scopes merge:
    bucket, level and phase may come from different ``anat`` components.
    Returns None when the name carries no contract component; ``level``
    is None for legacy (single-level) names."""
    if not isinstance(name, str) or SCOPE_PREFIX not in name:
        return None
    parsed = _contract_frames(name.split("/"))
    return None if parsed is None else parsed[:3]


def parse_scope(name: Any) -> Optional[Tuple[Optional[str], Optional[int]]]:
    """Legacy ``(phase, bucket)`` view of :func:`parse_scope_level` —
    level-lane components are transparent, so names with and without a
    ``lvlN`` component round-trip identically."""
    parsed = parse_scope_level(name)
    return None if parsed is None else parsed[:2]


def lane_of(phase: Optional[str], name: str = "") -> str:
    """compute vs collective lane for one contract-scoped event."""
    if phase in COLLECTIVE_PHASES or _COLLECTIVE_OPS.search(name or ""):
        return "collective"
    return "compute"


# ---------------------------------------------------------------------------
# trace loading


def find_trace_file(path: str) -> Optional[str]:
    """Resolve ``path`` to one trace-event JSON file. A file path is
    used as-is; a profiler logdir is searched for the newest capture
    (``plugins/profile/<ts>/*trace.json[.gz]`` is where
    ``jax.profiler.start_trace`` puts perfetto output)."""
    if os.path.isfile(path):
        return path
    if not os.path.isdir(path):
        return None
    patterns = ("**/perfetto_trace.json.gz", "**/*.trace.json.gz",
                "**/*.trace.json", "**/*.json")
    candidates: List[str] = []
    for pat in patterns:
        candidates = glob.glob(os.path.join(path, pat), recursive=True)
        if candidates:
            break
    if not candidates:
        return None
    return max(candidates, key=os.path.getmtime)


def load_trace_events(path: str) -> Tuple[List[Dict[str, Any]],
                                          Optional[str], Optional[str]]:
    """``(events, resolved_path, problem)``. Never raises: an
    unreadable/malformed trace returns ``([], path, reason)``. Accepts
    ``{"traceEvents": [...]}`` docs and bare event lists, gzipped or
    plain."""
    resolved = find_trace_file(path)
    if resolved is None:
        return [], None, f"no trace file under {path!r}"
    try:
        opener = gzip.open if resolved.endswith(".gz") else open
        with opener(resolved, "rt") as f:
            doc = json.load(f)
    except Exception as e:
        return [], resolved, f"unreadable trace: {e!r}"
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
    elif isinstance(doc, list):
        events = doc
    else:
        events = None
    if not isinstance(events, list):
        return [], resolved, "trace carries no traceEvents list"
    return [e for e in events if isinstance(e, dict)], resolved, None


# ---------------------------------------------------------------------------
# analysis


def _merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _union_ms(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in _merged(intervals))


def _intersection_ms(a: List[Tuple[float, float]],
                     b: List[Tuple[float, float]]) -> float:
    am, bm = _merged(a), _merged(b)
    i = j = 0
    total = 0.0
    while i < len(am) and j < len(bm):
        lo = max(am[i][0], bm[j][0])
        hi = min(am[i][1], bm[j][1])
        if hi > lo:
            total += hi - lo
        if am[i][1] <= bm[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class _Span:
    """One stretch of time of one phase: a contract-scoped event of a
    Chrome trace, or the part of a device event that is its own. Times
    in milliseconds. ``count`` is 0 on the later pieces of an event that
    its children cut up."""
    start: float
    end: float
    phase: Optional[str]
    bucket: Optional[int]
    lane: str
    level: Optional[int] = None
    count: int = 1


def _critical_path(spans: List[_Span]) -> Dict[str, float]:
    """Sweep the elementary intervals between the spans' ends; each
    instant is split equally among the phases active then, and an instant
    that no span covers lands on ``idle``."""
    edges: List[Tuple[float, int, str]] = []
    for sp in spans:
        if sp.end > sp.start:
            ph = sp.phase or "other"
            edges.append((sp.start, 1, ph))
            edges.append((sp.end, -1, ph))
    edges.sort(key=lambda e: e[0])
    critical: Dict[str, float] = {}
    active: Dict[str, int] = {}
    total = 0
    i, n = 0, len(edges)
    while i < n:
        t = edges[i][0]
        while i < n and edges[i][0] == t:
            _, d, ph = edges[i]
            active[ph] = active.get(ph, 0) + d
            total += d
            i += 1
        if i == n:
            break
        width = edges[i][0] - t
        if total == 0:
            critical["idle"] = critical.get("idle", 0.0) + width
            continue
        for ph, k in active.items():
            if k:
                critical[ph] = critical.get(ph, 0.0) + width * k / total
    return critical


def _scorecard(spans: List[_Span]) -> Dict[str, Any]:
    """The core both front ends feed: per-(bucket, phase) totals, the
    compute and collective lanes' unions, their overlap, the measured
    span against the fully-overlapped bound, the critical path."""
    t0 = min(sp.start for sp in spans)
    # per-(bucket, phase) totals; phase-less spans (a bare "anat/b000"
    # container, a device instruction nobody owns) attribute to "other".
    # Level-tagged spans (hierarchical collectives) get their own lane
    # key ("lvl1/exchange") so the two levels of one phase never merge.
    per: Dict[Tuple[int, str], Dict[str, Any]] = {}
    compute_iv: List[Tuple[float, float]] = []
    comm_iv: List[Tuple[float, float]] = []
    for sp in spans:
        pkey = sp.phase or "other"
        if sp.level is not None:
            pkey = f"lvl{int(sp.level)}/{pkey}"
        key = (-1 if sp.bucket is None else int(sp.bucket), pkey)
        d = per.setdefault(key, {"ms": 0.0, "count": 0, "lane": sp.lane})
        if sp.level is not None:
            d["level"] = int(sp.level)
        d["ms"] += sp.end - sp.start
        d["count"] += sp.count
        if sp.lane == "collective":
            d["lane"] = "collective"
            comm_iv.append((sp.start, sp.end))
        else:
            compute_iv.append((sp.start, sp.end))

    compute_ms = _union_ms(compute_iv)
    comm_ms = _union_ms(comm_iv)
    overlap_ms = _intersection_ms(compute_iv, comm_iv)
    step_ms = max(sp.end for sp in spans) - t0
    ideal_ms = max(compute_ms, comm_ms)

    # the dominant entry of the critical path is what a latency
    # optimisation must attack first (idle: host dispatch between
    # probes, tails)
    critical = _critical_path(spans)
    ranked = sorted(((ph, ms) for ph, ms in critical.items()
                     if ph != "idle"), key=lambda kv: -kv[1])
    critical_phase = ranked[0][0] if ranked else None

    buckets: Dict[int, Dict[str, Dict[str, Any]]] = {}
    for (bucket, phase), d in sorted(per.items()):
        entry = {"ms": round(d["ms"], 4), "count": d["count"],
                 "lane": d["lane"]}
        if "level" in d:
            entry["level"] = d["level"]
        buckets.setdefault(bucket, {})[phase] = entry
    return {
        "buckets": buckets,
        "compute_ms": round(compute_ms, 4),
        "comm_ms": round(comm_ms, 4),
        "overlap_ms": round(overlap_ms, 4),
        "overlap_ratio": round(overlap_ms / comm_ms, 6) if comm_ms > 0
        else 0.0,
        "step_ms": round(step_ms, 4),
        "ideal_ms": round(ideal_ms, 4),
        "serialization_ms": round(max(0.0, step_ms - ideal_ms), 4),
        "critical_path": {ph: round(ms, 4)
                          for ph, ms in sorted(critical.items())},
        "critical_phase": critical_phase,
        "events": sum(sp.count for sp in spans),
    }


def analyze_events(events: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """Attribute contract-scoped trace events into the step anatomy: the
    Chrome trace-event front end (an event is named by its scope).

    Returns None when no contract event is present (the caller
    journals an ``anatomy_warning``). Times in the trace are
    microseconds (trace-event convention); everything returned is
    milliseconds."""
    spans: List[_Span] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        parsed = parse_scope_level(e.get("name"))
        if parsed is None:
            continue
        ts, dur = e.get("ts"), e.get("dur")
        if not isinstance(ts, (int, float)) or not isinstance(
                dur, (int, float)) or dur < 0:
            continue
        phase, bucket, level = parsed
        spans.append(_Span(float(ts) / 1e3, (float(ts) + float(dur)) / 1e3,
                           phase, bucket,
                           lane_of(phase, str(e.get("name"))), level))
    return _scorecard(spans) if spans else None


def phase_totals(analysis: Dict[str, Any]) -> Dict[str, float]:
    """Per-phase-family total ms summed across buckets — the shape
    ``RegressionDetector.observe_phases`` checks limits against."""
    totals: Dict[str, float] = {}
    for phases in analysis.get("buckets", {}).values():
        for ph, d in phases.items():
            # level-tagged keys ("lvl1/exchange") fold into their phase
            # family so regression limits keyed by phase keep applying
            if _LEVEL_RE.match(ph.split("/", 1)[0]):
                ph = ph.split("/", 1)[1] if "/" in ph else "other"
            totals[ph] = round(totals.get(ph, 0.0) + float(d["ms"]), 4)
    return totals


def emit_anatomy(bus, analysis: Optional[Dict[str, Any]], step: int = 0,
                 source: str = "trace",
                 warn_reason: Optional[str] = None,
                 warn_path: Optional[str] = None) -> None:
    """Journal one capture: ``step_anatomy`` per bucket + one
    ``overlap_report`` — or a single ``anatomy_warning`` when there is
    nothing to attribute. ``bus`` may be an EventBus or a RunJournal
    (anything with ``emit``/``record``)."""
    if bus is None:
        return
    put = getattr(bus, "emit", None) or getattr(bus, "record")
    if analysis is None:
        put("anatomy_warning", step=int(step),
            reason=str(warn_reason or "empty or malformed trace"),
            path=warn_path, source=source)
        return
    for bucket, phases in sorted(analysis["buckets"].items()):
        levels = sorted({d["level"] for d in phases.values()
                         if "level" in d})
        extra = {"levels": levels} if levels else {}
        put("step_anatomy", step=int(step), bucket=int(bucket),
            phases=phases,
            total_ms=round(sum(d["ms"] for d in phases.values()), 4),
            source=source, **extra)
    put("overlap_report", step=int(step),
        compute_ms=analysis["compute_ms"], comm_ms=analysis["comm_ms"],
        overlap_ms=analysis["overlap_ms"],
        overlap_ratio=analysis["overlap_ratio"],
        step_ms=analysis["step_ms"], ideal_ms=analysis["ideal_ms"],
        serialization_ms=analysis["serialization_ms"],
        critical_path=analysis["critical_path"],
        critical_phase=analysis["critical_phase"],
        num_buckets=len(analysis["buckets"]),
        events=analysis["events"], source=source)


def analyze_capture(path: str, bus=None, step: int = 0,
                    source: str = "trace") -> Optional[Dict[str, Any]]:
    """Load + analyze + journal one captured trace. Never raises; a
    missing/malformed/contract-free trace journals an
    ``anatomy_warning`` and returns None."""
    try:
        events, resolved, problem = load_trace_events(path)
        analysis = analyze_events(events) if events else None
        if analysis is None and problem is None:
            problem = "no anatomy-scoped events in trace"
        emit_anatomy(bus, analysis, step=step, source=source,
                     warn_reason=problem, warn_path=resolved or path)
        return analysis
    except Exception as e:   # pragma: no cover - belt and braces
        emit_anatomy(bus, None, step=step, source=source,
                     warn_reason=f"analysis failed: {e!r}", warn_path=path)
        return None


# ---------------------------------------------------------------------------
# whose an instruction of the compiled step is

# the owner rules, in the order they are tried
HOWS = ("own", "pair", "operand", "user", "body", "none")
_SEARCH_LEVELS = 12     # operands up / users down, breadth first
_CALLER_LEVELS = 6      # a body's caller, and that one's caller
_REPO_DIR = "/oktopk_tpu/"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][\w\-]*)\(")
_REF = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(
    r"\b(?:body|condition|calls|to_apply|true_computation"
    r"|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_FRAME_ID = re.compile(r"\bstack_frame_id=(\d+)")
_PAYLOAD = re.compile(r'"[^"]{400,}"')
_PATH_SEP = re.compile(r"[/()]")
_TABLE_ROW = re.compile(r"^(\d+)\s+(.*)$")
_FIELD = re.compile(r"(\w+)=(\d+)")


@dataclasses.dataclass(frozen=True)
class Owner:
    """Whose an instruction of the compiled step is. ``how`` names the
    rule that answered (``HOWS``); ``frame`` is ``file:line function`` of
    the innermost stack frame inside ``oktopk_tpu/`` (the instruction's
    own where it carries one, else that of the instruction it inherits
    from), None where the text has no stack-frame tables."""
    phase: Optional[str]
    sub: Optional[str]
    bucket: Optional[int]
    how: str
    frame: Optional[str]


@dataclasses.dataclass
class _Inst:
    opcode: str
    operands: Tuple[str, ...]
    op_name: str
    frame_id: Optional[int]
    computation: Optional[str]


def parse_op_path(op_name: str):
    """``(phase, bucket, level, sub)`` of an instruction's ``op_name``, or
    None: :func:`parse_scope_level`, but brackets split the path too
    (``transpose(jvp(anat/fwd_bwd/experts))``) and the sub-scope comes
    with it."""
    if SCOPE_PREFIX not in op_name:
        return None
    return _contract_frames([p for p in _PATH_SEP.split(op_name) if p])


def _operands_of(rest: str, start: int) -> Tuple[str, ...]:
    """The ``%names`` between the bracket at ``rest[start]`` and the one
    that closes it."""
    depth = 0
    for i in range(start, len(rest)):
        c = rest[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return tuple(_REF.findall(rest, start, i))
    return tuple(_REF.findall(rest, start))


def _hlo_table(lines: List[str], title: str) -> Dict[int, str]:
    try:
        i = lines.index(title)
    except ValueError:
        return {}
    out: Dict[int, str] = {}
    for line in lines[i + 1:]:
        m = _TABLE_ROW.match(line)
        if not m:
            break
        out[int(m.group(1))] = m.group(2)
    return out


def _frame_names(lines: List[str]) -> Dict[int, Optional[str]]:
    """stack_frame_id -> ``file:line function`` of the innermost frame
    inside the repo, from the text's four tables. A row's
    ``parent_frame_id`` is printed one above the parent's id; 1 is no
    parent."""
    files = {k: v.strip('"') for k, v in _hlo_table(
        lines, "FileNames").items()}
    funcs = {k: v.strip('"') for k, v in _hlo_table(
        lines, "FunctionNames").items()}
    locs = {k: {f: int(n) for f, n in _FIELD.findall(v)}
            for k, v in _hlo_table(lines, "FileLocations").items()}
    frames = {k: {f: int(n) for f, n in _FIELD.findall(v)}
              for k, v in _hlo_table(lines, "StackFrames").items()}
    out: Dict[int, Optional[str]] = {}
    for fid in frames:
        cur, seen, found = fid, set(), None
        while cur in frames and cur not in seen:
            seen.add(cur)
            loc = locs.get(frames[cur].get("file_location_id"), {})
            path = files.get(loc.get("file_name_id"), "")
            if _REPO_DIR in path:
                found = (f"{path.split(_REPO_DIR, 1)[1]}:"
                         f"{loc.get('line', 0)} "
                         f"{funcs.get(loc.get('function_name_id'), '?')}")
                break
            cur = frames[cur].get("parent_frame_id", 1) - 1
        out[fid] = found
    return out


def _parse_hlo(hlo_text: str):
    """One sweep of the lines: the instructions by name, and who calls
    each computation (the first that does, in the order written)."""
    lines = hlo_text.split("\n")
    insts: Dict[str, _Inst] = {}
    caller_of: Dict[str, str] = {}
    computation: Optional[str] = None
    for line in lines:
        if not line:
            continue
        if line[0] != " ":
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group(1)
            continue
        if len(line) >= 1500:
            # a Mosaic call's line carries its whole kernel as bytes
            line = _PAYLOAD.sub('"..."', line)
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        if not op:
            continue
        operands = _operands_of(rest, op.end() - 1)
        op_name = _OP_NAME.search(rest)
        frame_id = _FRAME_ID.search(rest)
        insts[name] = _Inst(op.group(1), operands,
                            op_name.group(1) if op_name else "",
                            int(frame_id.group(1)) if frame_id else None,
                            computation)
        called = _CALLED.findall(rest)
        for group in _BRANCHES.findall(rest):
            called += _REF.findall(group)
        for c in called:
            caller_of.setdefault(c, name)
    return lines, insts, caller_of


def owners(hlo_text: str) -> Dict[str, Owner]:
    """instruction name -> :class:`Owner`, for every instruction of a
    compiled step's text (``step_fn.lower(...).compile().as_text()``).

    A device event of a TPU trace is named by its instruction and carries
    no scope; the ``anat/...`` path is in the instruction's ``op_name``.
    What the compiler made or renamed (asynchronous copies and slices,
    layout changes, kernels of its own) has no ``op_name`` of the
    program's, and is given the owner of its surroundings. The rules, in
    this order; ``Owner.how`` says which answered:

    1. ``own``: the instruction's ``op_name`` holds an ``anat/`` phase;
    2. ``pair``: a ``*-done`` takes its ``*-start``'s owner;
    3. ``operand``: the nearest instruction up its operands (breadth
       first, operands in the order written, ``_SEARCH_LEVELS`` deep)
       whose own ``op_name`` holds a phase;
    4. ``user``: else the nearest down its users, the same search;
    5. ``body``: else what rules 1-4 answer for the instruction that
       calls the computation it lies in (a loop, a branch, a fusion, a
       call), and for that one's caller, ``_CALLER_LEVELS`` up;
    6. ``none``.

    A pure function of the text: one sweep of its lines and two adjacency
    maps; nothing is compiled or run."""
    lines, insts, caller_of = _parse_hlo(hlo_text)
    frames = _frame_names(lines)
    parsed = {name: parse_op_path(i.op_name) if i.op_name else None
              for name, i in insts.items()}
    own = {name for name, p in parsed.items() if p and p[0]}
    users: Dict[str, List[str]] = {}
    for name, i in insts.items():
        for o in i.operands:
            users.setdefault(o, []).append(name)

    def nearest(name: str, neighbours) -> Optional[str]:
        seen, frontier = {name}, [name]
        for _ in range(_SEARCH_LEVELS):
            nxt: List[str] = []
            for n in frontier:
                for o in neighbours(n):
                    if o in seen:
                        continue
                    if o in own:
                        return o
                    seen.add(o)
                    nxt.append(o)
            if not nxt:
                return None
            frontier = nxt
        return None

    def up(n: str):
        i = insts.get(n)
        return i.operands if i else ()

    def down(n: str):
        return users.get(n, ())

    local: Dict[str, Tuple[Optional[str], str]] = {}

    def nearby(name: str) -> Tuple[Optional[str], str]:
        """Rules 1-4: the instruction whose ``op_name`` answers for
        ``name`` inside its own computation, and the rule."""
        if name in local:
            return local[name]
        got: Tuple[Optional[str], str] = (None, "none")
        inst = insts[name]
        if name in own:
            got = (name, "own")
        elif (inst.opcode.endswith("-done") and inst.operands
                and inst.operands[0] in insts
                and insts[inst.operands[0]].opcode.endswith("-start")):
            got = (nearby(inst.operands[0])[0], "pair")
        for how, neighbours in (("operand", up), ("user", down)):
            if got[0] is None:
                got = (nearest(name, neighbours), how)
        if got[0] is None:
            got = (None, "none")
        local[name] = got
        return got

    def resolve(name: str) -> Tuple[Optional[str], str]:
        src, how = nearby(name)
        n = name
        for _ in range(_CALLER_LEVELS):
            if src is not None:
                return src, how
            n = caller_of.get(insts[n].computation)
            if n not in insts:
                break
            src, how = nearby(n)[0], "body"
        return (src, how) if src is not None else (None, "none")

    out: Dict[str, Owner] = {}
    for name, inst in insts.items():
        src, how = resolve(name)
        phase, bucket, _level, sub = parsed[src] if src else (None,) * 4
        frame = frames.get(inst.frame_id)
        if frame is None and src is not None:
            frame = frames.get(insts[src].frame_id)
        out[name] = Owner(phase, sub, bucket, how, frame)
    return out


# ---------------------------------------------------------------------------
# the device front end: instruction-named events and the owners' map

_NOBODY = Owner(None, None, None, "none", None)
_KINDS = ("own_ms", "inherited_ms", "control_ms")
_NEST_EPS = 1e-12
_DEVICE_PLANE = "/device:TPU:"
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"


def _owner_key(who: Owner) -> Optional[str]:
    """``fwd_bwd/attention``, ``optimizer``: a row of the owners' table."""
    return who.phase if who.sub is None else f"{who.phase}/{who.sub}"


def _self_time(events: List[Tuple[float, float, str]]):
    """Give each instant to the innermost event that covers it. ``events``
    sorted by (start, -end). Returns the pieces ``(lo, hi, index)`` in
    time order, and which events are containers (they span another event
    of the line: a loop, a branch, a call)."""
    pieces: List[Tuple[float, float, int]] = []
    container = [False] * len(events)
    stack: List[int] = []
    cursor = events[0][0]

    def close(i: int):
        nonlocal cursor
        end = events[i][1]
        if end > cursor:
            pieces.append((cursor, end, i))
            cursor = end

    for i, (start, end, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= start:
            close(stack.pop())
        if stack:
            if start > cursor:
                pieces.append((cursor, start, stack[-1]))
            if end <= events[stack[-1]][1] + _NEST_EPS:
                container[stack[-1]] = True
        cursor = max(cursor, start)
        stack.append(i)
    while stack:
        close(stack.pop())
    return pieces, container


def analyze_device(ops, owner_map: Dict[str, Owner], steps: int = 1,
                   top: int = 10) -> Optional[Dict[str, Any]]:
    """Attribute the device events of one chip's line of operations to
    their owners: the device front end (an event is named by its
    instruction; :func:`owners` says whose that is).

    ``ops`` are ``(instruction name, start, end)`` in seconds, leaves and
    the loops, branches and calls that span them alike. Every instant of
    the union busy time goes to the innermost event that covers it, so
    the table closes: over all ``(phase, sub)`` the ``own_ms`` (the
    instruction's own ``op_name`` names the phase), ``inherited_ms`` (it
    got its owner by rules 2-5) and ``control_ms`` (the time a loop or a
    branch covers and none of its leaves does, given to the container's
    owner), plus ``unowned_ms``, are ``busy_ms``. Everything returned is
    milliseconds a step (the window's total over ``steps``), on top of
    what :func:`analyze_events` returns, from the same core. None when
    ``ops`` holds no event."""
    events = sorted(((float(s), float(e), str(n)) for n, s, e in ops
                     if e > s), key=lambda ev: (ev[0], -ev[1]))
    if not events:
        return None
    pieces, container = _self_time(events)
    scale = 1e3 / max(1, int(steps))
    table: Dict[str, Dict[str, float]] = {}
    split: Dict[Tuple[int, str], Dict[str, float]] = {}
    by_name: Dict[str, Dict[str, float]] = {"inherited": {}, "unowned": {}}
    spans: List[_Span] = []
    unowned = busy = 0.0
    counted = set()
    for lo, hi, i in pieces:
        name = events[i][2]
        who = owner_map.get(name, _NOBODY)
        ms = (hi - lo) * scale
        busy += ms
        if who.how == "none":
            kind = "unowned"
            unowned += ms
        else:
            kind = ("control" if container[i] else
                    "own" if who.how == "own" else "inherited")
            for rows, key in ((table, _owner_key(who)), (split, (
                    -1 if who.bucket is None else who.bucket, who.phase))):
                row = rows.setdefault(key, dict.fromkeys(_KINDS, 0.0))
                row[kind + "_ms"] += ms
        if kind in by_name:
            by_name[kind][name] = by_name[kind].get(name, 0.0) + ms
        lane = lane_of(who.phase, name)
        first = i not in counted
        counted.add(i)
        last = spans[-1] if spans else None
        if (last is not None and last.end >= lo * scale - _NEST_EPS
                and (last.phase, last.bucket, last.lane)
                == (who.phase, who.bucket, lane)):
            last.end = hi * scale
            last.count += first
        else:
            spans.append(_Span(lo * scale, hi * scale, who.phase,
                               who.bucket, lane, count=int(first)))

    out = _scorecard(spans)
    for (bucket, phase), cell in split.items():
        out["buckets"][bucket][phase].update(cell)

    def largest(kind: str):
        ranked = sorted(by_name[kind].items(), key=lambda r: (-r[1], r[0]))
        return [{"name": n, "ms": ms, "how": who.how,
                 "owner": _owner_key(who), "frame": who.frame}
                for n, ms in ranked[:top]
                for who in (owner_map.get(n, _NOBODY),)]

    out.update(
        owners=dict(sorted(table.items())),
        unowned_ms=unowned, busy_ms=busy,
        largest_inherited=largest("inherited"),
        largest_unowned=largest("unowned"), steps=int(steps))
    return out


def find_xplane_file(path: str) -> Optional[str]:
    """A ``.xplane.pb`` file itself, or the newest one under a profiler
    logdir (``plugins/profile/<time>/<host>.xplane.pb``)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _load_profile(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def _device_ops(profile) -> List[List[Tuple[str, float, float]]]:
    """For each ``/device:TPU:<i>`` plane, in the planes' order, the
    events of its line of operations as ``(instruction name, start,
    end)`` in seconds. An event is named by its instruction's whole text
    (``%fusion.4 = bf16[...] fusion(...)``). Where the plane has a line
    of programs, only the operations inside executions of the program
    that took most of the capture are kept: the train step."""
    chips = []
    for plane in profile.planes:
        if not plane.name.startswith(_DEVICE_PLANE):
            continue
        ops: List[Tuple[str, float, float]] = []
        modules: Dict[str, List[Tuple[float, float]]] = {}
        for line in plane.lines:
            if line.name not in (_OPS_LINE, _MODULES_LINE):
                continue
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if line.name == _MODULES_LINE:
                    modules.setdefault(ev.name, []).append((start, end))
                    continue
                m = _INSTRUCTION.match(ev.name)
                ops.append((m.group(1) if m else ev.name, start, end))
        if modules:
            runs = _merged(max(modules.values(), key=_union_ms))
            starts = [s for s, _ in runs]
            kept = []
            for op in ops:
                k = bisect.bisect_right(starts, op[1]) - 1
                if k >= 0 and op[1] < runs[k][1]:
                    kept.append(op)
            ops = kept
        if ops:
            chips.append(ops)
    return chips


def analyze_xplane(path: str, hlo_text, steps: int = 1
                   ) -> Optional[Dict[str, Any]]:
    """A ``jax.profiler`` capture (an ``.xplane.pb`` or the logdir that
    holds one) and the compiled step's text -> :func:`analyze_device` of
    the first chip, with ``chips`` (how many device planes the capture
    holds) beside it. ``hlo_text`` may be a function that gives the text
    (``Trainer.step_hlo``): it is called only once the capture is known
    to hold a device plane. None where it holds none (a CPU run: only
    host threads appear). Raises what reading the file or making the
    text raises: the caller decides whether that may stop it."""
    resolved = find_xplane_file(path)
    if resolved is None:
        return None
    chips = _device_ops(_load_profile(resolved))
    if not chips:
        return None
    if callable(hlo_text):
        hlo_text = hlo_text()
    out = analyze_device(chips[0], owners(hlo_text), steps=steps)
    if out is not None:
        out["chips"] = len(chips)
    return out
