"""Step-anatomy plane: phase annotation contract + device-trace attribution.

The jitted train step is one opaque XLA program; the reference's
per-phase timers (VGG/allreducer.py:256-262) have no analogue inside
it. This module gives the step a time-domain anatomy in three pieces:

1. **Naming contract** — ``scope_name(phase, bucket)`` produces names
   like ``anat/b003/exchange``. ``phase_scope(...)`` wraps pipeline
   regions in ``jax.named_scope`` so the names reach compiled-HLO op
   metadata (``op_name="jit(step)/.../anat/b000/select/..."``) and
   therefore the device lanes of a ``jax.profiler`` capture on
   backends that attribute per-op device time (TPU). The scopes are
   pure metadata: computation is bit-identical annotations-on vs
   annotations-off and no host callback is ever introduced
   (tests/test_anatomy.py pins both). ``trace_annotation(...)`` is the
   host-side twin (``jax.profiler.TraceAnnotation``) used by capture
   drivers on backends whose traces carry no per-op device lanes
   (CPU: only host threads appear, so the driver dispatches per-phase
   subprograms under annotations instead).

2. **Trace analyzer** — parses captured profiler output (the perfetto
   trace-event JSON ``jax.profiler.start_trace(...,
   create_perfetto_trace=True)`` writes, or any Chrome trace-event
   file, plus checked-in synthetic fixtures in CI) into per-(bucket,
   phase) durations, classifies events into
   compute vs collective lanes, computes the compute/comm overlap
   ratio and a time-sweep critical-path attribution of the measured
   span.

3. **Journal events** — ``step_anatomy`` (one per bucket; model-level
   unbucketed phases land on bucket -1) and one ``overlap_report``
   carrying the scorecard: measured span vs the ideal fully-overlapped
   lower bound ``max(compute_ms, comm_ms)``. Malformed or empty traces
   journal one ``anatomy_warning`` — analysis never raises
   (observability must never take down the thing it observes).

Scorecard semantics (docs/OBSERVABILITY.md "Step anatomy"):
``overlap_ratio = overlap_ms / comm_ms`` — the fraction of collective
time hidden under compute. A fully serial step scores 0.0; the
ROADMAP's bucket-pipelined overlap item is judged by how far it moves
this number toward 1.0 while ``step_ms`` approaches ``ideal_ms``.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from contextlib import contextmanager, nullcontext
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
# (models/deepseek_v2.py, models/qwen3_next.py, models/smallthinker.py), so
# forward, recomputed and backward operations alike carry them: a model that
# enters none leaves the phase unscoped. ``delta_rule`` (the chunked
# recurrence alone) lies inside ``linear_attention`` (its projections,
# convolution, gates and norm), and ``window_scores`` (a windowed layer's
# blocked scores, softmax and weighted sum alone) inside ``window_attention``
# (its projections, rotary and output projection): a reader takes the
# innermost.
SUB_SCOPES = {
    "select": (SUB_THRESHOLD, SUB_SWEEP, SUB_GLOBAL, SUB_FEEDBACK),
    "stage": (SUB_REPARTITION, SUB_FINALIZE),
    "fwd_bwd": ("attention", "router", "experts", "shared", "mlp", "head",
                "linear_attention", "delta_rule", "window_attention",
                "window_scores"),
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


@contextmanager
def trace_annotation(phase: Optional[str] = None,
                     bucket: Optional[int] = None):
    """Host-side ``jax.profiler.TraceAnnotation`` with the contract
    name — the capture-driver twin of :func:`phase_scope` for backends
    whose device traces carry no per-op lanes. Degrades to a no-op if
    the profiler annotation cannot start."""
    name = scope_name(phase, bucket)
    try:
        import jax
        cm = jax.profiler.TraceAnnotation(name)
    except Exception:
        cm = nullcontext()
    with cm:
        yield


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
    parts = name.split("/")
    phase: Optional[str] = None
    bucket: Optional[int] = None
    level: Optional[int] = None
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
            phase = parts[j]
    return (phase, bucket, level) if seen else None


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


def analyze_events(events: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """Attribute contract-scoped trace events into the step anatomy.

    Returns None when no contract event is present (the caller
    journals an ``anatomy_warning``). Times in the trace are
    microseconds (trace-event convention); everything returned is
    milliseconds."""
    spans: List[Tuple[float, float, Optional[str], Optional[int], str,
                      Optional[int]]] = []
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
        start, end = float(ts) / 1e3, (float(ts) + float(dur)) / 1e3
        spans.append((start, end, phase, bucket,
                      lane_of(phase, str(e.get("name"))), level))
    if not spans:
        return None

    t0 = min(s for s, *_ in spans)
    # per-(bucket, phase) totals; phase-less contract events (a bare
    # "anat/b000" container) attribute to phase "other". Level-tagged
    # spans (hierarchical collectives) get their own lane key
    # ("lvl1/exchange") so the two levels of one phase never merge;
    # legacy keys are unchanged.
    per: Dict[Tuple[int, str], Dict[str, Any]] = {}
    compute_iv: List[Tuple[float, float]] = []
    comm_iv: List[Tuple[float, float]] = []
    for start, end, phase, bucket, lane, level in spans:
        pkey = phase or "other"
        if level is not None:
            pkey = f"lvl{int(level)}/{pkey}"
        key = (-1 if bucket is None else int(bucket), pkey)
        d = per.setdefault(key, {"ms": 0.0, "count": 0, "lane": lane})
        if level is not None:
            d["level"] = int(level)
        d["ms"] += end - start
        d["count"] += 1
        if lane == "collective":
            d["lane"] = "collective"
            comm_iv.append((start, end))
        else:
            compute_iv.append((start, end))

    compute_ms = _union_ms(compute_iv)
    comm_ms = _union_ms(comm_iv)
    overlap_ms = _intersection_ms(compute_iv, comm_iv)
    step_ms = max(e for _, e, *_ in spans) - t0
    ideal_ms = max(compute_ms, comm_ms)

    # critical-path attribution: sweep the span's elementary intervals;
    # each instant's duration is split equally among the phases active
    # then (idle gaps — host dispatch between probes, tails — land on
    # "idle"). The dominant entry is what a latency optimisation must
    # attack first.
    bounds = sorted({b for s, e, *_ in spans for b in (s, e)})
    critical: Dict[str, float] = {}
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi <= lo:
            continue
        active = [ph or "other" for s, e, ph, _b, _l, _lv in spans
                  if s <= lo and e >= hi]
        if not active:
            critical["idle"] = critical.get("idle", 0.0) + (hi - lo)
            continue
        share = (hi - lo) / len(active)
        for ph in active:
            critical[ph] = critical.get(ph, 0.0) + share
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
        "events": len(spans),
    }


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
# capture driver


def capture_pipeline_anatomy(cfg, mesh, logdir: str, num_buckets: int = 4,
                             iters: int = 3, axis_name: str = "data",
                             bus=None, step: int = 0,
                             fwd_bwd_elems: int = 1 << 16):
    """Capture + attribute one step anatomy on the given mesh.

    On backends whose device traces carry no per-op lanes (CPU), the
    in-jit named scopes never reach the trace, so this driver measures
    the anatomy by dispatching separately-jitted per-phase subprograms
    (the profile_step.py decomposition) under host
    ``TraceAnnotation``s — same shapes and caps as the configured
    pipeline, one annotation span per (bucket, phase) per iteration.
    Dispatch is serial by construction, so the resulting
    ``overlap_ratio`` is the honest floor of today's un-pipelined step;
    an in-jit device capture on TPU flows through the same analyzer and
    credits real overlap.

    Returns the analysis dict (journalled on ``bus`` when given), or
    None when the profiler cannot capture — the caller records
    ``anatomy_unavailable``/``anatomy_warning`` instead of dying."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from oktopk_tpu.comm import all_gather, all_to_all, compat
    from oktopk_tpu.ops import pack_by_region, scatter_sparse, \
        select_by_threshold
    from oktopk_tpu.ops.topk import k2threshold_method
    from jax.sharding import PartitionSpec as P_

    P = int(cfg.num_workers)
    nb = max(1, int(num_buckets))
    sizes = [cfg.n // nb] * nb
    sizes[-1] += cfg.n - sum(sizes)
    rng = np.random.RandomState(0)

    def sync(x):
        jax.tree.map(lambda a: np.asarray(a), x)

    probes = []   # (phase, bucket, fn) in dispatch order

    # model-level fwd/bwd stand-in: a matmul-chain gradient sized to be
    # visible next to the bucket probes (the real model's fwd/bwd is
    # profiled by profile_step.py's fwd_bwd_dense probe)
    d = max(32, int(np.sqrt(fwd_bwd_elems)) // 32 * 32)
    w = jax.device_put(jnp.asarray(rng.randn(d, d).astype(np.float32)))
    x0 = jax.device_put(jnp.asarray(rng.randn(8, d).astype(np.float32)))
    fwd_bwd = jax.jit(jax.grad(
        lambda wv: jnp.sum(jnp.tanh(x0 @ wv @ wv.T) ** 2)))
    sync(fwd_bwd(w))
    probes.append(("fwd_bwd", None, lambda: fwd_bwd(w)))

    for bi, n_b in enumerate(sizes):
        cfg_b = cfg.replace(n=n_b, bucket_index=bi)
        k_b, cap_p, cap_g = cfg_b.k, cfg_b.cap_pair, cfg_b.cap_gather
        g_b = jax.device_put(jnp.asarray(
            rng.randn(n_b).astype(np.float32)))
        bnd = jnp.asarray(
            [round(i * n_b / P) for i in range(P + 1)], jnp.int32)

        sel = jax.jit(lambda x, k=k_b, cap=cap_g, c=cfg_b:
                      select_by_threshold(
                          x, k2threshold_method(
                              jnp.abs(x), k, c.threshold_method,
                              c.bisect_iters).astype(x.dtype),
                          cap, use_pallas=False))
        sync(sel(g_b))
        t_b = jax.jit(lambda x, k=k_b, c=cfg_b: k2threshold_method(
            jnp.abs(x), k, c.threshold_method, c.bisect_iters))(g_b)

        stage = jax.jit(lambda x, t, b=bnd, cap=cap_p:
                        pack_by_region(x, jnp.abs(x) >= t, b, P, cap,
                                       thresh=t, use_pallas=False))
        sync(stage(g_b, t_b))
        s_vals, s_idx, _ = stage(g_b, t_b)

        def _exchange(sv, si, gv):
            # shard_map blocks keep the sharded axis at size 1 — drop it
            # so all_to_all sees split-axis size == mesh size, and re-add
            # it so out_specs can concatenate the per-shard results
            rv = all_to_all(sv[0], axis_name)
            ri = all_to_all(si[0], axis_name)
            gg = all_gather(gv[0], axis_name)
            return rv[None], ri[None], gg[None]

        exchange = jax.jit(compat.shard_map(
            _exchange, mesh=mesh,
            in_specs=(P_(axis_name), P_(axis_name), P_(axis_name)),
            out_specs=(P_(axis_name),) * 3, check_vma=False))
        sv8 = jnp.broadcast_to(s_vals, (P,) + s_vals.shape)
        si8 = jnp.broadcast_to(s_idx, (P,) + s_idx.shape)
        gv8 = jnp.asarray(rng.randn(P, cap_g).astype(np.float32))
        sync(exchange(sv8, si8, gv8))
        rv8, ri8, _ = exchange(sv8, si8, gv8)

        combine = jax.jit(
            lambda rv, ri, x, n_b=n_b:
            jnp.where(scatter_sparse(n_b, rv, ri) != 0.0, 0.0, x))
        sync(combine(rv8[0], ri8[0], g_b))

        probes.append(("select", bi, lambda g=g_b, f=sel: f(g)))
        probes.append(("stage", bi,
                       lambda g=g_b, t=t_b, f=stage: f(g, t)))
        probes.append(("exchange", bi,
                       lambda a=sv8, b=si8, c=gv8, f=exchange: f(a, b, c)))
        probes.append(("combine", bi,
                       lambda a=rv8[0], b=ri8[0], g=g_b, f=combine:
                       f(a, b, g)))

    # model-level optimizer: SGD-momentum update on the flat vector
    gm = jax.device_put(jnp.asarray(rng.randn(cfg.n).astype(np.float32)))
    pm = jnp.zeros_like(gm)
    opt = jax.jit(lambda p, m, g: (p - 0.1 * (0.9 * m + g), 0.9 * m + g))
    sync(opt(pm, pm, gm))
    probes.append(("optimizer", None, lambda: opt(pm, pm, gm)))

    os.makedirs(logdir, exist_ok=True)
    try:
        jax.profiler.start_trace(logdir, create_perfetto_trace=True)
    except Exception:
        return None
    try:
        for _ in range(max(1, int(iters))):
            for phase, bucket, fn in probes:
                with trace_annotation(phase, bucket):
                    sync(fn())
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            return None
    return analyze_capture(logdir, bus=bus, step=step, source="host_probe")
