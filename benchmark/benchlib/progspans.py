"""What the program says about its own steps, joined to the device trace.

Three records of the program (``oktopk_tpu/utils/profiling.py``):

- its host spans, ``oktopk/...`` ``TraceAnnotation``s, read from the same
  ``.xplane.pb`` that ``run.py`` wrote under ``.bench_out/trace/<cell>/``:
  they are on the clock of ``ctx.trace``'s device events and of the
  harness's ``bench/...`` spans. ``oktopk/step`` carries ``step_num``, the
  program's host step counter;
- its ``counters`` vector of every step (``profiling.snapshot()``), joined
  to the spans by ``step_num``;
- its compile listener's seconds by host step.

And the sub-scopes under ``anat/.../select`` and ``.../stage``, read from
the scope path of each device operation. Their names, like the order of a
``counters`` vector and the names of the branches, are the program's own,
from its snapshot: nothing of the program is copied here.

A program without these (the parent of the PR that added them) gives
``view(ctx) is None`` and every reader returns None: nothing raises.

This module and ``harness.py`` are the two that import the program.
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics
from typing import Dict, List, Optional

from benchlib import discover, intervals, xtrace

PREFIX = "oktopk/"
STEP, DISPATCH = "oktopk/step", "oktopk/dispatch"
Subs = Dict[str, List[str]]   # phase -> its sub-scopes' names


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    step: Optional[int] = None


@dataclasses.dataclass
class View:
    spans: List[Span]                   # oktopk/... inside the window
    steps: List[int]                    # step_num of each oktopk/step there
    window_steps: int                   # steps the harness drove there
    names: List[str]                    # order of a counters vector
    branches: List[str]                 # a branch entry's value -> name
    counters: Dict[int, List[int]]      # step_num -> vector
    compile_by_step: Dict[int, Dict[str, float]]
    gap_s: List[float]                  # idle between two step programs
    shares: Dict[str, float]            # idle seconds of chip 0 by who held
    # the host: an oktopk/ span, else a bench/ span, else none

    def median_ms(self, name: str) -> Optional[float]:
        d = [s.end - s.start for s in self.spans if s.name == name]
        return 1e3 * statistics.median(d) if d else None

    def joined(self) -> Optional[List[List[int]]]:
        """The counters vector of every step of the window, in order; None
        unless every ``oktopk/step`` span has one and their number is the
        window's step count."""
        rows = [self.counters.get(s) for s in self.steps]
        if len(rows) != self.window_steps or any(r is None for r in rows):
            return None
        return rows

    def column(self, name: str) -> Optional[List[int]]:
        rows = self.joined()
        if rows is None or name not in self.names:
            return None
        i = self.names.index(name)
        return [r[i] for r in rows]


def xplane_path(cell: str) -> Optional[str]:
    root = os.path.join(discover.ROOT, ".bench_out", "trace", cell)
    found = [os.path.join(d, n) for d, _, names in os.walk(root)
             for n in names if n.endswith(".xplane.pb")]
    return found[0] if len(found) == 1 else None


def host_spans(profile) -> List[Span]:
    """Every ``oktopk/...`` event of the host planes, with its
    ``step_num`` where it carries one."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                start = ev.start_ns * 1e-9
                step = dict(ev.stats).get("step_num")
                out.append(Span(ev.name, start,
                                start + ev.duration_ns * 1e-9,
                                None if step is None else int(step)))
    return sorted(out, key=lambda s: s.start)


def program_snapshot(ctx) -> dict:
    """``profiling.snapshot()``, taken once a run and kept on ``ctx``;
    empty for a program from before the snapshot existed."""
    if not hasattr(ctx, "program_snapshot"):
        try:
            from oktopk_tpu.utils import profiling
            ctx.program_snapshot = profiling.snapshot()
        except (ImportError, AttributeError):
            ctx.program_snapshot = {}
    return ctx.program_snapshot


def step_runs(trace: xtrace.Trace, steps: List[Span]):
    """Executions of the step program on the first chip
    (``xtrace.Chip.step_runs``). In the sandbox's stand-in (no line of
    programs) the extent of the operations that start under each
    ``oktopk/step`` span or after it, up to the next."""
    chip = trace.chips[0]
    runs = chip.step_runs(trace.window)
    if runs or not steps:
        return runs
    edges = [s.start for s in steps] + [trace.window[1]]
    for lo, hi in zip(edges, edges[1:]):
        inside = [o for o in chip.ops if lo <= o.start < hi]
        if inside:
            runs.append((min(o.start for o in inside),
                         max(o.end for o in inside)))
    return runs


def build(trace: xtrace.Trace, spans: List[Span],
          snap: dict) -> Optional[View]:
    lo, hi = trace.window
    spans = [s for s in spans if lo <= s.start < hi]
    steps = [s for s in spans if s.name == STEP]
    if not steps or any(s.step is None for s in steps):
        return None
    counters = {int(r["step"]): list(r["counters"])
                for r in snap.get("step_counters", [])}
    by_step = {int(k): v for k, v in snap.get(
        "host_counters", {}).get("by_step", {}).items()}
    chip = trace.chips[0]
    ops = [(o.start, o.end) for o in chip.ops]
    runs = step_runs(trace, steps)
    between = [intervals.length(intervals.gaps(ops, a, b))
               for (_, a), (b, _) in zip(runs, runs[1:])]
    prog = [(s.start, s.end) for s in spans]
    bench = [(s, e) for _, s, e in trace.host]
    idle = intervals.gaps(ops, lo, hi)
    in_prog = intervals.intersection_len(idle, prog)
    in_bench = intervals.intersection_len(idle, prog + bench) - in_prog
    total = intervals.length(idle)
    return View(spans, [s.step for s in steps], trace.steps,
                list(snap.get("counter_names", [])),
                list(snap.get("branch_names", [])), counters, by_step,
                between,
                {"oktopk": in_prog, "bench": in_bench,
                       "none": total - in_prog - in_bench})


def view(ctx) -> Optional[View]:
    """The joined records of this run's traced window, read once (and kept
    on ``ctx``); None where the program has no such spans."""
    if not hasattr(ctx, "progspans_view"):
        import jax
        path = xplane_path(ctx.cell["name"])
        ctx.progspans_view = None
        if path is not None and ctx.trace is not None:
            ctx.progspans_view = build(
                ctx.trace,
                host_spans(jax.profiler.ProfileData.from_file(path)),
                program_snapshot(ctx))
        if ctx.progspans_view is not None:
            keep(ctx, ctx.progspans_view)
    return ctx.progspans_view


def keep(ctx, v: View) -> None:
    """The join, a step a row, for whoever wants to look: next to the
    stamps, under ``.bench_out/progspans/``."""
    out = os.path.join(discover.ROOT, ".bench_out", "progspans")
    os.makedirs(out, exist_ok=True)
    disp = {s.step: s for s in v.spans if s.name == STEP}
    rows = [{"step": st, "host_ms": 1e3 * (disp[st].end - disp[st].start),
             "counters": v.counters.get(st)} for st in v.steps]
    with open(os.path.join(out, ctx.cell["name"] + ".json"), "w") as f:
        json.dump({"counter_names": v.names, "steps": rows,
                   "idle_s_by_holder": v.shares,
                   "step_gaps_ms": [1e3 * g for g in v.gap_s],
                   "compile_s_by_step": v.compile_by_step,
                   "sub_scope_ms": sub_scope_ms(ctx)}, f)


# ---- sub-scopes of select and stage ------------------------------------

def sub_of(path: str, subs: Subs) -> Optional[str]:
    """``.../anat/b000/select/sweep/...`` -> ``select_sweep``: the named
    step of the algorithm an operation of ``select`` or ``stage`` lies in;
    None for other phases, ``<phase>_unscoped`` where it lies in none."""
    phase = xtrace.phase_of(path)
    if phase not in subs:
        return None
    parts = path.split("/")[:-1]
    for p in reversed(parts):
        if p in subs[phase]:
            return f"{phase}_{p}"
    return f"{phase}_unscoped"


def label_of(op: xtrace.Op, subs: Subs) -> Optional[str]:
    sub = sub_of(op.path, subs)
    if sub is None:
        return None
    return "kernel:" + xtrace.base_name(op.name) if xtrace.is_kernel(op) \
        else sub


def sub_scope_ms(ctx) -> Optional[Dict[str, float]]:
    """Device milliseconds a step of each sub-scope, kernels apart under
    their own names (the mean over chips, as ``Trace.seconds``); reduced
    once a run and kept on ``ctx``. None where the program names no
    sub-scope or its step has none (then ``unscoped`` would be all of it,
    and that is the parent's reading, not a number)."""
    if not hasattr(ctx, "sub_scope_ms"):
        ctx.sub_scope_ms = None
        subs = program_snapshot(ctx).get("sub_scopes")
        if subs and ctx.trace is not None:
            ms = _sub_scope_ms(ctx.trace, subs)
            if any(not k.startswith("kernel:")
                   and not k.endswith("_unscoped") for k in ms):
                ctx.sub_scope_ms = ms
    return ctx.sub_scope_ms


def _sub_scope_ms(trace: xtrace.Trace, subs: Subs) -> Dict[str, float]:
    labels = set()
    for c in trace.chips:
        for o in c.leaves():
            lab = label_of(o, subs)
            if lab is not None:
                labels.add(lab)
    return {lab: 1e3 * trace.seconds(
        lambda o, lab=lab: label_of(o, subs) == lab) / trace.steps
        for lab in sorted(labels)}


def sub_ms(ctx, label: str) -> Optional[float]:
    """One sub-scope's device milliseconds a step, kernels excluded."""
    ms = sub_scope_ms(ctx)
    return None if ms is None else ms.get(label, 0.0)


def has_reader(label: str) -> bool:
    """Whether a sub-scope has a metric of its own under ``metrics/``."""
    return os.path.exists(os.path.join(discover.HERE, "metrics",
                                       label + "_ms.py"))
