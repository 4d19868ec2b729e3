"""Whose each device millisecond of the traced step is.

The program says it: ``oktopk_tpu/obs/anatomy.owners`` reads the compiled
step's text (``run.py`` wrote it to ``.bench_out/trace/<cell>/step.hlo.txt``)
into a map instruction -> owner (phase, sub-scope, the rule that answered,
the source frame), and ``anatomy.analyze_device`` sums the trace's device
events by it: every instant of the busy time goes to the innermost event
that covers it, so the table closes on ``busy_s``. No rule is copied here.

A program without ``anatomy.owners`` (the parent of the PR that added it)
gives ``table(ctx) is None`` and every reader returns None: nothing raises.

The table, milliseconds a step (the mean over the chips), is kept beside
``progspans/``: ``.bench_out/owners/<cell>.json``, with the seconds the map
and the sums took.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Sequence

from benchlib import discover

KINDS = ("own_ms", "inherited_ms", "control_ms")


def _reduce(trace, owner_map, analyze_device) -> Optional[dict]:
    """``analyze_device`` of each chip's events inside the window; the
    tables' mean over the chips, the largest instructions of the first."""
    lo, hi = trace.window
    per = []
    for chip in trace.chips:
        ops = [(o.name, max(o.start, lo), min(o.end, hi))
               for o in chip.ops]     # what lies outside is dropped as empty
        a = analyze_device(ops, owner_map, steps=trace.steps)
        if a is None:
            return None
        per.append(a)
    rows: Dict[str, Dict[str, float]] = {}
    for a in per:
        for key, row in a["owners"].items():
            mine = rows.setdefault(key, dict.fromkeys(KINDS, 0.0))
            for k in KINDS:
                mine[k] += row[k] / len(per)
    mean = lambda k: sum(a[k] for a in per) / len(per)
    return {"owners": rows, "unowned_ms": mean("unowned_ms"),
            "busy_ms": mean("busy_ms"), "steps": trace.steps,
            "largest_inherited": per[0]["largest_inherited"],
            "largest_unowned": per[0]["largest_unowned"]}


def table(ctx) -> Optional[dict]:
    """The owners' table of this run's traced window, built once (and kept
    on ``ctx``); None without a trace, without the step's text or for a
    program that has no map."""
    if not hasattr(ctx, "owners_table"):
        ctx.owners_table = None
        path = os.path.join(discover.ROOT, ".bench_out", "trace",
                            ctx.cell["name"], "step.hlo.txt")
        try:
            from oktopk_tpu.obs import anatomy
            owners, analyze_device = anatomy.owners, anatomy.analyze_device
        except (ImportError, AttributeError):
            owners = None
        if owners is not None and ctx.trace is not None \
                and os.path.exists(path):
            t0 = time.perf_counter()
            with open(path) as f:
                owner_map = owners(f.read())
            t1 = time.perf_counter()
            ctx.owners_table = _reduce(ctx.trace, owner_map, analyze_device)
            if ctx.owners_table is not None:
                ctx.owners_table.update(
                    instructions=len(owner_map), owners_s=t1 - t0,
                    table_s=time.perf_counter() - t1)
                keep(ctx, ctx.owners_table)
    return ctx.owners_table


def keep(ctx, t: dict) -> None:
    out = os.path.join(discover.ROOT, ".bench_out", "owners")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, ctx.cell["name"] + ".json"), "w") as f:
        json.dump(t, f, indent=1)


def owned_ms(ctx, phase: str,
             subs: Optional[Sequence[str]] = None) -> Optional[float]:
    """Device milliseconds a step that the phase owns (``subs``: only
    those sub-scopes of it), its own instructions', the inherited ones'
    and its loops' and branches' own time together; None where the table
    holds nothing of it."""
    t = table(ctx)
    if t is None:
        return None
    keys = ([f"{phase}/{s}" for s in subs] if subs is not None else
            [k for k in t["owners"] if k.split("/")[0] == phase])
    rows = [t["owners"][k] for k in keys if k in t["owners"]]
    return sum(r[k] for r in rows for k in KINDS) if rows else None


def total_ms(ctx, kind: str) -> Optional[float]:
    """One kind of time (``KINDS``) over all owners."""
    t = table(ctx)
    return None if t is None else sum(r[kind] for r in t["owners"].values())
