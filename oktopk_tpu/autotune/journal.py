"""JSONL decision journal — the autotuner's observability surface.

Every calibration and per-bucket decision appends one JSON line, so tuner
quality is auditable after the fact (predicted vs measured ms per
candidate, why a plan was kept or switched). The format is line-delimited
JSON on purpose: it survives crashes mid-run (every line that made it to
disk parses alone) and greps cleanly, like the reference's per-rank
profiling logs (VGG/allreducer.py:702-703) but machine-readable.

Schema — the first record is always an environment header, so decision
logs are comparable across machines (the same tuner on jax
0.4.x/CPU vs 0.9/TPU legitimately decides differently); subsequent
events carry ``event`` and ``step``:

  {"event": "header", "jax": "0.4.37", "jaxlib": "0.4.36",
   "device_kind": "cpu", "platform": "cpu", "world_size": 8}

  {"event": "calibration", "step": 0, "num_workers": 8,
   "alpha": 1.1e-6, "beta": 9.8e-12, "sizes": [...], "times_ms": [...],
   "residual": 0.02, "source": "measured" | "default"}

  {"event": "decision", "step": 0, "bucket": 0, "n": 1182720,
   "num_workers": 8,
   "candidates": [{"algo": "dense", "density": 1.0,
                   "predicted_ms": 3.1, "measured_ms": 2.9}, ...],
   "chosen": {"algo": "oktopk", "density": 0.02},
   "incumbent": {"algo": "dense", "density": 1.0} | null,
   "reason": "trial" | "hold" | "plan"}

``reason`` is "hold" when hysteresis kept the incumbent despite a
challenger measuring faster (within the hysteresis margin), "trial"
otherwise — or "plan" when the tuner ran in fabric-preset plan mode
(no trials; the cost-model prior stood in for the posterior). Plan-mode
decisions additionally carry ``fabric`` and ``num_pods``, and
hierarchical candidates/chosen carry ``outer`` plus a ``levels`` list of
per-level (algorithm, density).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from oktopk_tpu.obs.events import SCHEMA_VERSION

# standalone journal event name -> unified-bus event name. The file
# view keeps its historical "decision" name; the bus renames it so a
# consumer of the unified run journal can tell the streams apart.
_BUS_EVENT_REMAP = {"decision": "autotune_decision"}


def environment_header() -> Dict[str, Any]:
    """The jax/jaxlib/device/world identification every journal leads
    with. Tolerant of an uninitialisable backend (the header must never
    be the reason a journal cannot be written)."""
    import jax

    hdr: Dict[str, Any] = {"jax": jax.__version__,
                           "schema_version": SCHEMA_VERSION}
    try:
        import jaxlib
        hdr["jaxlib"] = getattr(jaxlib, "__version__", None)
    except Exception:
        hdr["jaxlib"] = None
    try:
        devs = jax.devices()
        hdr["device_kind"] = getattr(devs[0], "device_kind",
                                     devs[0].platform)
        hdr["platform"] = devs[0].platform
        hdr["world_size"] = len(devs)
    except Exception:
        hdr.update(device_kind=None, platform=None, world_size=0)
    return hdr


class DecisionJournal:
    """Append-only JSONL writer. ``path=None`` keeps entries in memory only
    (tests, or callers that just want the plan). ``header=True`` writes
    the :func:`environment_header` as the first record.

    With ``bus=`` (an ``obs.journal.EventBus``) every recorded event is
    ALSO forwarded onto the unified run journal's bus — except the
    header, which belongs to this standalone file only (the run journal
    writes exactly one header of its own) — making this file a thin
    view of the unified stream."""

    def __init__(self, path: Optional[str] = None, header: bool = True,
                 bus=None):
        self.path = path
        self.bus = bus
        self.entries: List[Dict[str, Any]] = []
        if path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            # truncate: one journal per tuner lifetime; re-tunes append
            with open(path, "w"):
                pass
        if header:
            self.record("header", **environment_header())

    def record(self, event: str, **fields) -> Dict[str, Any]:
        entry = {"event": event, **fields}
        self.entries.append(entry)
        if self.path:
            with open(self.path, "a") as f:
                f.write(json.dumps(entry) + "\n")
        if self.bus is not None and event != "header":
            self.bus.emit(_BUS_EVENT_REMAP.get(event, event), **fields)
        return entry


def read_journal(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL journal back into a list of entries."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
