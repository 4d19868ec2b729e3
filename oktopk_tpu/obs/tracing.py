"""Anomaly-triggered profiler windows + Chrome trace export.

``AnomalyTracer`` subscribes to the run-journal event bus: a
``guard_trip``, ``fallback``, or breach-flagged ``quality_rollup``
event ARMS it, and the next
``on_step()`` call opens a bounded ``jax.profiler`` trace window over
the following N steps, closing with a ``trace_captured`` journal event
that ties the capture back to its trigger (``"guard_trip@step12"``).
The expensive instrument therefore runs only when something is already
wrong — the steady-state overhead is one predicate per step.

The event also carries what the captured steps did: the program's
``counters`` vectors (``collectives/state.COUNTERS``) of the steps inside
the window, when the tracer was given their source.

Capture count is capped (``max_captures``): a flapping guard must not
fill the disk with traces. Profiler failures are tolerated — the
window is journalled with ``logdir: null`` rather than raising, since
observability must never take down training (some backends/platforms
cannot start a trace at all).

The window is the profiler's own trace: it holds the program's host spans
(``utils/profiling.span``) beside the device planes, on one clock. Where it
holds device planes (a TPU run) and the tracer was given the compiled
step's text (``Trainer.step_hlo``), the capture is reduced on the spot:
``anatomy.analyze_xplane`` labels every device event with its owner and
the window's anatomy is journalled as ``step_anatomy`` / ``overlap_report``
events with ``source: "device"``, milliseconds a captured step.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Dict, List, Optional

_TRIGGERS = ("guard_trip", "fallback", "quality_rollup")


class AnomalyTracer:
    """Arms on anomaly events, captures a bounded trace window."""

    def __init__(self, logdir: str, bus=None, num_steps: int = 3,
                 max_captures: int = 3,
                 step_counters: Optional[Callable[[], list]] = None,
                 step_hlo: Optional[Callable[[], str]] = None):
        self.logdir = logdir
        self.bus = bus
        self.num_steps = max(1, int(num_steps))
        self.max_captures = max(0, int(max_captures))
        # () -> [(step, counters array), ...]: Trainer.step_counters
        self.step_counters = step_counters
        # () -> the compiled step's text: Trainer.step_hlo
        self.step_hlo = step_hlo
        self.captures: List[Dict[str, Any]] = []
        self._armed: Optional[str] = None      # trigger description
        self._start_step: Optional[int] = None
        self._active_dir: Optional[str] = None
        self._profiler_ok = False
        if bus is not None:
            bus.subscribe(self._on_event)

    @property
    def active(self) -> bool:
        return self._start_step is not None

    def _on_event(self, entry: Dict[str, Any]):
        event = entry.get("event")
        if event not in _TRIGGERS:
            return
        if event == "quality_rollup" and not entry.get("breaches"):
            return                 # only breached rollups are anomalies
        if self.active or self._armed is not None:
            return                 # one window at a time
        if len(self.captures) >= self.max_captures:
            return
        self._armed = f"{event}@step{entry.get('step')}"

    def on_step(self, step: int):
        """Call once per training step (host side, before the step)."""
        step = int(step)
        if self.active:
            if step >= self._start_step + self.num_steps:
                self._stop(step)
            return
        if self._armed is not None:
            self._start(step)

    def _start(self, step: int):
        d = os.path.join(self.logdir, f"anomaly_step{step}")
        self._profiler_ok = False
        try:
            import jax
            os.makedirs(d, exist_ok=True)
            jax.profiler.start_trace(d)
            self._profiler_ok = True
            self._active_dir = d
        except Exception:
            self._active_dir = None   # journal the window anyway
        self._start_step = step

    def _stop(self, step: int):
        if self._profiler_ok:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                self._active_dir = None
        cap = {"step": int(step), "start_step": int(self._start_step),
               "num_steps": int(step - self._start_step),
               "logdir": self._active_dir,
               "trigger": self._armed or "unknown"}
        if self.step_counters is not None:
            try:
                from oktopk_tpu.utils.profiling import fetch_counters
                cap["counters"] = fetch_counters(
                    [(s, c) for s, c in self.step_counters()
                     if self._start_step <= s < step])
            except Exception:
                # a device fetch: journal the window without them
                logging.getLogger(__name__).exception(
                    "counters of the captured steps not fetched")
        self.captures.append(cap)
        self._armed = None
        self._start_step = None
        self._active_dir = None
        self._profiler_ok = False
        if self.bus is not None:
            self.bus.emit("trace_captured", **cap)
        self._journal_anatomy(cap)

    def _journal_anatomy(self, cap: Dict[str, Any]):
        """The captured window's device time by owner, a step. Nothing
        where there is no capture, no bus or no step text; a capture
        without device planes (a CPU run) journals an
        ``anatomy_warning``."""
        if (self.bus is None or self.step_hlo is None
                or cap["logdir"] is None or cap["num_steps"] < 1):
            return
        try:
            from oktopk_tpu.obs import anatomy
            analysis = anatomy.analyze_xplane(
                cap["logdir"], self.step_hlo, steps=cap["num_steps"])
            anatomy.emit_anatomy(
                self.bus, analysis, step=cap["step"], source="device",
                warn_reason="no device plane in the capture",
                warn_path=cap["logdir"])
        except Exception:
            # a compile, a file of the profiler's, a text of another
            # XLA: the window is journalled, its anatomy is not
            logging.getLogger(__name__).exception(
                "the captured window's anatomy not journalled")

    def finish(self, step: int):
        """Force-close any open window (end of train())."""
        if self.active:
            self._stop(int(step))
