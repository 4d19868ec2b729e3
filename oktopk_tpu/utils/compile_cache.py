"""Where the persistent XLA compilation cache lives, and what compiling
cost.

One rule, applied by every entry point (train/main_trainer.py,
train/main_bert.py, bench.py's children, chip_smoke.py) before its first
compile: if ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from
outside and this code sets nothing (jax reads the variable itself);
otherwise it is ``<checkout>/.jax_cache`` — a fixed path derived from the
package's own location, because the directory is part of the cache key and
a path that moves (a temp name, a pid, a time) never hits.

Beside it, the program's own host counters: one ``jax.monitoring``
listener (:func:`compile_counters`) that adds up seconds and counts of
jaxpr tracing, lowering and back-end compiling and the persistent cache's
hits and misses, each stamped with the host step it happened in
(``utils/profiling.current_step``).
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from oktopk_tpu.utils import profiling

# jax.monitoring event -> the name it is counted under
DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
MAX_STEPS = 1024   # distinct host steps whose seconds are kept apart


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: the directory that holds ``oktopk_tpu/``."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def ensure_compile_cache() -> str:
    """Place the compilation cache and return the directory in use."""
    compile_counters()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounters:
    """Totals since the listener was installed. ``seconds``/``counts`` by
    kind (``trace``, ``lower``, ``compile``; a ``compile`` that the
    persistent cache served is counted too, with the time the load took);
    ``by_step[step][kind]`` the same seconds by host step (step 0: before
    the first train step); ``recompiles`` what a ``Trainer`` reported: a
    back-end compile in a later call of a step function than its first.

    jax times these spans nested (a jitted function traced inside another's
    trace, a kernel traced while its caller is lowered), so only the
    outermost of a nest adds its seconds, under its own kind: the seconds
    are wall time and add up. ``counts`` count every span."""

    def __init__(self):
        self._nest = threading.local()
        self.seconds: Dict[str, float] = {k: 0.0 for k in DURATIONS.values()}
        self.counts: Dict[str, int] = {
            k: 0 for k in (*DURATIONS.values(), *EVENTS.values())}
        self.by_step: "OrderedDict[int, Dict[str, float]]" = OrderedDict()
        self.recompiles: List[Dict[str, Any]] = []

    def _on_enter(self, event: str, *_, **__) -> None:
        # jax records a scalar (the start time) when a timed span opens
        if event in DURATIONS:
            self._nest.depth = getattr(self._nest, "depth", 0) + 1

    def _on_duration(self, event: str, secs: float, **_) -> None:
        kind = DURATIONS.get(event)
        if kind is None:
            return
        self.counts[kind] += 1
        depth = self._nest.depth = max(getattr(self._nest, "depth", 1) - 1,
                                       0)
        if depth:
            return      # inside another timed span: its seconds hold these
        self.seconds[kind] += secs
        step = profiling.current_step()
        at = self.by_step.get(step)
        if at is None:
            at = self.by_step[step] = {k: 0.0 for k in DURATIONS.values()}
            if len(self.by_step) > MAX_STEPS:
                self.by_step.popitem(last=False)
        at[kind] += secs

    def _on_event(self, event: str, **_) -> None:
        kind = EVENTS.get(event)
        if kind is not None:
            self.counts[kind] += 1

    def note_recompile(self, step: int, seconds: float) -> None:
        self.recompiles.append({"step": int(step), "seconds": seconds})

    def as_dict(self) -> Dict[str, Any]:
        return {"seconds": dict(self.seconds), "counts": dict(self.counts),
                "by_step": {str(s): dict(v) for s, v in self.by_step.items()},
                "recompiles": list(self.recompiles)}


_counters: Optional[CompileCounters] = None


def compile_counters() -> CompileCounters:
    """The process's one listener, installed at the first call."""
    global _counters
    if _counters is None:
        import jax.monitoring as mon
        _counters = CompileCounters()
        mon.register_scalar_listener(_counters._on_enter)
        mon.register_event_duration_secs_listener(_counters._on_duration)
        mon.register_event_listener(_counters._on_event)
    return _counters
