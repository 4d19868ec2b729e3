"""Profiling / tracing subsystem (SURVEY.md §5.1).

Reference shape: per-phase wall-clock timer dicts in the allreducer
(``_merge/_compression/_allreduce/_demerge/_d2h/_h2d_timers``,
VGG/allreducer.py:256-262) dumped every 50 steps as a per-layer-group table
by ``_print_profiling`` (VGG/allreducer.py:379-439), plus TensorBoard scalars
(VGG/dl_trainer.py:611-613) and GPU/CPU memory logging
(VGG/dl_trainer.py:697-699).

TPU-native reality: the compression/collective phases fuse into ONE XLA
program, so the program says what a step did in three kinds of record,
all collected here:

- **host spans** — :func:`span` is the one host-side primitive: a
  ``jax.profiler.TraceAnnotation`` (so the span lands in the profiler's own
  ``.xplane.pb``, on the clock of the device planes, and costs a flag test
  when no profiler runs) and, when a recorder is attached
  (:func:`attach`) or handed in, one ``(name, start_ns, end_ns, step,
  parent)`` record in a :class:`PhaseTimers`. The device-side twin is
  ``obs/anatomy.phase_scope``;
- **host counters** — the compile listener of ``utils/compile_cache.py``;
- **device counters** — the ``metrics["counters"]`` vector of every step
  (``collectives/state.COUNTERS``), kept unsynced by the ``Trainer``.

:func:`snapshot` / :func:`dump` are the one way out for all three. Also
here: :class:`MetricWriter` (per-step scalar CSV), :class:`TraceWindow` /
:func:`trace_window` (bounded ``jax.profiler`` captures) and
:func:`device_memory_stats`.
"""

from __future__ import annotations

import csv
import json
import os
import threading
import time
import weakref
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

SPAN_PREFIX = "oktopk/"
MAX_RECORDS = 8192     # span records a recorder keeps (a ring)

_recorder: Optional["PhaseTimers"] = None
_open = threading.local()          # the stack of open spans, a thread
_step = 0                          # host step of the last stepped span
_sources: List[weakref.ref] = []   # live objects with step_counters()


def attach(recorder: Optional["PhaseTimers"]) -> Optional["PhaseTimers"]:
    """Make ``recorder`` the in-memory sink of every span (None detaches);
    returns the one attached before."""
    global _recorder
    prev, _recorder = _recorder, recorder
    return prev


def current_step() -> int:
    """The host step counter of the innermost span that carried one."""
    return _step


class span:
    """``with span("oktopk/dispatch"):`` — one host span.

    Always a ``TraceAnnotation`` (``step`` becomes its ``step_num`` stat);
    with a recorder (``recorder=`` or the attached one) also one record.
    A span without ``step`` inherits its parent's, so every record of one
    step shares the identifier."""

    __slots__ = ("name", "step", "recorder", "_ann", "_t0", "_parent")

    def __init__(self, name: str, step: Optional[int] = None,
                 recorder: Optional["PhaseTimers"] = None):
        self.name, self.step, self.recorder = name, step, recorder

    def __enter__(self):
        global _step
        import jax
        if self.step is not None:
            _step = self.step
            self._ann = jax.profiler.TraceAnnotation(self.name,
                                                     step_num=self.step)
        else:
            self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        if self.recorder is None:
            self.recorder = _recorder
        if self.recorder is not None:
            stack = _open.__dict__.setdefault("stack", [])
            self._parent = stack[-1] if stack else None
            if self.step is None and self._parent is not None:
                self.step = self._parent.step
            stack.append(self)
            self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        if self.recorder is not None:
            t1 = time.time_ns()
            _open.stack.pop()
            self.recorder.record(
                self.name, self._t0, t1, self.step,
                self._parent.name if self._parent is not None else None)
        self._ann.__exit__(*exc)
        return False


class PhaseTimers:
    """The in-memory sink of :func:`span`: every record as it came
    (``records``, a ring of ``MAX_RECORDS``) and rolling per-name
    durations.

    ``table()`` renders the reference-style mean/total dump
    (VGG/allreducer.py:379-439), ``summary()`` its machine-readable form,
    and ``maybe_log(step, logger)`` prints the table every ``every`` steps
    then resets, like the reference's 50-step cadence. ``phase(name)`` is a
    span recorded here whether or not this recorder is the attached one.
    """

    def __init__(self, every: int = 50):
        self.every = every
        self._samples: Dict[str, list] = defaultdict(list)
        # (name, start_ns, end_ns, step, parent); wall-clock nanoseconds,
        # the clock the profiler stamps its host events with
        self.records: deque = deque(maxlen=MAX_RECORDS)

    def phase(self, name: str) -> span:
        return span(name, recorder=self)

    def record(self, name: str, start_ns: int, end_ns: int,
               step: Optional[int] = None,
               parent: Optional[str] = None) -> None:
        self._samples[name].append((end_ns - start_ns) * 1e-9)
        self.records.append((name, start_ns, end_ns, step, parent))

    def add(self, name: str, seconds: float) -> None:
        self._samples[name].append(seconds)

    def table(self) -> str:
        rows = [f"{'phase':<22}{'mean_ms':>10}{'total_s':>10}{'count':>8}"]
        for name in sorted(self._samples):
            s = self._samples[name]
            if not s:
                # defaultdict access can register a phase with no
                # samples; render it instead of dividing by zero
                rows.append(f"{name:<22}{'-':>10}{'-':>10}{0:>8d}")
                continue
            mean = sum(s) / len(s)
            rows.append(
                f"{name:<22}{mean * 1e3:>10.2f}{sum(s):>10.3f}{len(s):>8d}")
        return "\n".join(rows)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Machine-readable form of :meth:`table` (for the run
        journal's ``phase`` events): mean/min/max and nearest-rank
        p50/p95 per phase, so host-phase spread sits next to the device
        anatomy in one report (scripts/obs_report.py)."""
        out: Dict[str, Dict[str, float]] = {}
        for name, s in self._samples.items():
            if not s:
                out[name] = {"mean_ms": 0.0, "min_ms": 0.0, "max_ms": 0.0,
                             "p50_ms": 0.0, "p95_ms": 0.0,
                             "total_s": 0.0, "count": 0.0}
                continue
            srt = sorted(s)
            cnt = len(srt)

            def rank(q: float) -> float:
                # nearest-rank percentile: exact order statistic, no
                # interpolation inventing never-observed durations
                return srt[min(cnt - 1, max(0, int(q * cnt + 0.5) - 1))]

            out[name] = {
                "mean_ms": sum(s) / cnt * 1e3,
                "min_ms": srt[0] * 1e3,
                "max_ms": srt[-1] * 1e3,
                "p50_ms": rank(0.50) * 1e3,
                "p95_ms": rank(0.95) * 1e3,
                "total_s": float(sum(s)),
                "count": float(cnt),
            }
        return out

    def reset(self) -> None:
        self._samples.clear()

    def maybe_log(self, step: int, logger) -> bool:
        if self.every and step % self.every == 0 and self._samples:
            logger.info("phase timing @ step %d\n%s", step, self.table())
            self.reset()
            return True
        return False


class MetricWriter:
    """Append-only per-step scalar log: ``<logdir>/scalars.csv``.

    Stands in for the reference's rank-0 tensorboardX writer
    (VGG/main_trainer.py:170-172, VGG/dl_trainer.py:611-613) without the
    dependency; the CSV loads straight into pandas for the same plots.
    """

    def __init__(self, logdir: str, filename: str = "scalars.csv"):
        os.makedirs(logdir, exist_ok=True)
        self.path = os.path.join(logdir, filename)
        self._existing_fields: Optional[list] = None
        if os.path.exists(self.path) and os.path.getsize(self.path) > 0:
            with open(self.path, newline="") as f:
                header = next(csv.reader(f), None)
            if header and header[0] == "step":
                self._existing_fields = header[1:]
        self._file = open(self.path, "a", newline="")
        self._writer = csv.writer(self._file)
        self._fields: Optional[list] = None

    def write(self, step: int, scalars: Dict[str, float]) -> None:
        if self._fields is None:
            self._fields = sorted(scalars)
            if self._existing_fields is None:
                self._writer.writerow(["step"] + self._fields)
            elif self._existing_fields != self._fields:
                # resuming with a different metric set: rotate to a fresh
                # file rather than appending misaligned rows
                self._file.close()
                base, ext = os.path.splitext(self.path)
                i = 1
                while os.path.exists(f"{base}-{i}{ext}"):
                    i += 1
                self.path = f"{base}-{i}{ext}"
                self._file = open(self.path, "a", newline="")
                self._writer = csv.writer(self._file)
                self._writer.writerow(["step"] + self._fields)
        row = [step] + [format(float(scalars.get(k, float("nan"))), ".8g")
                        for k in self._fields]
        self._writer.writerow(row)
        self._file.flush()

    def close(self) -> None:
        self._file.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class TraceWindow:
    """Start a ``jax.profiler`` trace at ``start_step`` and stop it
    ``num_steps`` later — a bounded xprof capture (the TPU replacement for
    the reference's flag-gated deep profiling, VGG/settings.py:20-26)."""

    def __init__(self, logdir: str, start_step: int, num_steps: int = 3):
        self.logdir = logdir
        self.start_step = start_step
        self.stop_step = start_step + num_steps
        self._active = False

    def on_step(self, step: int) -> None:
        import jax

        # range test, not equality: a resumed run may first observe a step
        # past start_step and should still capture the remaining window
        if self.start_step <= step < self.stop_step and not self._active:
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif step >= self.stop_step and self._active:
            jax.profiler.stop_trace()
            self._active = False

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False


@contextmanager
def trace_window(logdir: str):
    """Trace everything inside the block (convenience for benchmarks).

    Degrades to a no-op when the profiler cannot start (CPU-only
    backends without profiler support, or a trace already running —
    e.g. nested inside an obs/tracing.py anomaly window): the traced
    code must run either way."""
    import jax

    started = False
    try:
        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(logdir)
        started = True
    except Exception:
        pass
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass


def device_memory_stats(device=None) -> Dict[str, float]:
    """HBM usage for one device (reference logs
    ``torch.cuda.memory_allocated``/psutil RSS, VGG/dl_trainer.py:697-699).
    Returns {} on backends without memory_stats (CPU)."""
    import jax

    dev = device or jax.local_devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return {}
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if key in stats:
            out[key] = float(stats[key])
    return out


def host_memory_stats() -> Dict[str, float]:
    """Host RSS via /proc (psutil-free)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return {"host_rss_bytes": float(line.split()[1]) * 1024}
    except OSError:
        pass
    return {}


# ---- one way out ------------------------------------------------------

# set-up spans (``oktopk/setup/...``) are recorded here always: a dozen
# records a process
SETUP = PhaseTimers(every=0)


def register(source) -> None:
    """Weakly register an object whose ``step_counters()`` returns
    ``[(step_num, counters array), ...]`` and whose ``capacities()``
    returns ``[{"cap_pair": int, "cap_gather": int, "leafwise": bool},
    ...]``, a bucket each (a ``Trainer`` does at construction), so that
    :func:`snapshot` finds it."""
    _sources[:] = [r for r in _sources if r() is not None]
    _sources.append(weakref.ref(source))


def fetch_counters(pairs) -> List[List[Any]]:
    """``[(step, counters device array), ...]`` as ``[[step, [ints]],
    ...]``, with ONE ``device_get``."""
    import jax
    host = jax.device_get([c for _, c in pairs])
    return [[int(s), [int(v) for v in c]]
            for (s, _), c in zip(pairs, host)]


def snapshot() -> Dict[str, Any]:
    """Everything the program recorded about itself, as plain data:

    - ``spans``: the set-up spans and the attached recorder's records;
    - ``host_counters``: the compile listener's totals, its seconds by
      host step and the recompiles it saw (utils/compile_cache.py);
    - ``step_counters``: the retained ``(step, counters)`` pairs of every
      live registered source, fetched with ONE ``device_get``; the order
      of a vector is ``counter_names``, a branch entry's value its place
      in ``branch_names``;
    - ``capacities``: the same sources' static buffer sizes, ``cap_pair``
      and ``cap_gather`` a bucket, in the order of the buckets: a step's
      ``local_k`` and ``global_k`` over their sums are the live shares of
      the two buffers, which the materialise's cost follows
      (ops/compaction.py ``_gather_live``); and ``leafwise`` beside them,
      whether the built step reduces the bucket a leaf at a time (a dense
      bucket whose flat vector nothing reads: none is built or cut up
      again, optim/distributed.py) or flattens it (every other bucket);
    - ``attention``: every distinct softmax-attention call traced in this
      process, grouped heads (``models/attention.blocked_causal_gqa``) and
      MLA's split heads (``models/attention.blocked_causal_attention``)
      alike: ``kernel``, whether the Pallas kernels of
      ``ops/flash_gqa.py`` run it or the blocked XLA form; its ``window`` (None: causal); ``tiles_visited``,
      the key tiles a sequence and head group visits, against
      ``tiles_causal``, what the causal triangle holds;
      ``kv_heads_a_step``, the key-value heads that ride one grid step of
      the kernels with their query heads (0: the XLA form) (static, from
      shapes); empty for a model that has no such layer;
    - ``delta_rule``: every distinct walk of the gated delta rule over a
      segment's chunks traced in this process
      (``models/qwen3_next.chunk_gated_delta_rule``): ``kernel``, whether
      the Pallas kernels of ``ops/delta_rule.py`` carry the state or the
      plain ``lax.scan``; ``segment``, the tokens walked, in chunks of
      ``chunk``; ``value_heads``, each with a state of [``dk``, ``dv``];
      ``heads_a_step`` and ``chunks_a_block``, the heads and chunks of one
      grid step of the kernels (0: the scan) (static, from shapes); empty
      for a model that has no such layer;
    - ``short_conv``: every distinct call of the gated short convolution
      traced in this process (``models/lfm2.ShortConv``): the ``tokens`` of
      a sequence, its ``channels`` and the convolution's ``taps`` (static,
      from shapes); empty for a model that has no such layer;
    - ``sub_scopes``: the named steps under the ``select`` and ``stage``
      phase scopes (obs/anatomy.SUB_SCOPES), for whoever reads a trace.
    """
    from oktopk_tpu.collectives.state import BRANCHES, COUNTERS
    from oktopk_tpu.obs.anatomy import SUB_SCOPES
    from oktopk_tpu.models import lfm2
    from oktopk_tpu.ops import delta_rule, flash_gqa
    from oktopk_tpu.utils.compile_cache import compile_counters

    recs = list(SETUP.records)
    if _recorder is not None and _recorder is not SETUP:
        recs += list(_recorder.records)
    sources = [src for r in _sources if (src := r()) is not None]
    pairs = [p for src in sources for p in src.step_counters()]
    return {
        "spans": [{"name": n, "start_ns": a, "end_ns": b, "step": s,
                   "parent": p} for n, a, b, s, p in recs],
        "host_counters": compile_counters().as_dict(),
        "counter_names": list(COUNTERS),
        "branch_names": list(BRANCHES),
        "capacities": [c for src in sources for c in src.capacities()],
        "attention": flash_gqa.calls(),
        "delta_rule": delta_rule.calls(),
        "short_conv": lfm2.short_conv_calls(),
        "sub_scopes": {ph: list(subs) for ph, subs in SUB_SCOPES.items()},
        "step_counters": [{"step": s, "counters": c}
                          for s, c in fetch_counters(pairs)],
    }


def dump(path: str) -> str:
    """:func:`snapshot` as JSON at ``path``."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(snapshot(), f)
    return path
