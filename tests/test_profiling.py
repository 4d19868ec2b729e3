"""Profiling subsystem tests (SURVEY.md §5.1; reference per-phase timers at
VGG/allreducer.py:256-262,379-439 and memory logging VGG/dl_trainer.py:697)."""

import csv
import logging
import time

import jax

from oktopk_tpu.utils.logging import get_logger
from oktopk_tpu.utils.profiling import (
    MetricWriter,
    PhaseTimers,
    TraceWindow,
    device_memory_stats,
    host_memory_stats,
    trace_window,
)


class TestPhaseTimers:
    def test_accumulates_and_renders(self):
        t = PhaseTimers(every=2)
        with t.phase("data"):
            time.sleep(0.01)
        with t.phase("step"):
            pass
        tab = t.table()
        assert "data" in tab and "step" in tab
        assert "mean_ms" in tab

    def test_maybe_log_cadence_and_reset(self):
        logs = []

        class L:
            def info(self, fmt, *a):
                logs.append(fmt % a)

        t = PhaseTimers(every=2)
        t.add("step", 0.5)
        assert not t.maybe_log(1, L())
        assert t.maybe_log(2, L())
        assert len(logs) == 1
        # reset happened: nothing to log next cadence
        assert not t.maybe_log(4, L())

    def test_table_renders_empty_phase(self):
        t = PhaseTimers()
        t._samples["ghost"]  # defaultdict access registers sample-less phase
        t.add("step", 0.25)
        tab = t.table()
        ghost_row = next(r for r in tab.splitlines() if "ghost" in r)
        assert "-" in ghost_row
        assert "step" in tab

    def test_summary_matches_samples(self):
        t = PhaseTimers()
        t.add("step", 0.1)
        t.add("step", 0.3)
        t._samples["ghost"]
        s = t.summary()
        assert s["step"]["count"] == 2
        assert s["step"]["total_s"] == 0.4
        assert abs(s["step"]["mean_ms"] - 200.0) < 1e-6
        assert s["step"]["min_ms"] == 100.0
        assert s["step"]["max_ms"] == 300.0
        assert s["ghost"] == {"mean_ms": 0.0, "min_ms": 0.0, "max_ms": 0.0,
                              "p50_ms": 0.0, "p95_ms": 0.0,
                              "total_s": 0.0, "count": 0.0}

    def test_phase_is_a_span_recorded_here(self):
        # took the place of the ChromeTraceSink export test: the records a
        # sink was handed are the recorder's own now, in order, with
        # wall-clock nanoseconds
        t = PhaseTimers()
        with t.phase("data"):
            pass
        with t.phase("step"):
            pass
        assert [r[0] for r in t.records] == ["data", "step"]
        for name, start_ns, end_ns, step, parent in t.records:
            assert end_ns >= start_ns and step is None and parent is None
        assert t.summary()["data"]["count"] == 1


class TestMetricWriter:
    def test_csv_roundtrip(self, tmp_path):
        with MetricWriter(str(tmp_path)) as w:
            w.write(1, {"loss": 2.5, "vol": 100.0})
            w.write(2, {"loss": 1.5, "vol": 90.0})
        with open(w.path) as f:
            rows = list(csv.reader(f))
        assert rows[0] == ["step", "loss", "vol"]
        assert rows[1][0] == "1" and float(rows[1][1]) == 2.5
        assert len(rows) == 3

    def test_append_does_not_duplicate_header(self, tmp_path):
        with MetricWriter(str(tmp_path)) as w:
            w.write(1, {"a": 1.0})
        with MetricWriter(str(tmp_path)) as w:
            w.write(2, {"a": 2.0})
        with open(w.path) as f:
            rows = list(csv.reader(f))
        assert sum(1 for r in rows if r and r[0] == "step") == 1
        assert len(rows) == 3

    def test_append_with_changed_fields_rotates(self, tmp_path):
        with MetricWriter(str(tmp_path)) as w:
            w.write(1, {"a": 1.0})
            first = w.path
        with MetricWriter(str(tmp_path)) as w:
            w.write(2, {"a": 2.0, "b": 3.0})
            second = w.path
        assert first != second
        with open(second) as f:
            rows = list(csv.reader(f))
        assert rows[0] == ["step", "a", "b"]
        assert rows[1][0] == "2"


def test_trace_window_produces_trace(tmp_path):
    logdir = str(tmp_path / "trace")
    tw = TraceWindow(logdir, start_step=2, num_steps=1)
    x = jax.numpy.ones((8, 8))
    for step in range(1, 5):
        tw.on_step(step)
        jax.block_until_ready(x @ x)
    tw.close()
    assert not tw._active
    # a plugins/profile dir with at least one capture should exist
    import os

    found = []
    for root, _dirs, files in os.walk(logdir):
        found.extend(files)
    assert found, "trace produced no files"


def test_trace_window_noop_when_profiler_unavailable(tmp_path, monkeypatch):
    """CPU backends without profiler support must not break the traced
    code: the block still runs, and stop is never attempted."""
    def boom(*a, **k):
        raise RuntimeError("profiler unavailable")

    stops = []
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: stops.append(1))
    ran = []
    with trace_window(str(tmp_path / "t")):
        ran.append(1)
    assert ran == [1]
    assert stops == []  # never started, so never stopped


def test_trace_window_tolerates_nesting(tmp_path):
    """A trace_window nested inside an already-open trace (e.g. an
    obs/tracing.py anomaly window) degrades to a no-op instead of
    raising out of the traced code."""
    ran = []
    with trace_window(str(tmp_path / "outer")):
        with trace_window(str(tmp_path / "inner")):
            ran.append(1)
    assert ran == [1]


def test_memory_stats_shapes():
    stats = device_memory_stats()
    assert isinstance(stats, dict)  # may be {} on CPU
    host = host_memory_stats()
    assert host.get("host_rss_bytes", 1.0) > 0


def test_device_memory_stats_handles_statless_device():
    class NoStats:  # CPU-like device object without memory_stats
        pass

    class NullStats:
        def memory_stats(self):
            return None

    class Full:
        def memory_stats(self):
            return {"bytes_in_use": 7, "bytes_limit": 100,
                    "num_allocs": 3}  # extraneous key is dropped

    assert device_memory_stats(NoStats()) == {}
    assert device_memory_stats(NullStats()) == {}
    assert device_memory_stats(Full()) == {
        "bytes_in_use": 7.0, "bytes_limit": 100.0}


def test_get_logger_attaches_logfile_to_existing_logger(tmp_path):
    """The console-only logger created at import time must still gain
    the per-experiment file handler once the rundir exists (the old
    early-return dropped it), without duplicating on repeat calls."""
    name = "oktopk_tpu.test_logfile_attach"
    lg = get_logger(name)  # console-only first
    logfile = str(tmp_path / "run" / "train.log")
    try:
        lg2 = get_logger(name, logfile=logfile)
        assert lg2 is lg
        lg.info("hello-logfile")
        get_logger(name, logfile=logfile)  # idempotent
        fhs = [h for h in lg.handlers
               if isinstance(h, logging.FileHandler)]
        assert len(fhs) == 1
        fhs[0].flush()
        with open(logfile) as f:
            assert "hello-logfile" in f.read()
    finally:
        for h in list(lg.handlers):
            h.close()
            lg.removeHandler(h)
