"""Unified observability layer: typed run-journal events, wire-level
volume conformance, and anomaly-triggered tracing.

Deliberately import-free: ``autotune/journal.py`` imports
``obs.events`` (for the schema version) while ``obs.journal`` imports
``autotune/journal.py`` (for the environment header and JSONL reader).
Importing either submodule here would close that loop into a cycle, so
callers import the submodules directly:

  - :mod:`oktopk_tpu.obs.events`  — schema-versioned event definitions +
    validation (no oktopk imports at all).
  - :mod:`oktopk_tpu.obs.journal` — :class:`EventBus` and
    :class:`RunJournal` (the single per-run JSONL sink).
  - :mod:`oktopk_tpu.obs.volume`  — per-algorithm analytic wire-byte
    budgets and conformance ratios.
  - :mod:`oktopk_tpu.obs.tracing` — :class:`AnomalyTracer` (bounded
    ``jax.profiler`` windows armed by guard trips).
  - :mod:`oktopk_tpu.obs.regress` — step-time regression detection
    against the repo's BENCH_r*.json trajectory (plus quality-summary
    watching and baseline-gap warnings).
  - :mod:`oktopk_tpu.obs.quality` — in-jit signal-fidelity taps:
    per-bucket compression error, residual growth, effective density,
    threshold drift and winner-index churn (docs/OBSERVABILITY.md
    "Signal fidelity").
  - :mod:`oktopk_tpu.obs.metrics_buffer` — the device-side metric ring
    the taps accumulate into (host flush only on the configured
    cadence; zero steady-state syncs).
  - :mod:`oktopk_tpu.obs.rollup` — windowed rollups over flushed
    quality events with breach detection feeding the closed-loop
    seams.
  - :mod:`oktopk_tpu.obs.export` — Prometheus-textfile export of the
    latest quality rollups.
"""
