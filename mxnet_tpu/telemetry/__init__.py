"""Unified telemetry: one registry, step-time attribution, durable export.

The observability layer everything reports into (``mx.telemetry``):

- **registry.py** — the process-wide metrics registry (counters,
  gauges, timers, histograms with p50/p99, all named
  ``subsystem::name``) with ONE atomic snapshot-and-clear ``reset``.
  The six legacy report surfaces — ``fusion_report``,
  ``serving_report``, ``data_report``, ``fault_report``,
  ``compile_report``, ``profiler.counters`` — register collectors here
  and became filtered views of :func:`report`, which is therefore a
  strict superset of all of them (pinned in tests/test_telemetry.py).
- **timeline.py** — :class:`StepTimeline`: ``fit()`` attributes every
  step's wall time across data-wait / H2D / compile / device-step /
  metric-sync / callbacks phases, each a span of trace.py, and the
  fused step records XLA cost-analysis bytes-accessed from the
  already-compiled program — the live bytes, flops and
  arithmetic-intensity gauges (counts XLA gives; no time enters them).
- **export.py** — with ``MXTPU_TELEMETRY_DIR`` set: rotating JSONL
  event log (train-step milestones, serving batches, checkpoint and
  compile-cache events), periodic atomic report snapshots, and a
  Prometheus-style text rendering. ``tools/telemetry.py`` tails,
  summarizes, and diffs the exports; ``diff --gate-bytes`` is the
  reusable bytes-accessed regression gate.
- **trace.py** (round 14) — the span primitive: one clock read per
  interval feeds its registry aggregate always and, while tracing is on
  (``MXTPU_TRACE_DIR`` set or a ``jax.profiler`` trace running), a
  bounded ring of spans with trace/span/parent ids (serving request ->
  batch -> bucket; fit -> step -> phase -> pipeline stage) and a
  ``mx:<cat>/<name>`` annotation on the profiler's clock; the ring
  exports as Chrome trace-event JSON under ``MXTPU_TRACE_DIR``.
- **memory.py** (round 14) — per-program HBM accounting read off every
  compiled executable's ``memory_analysis()``: ``mx.memory_report()``,
  ``mem::`` gauges, and the ``--gate-peak-mem`` CI gate's input.

Everything here is observability: failures count and log, they never
take down the training step or the serving loop.
"""
from __future__ import annotations

from . import registry
from . import timeline
from . import export
from . import trace
from . import memory
from .registry import (Counter, Gauge, Timer, Histogram, counter, gauge,
                       timer, histogram, snapshot, report, collect,
                       register_collector, reset, remove)
from .timeline import (StepTimeline, current, peak_hbm_bytes_s,
                       set_step_cost)
from .export import (enabled, telemetry_dir, emit_event, export_snapshot,
                     render_prometheus, read_events)
from .memory import memory_report

__all__ = ["registry", "timeline", "export", "trace", "memory",
           "Counter", "Gauge", "Timer", "Histogram",
           "counter", "gauge", "timer", "histogram",
           "snapshot", "report", "collect", "register_collector", "reset",
           "remove",
           "StepTimeline", "current", "peak_hbm_bytes_s", "set_step_cost",
           "enabled", "telemetry_dir", "emit_event", "export_snapshot",
           "render_prometheus", "read_events", "memory_report"]
