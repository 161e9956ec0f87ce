"""StepTimeline: where does a training step's wall time and byte budget go?

The timeline attributes a step's *measured seconds per phase* on the
host and records XLA's *bytes and FLOPs per step* for the compiled
program (counts, not device times: those are the benchmark's, PERF.md
section 3):

- **Phase attribution**: ``fit()`` opens one timeline for the run;
  each step's wall time splits across ``data_wait`` (blocked on the
  host input pipeline), ``h2d_stage`` (device_put of the feed),
  ``compile`` (program acquisition — trace/compile or AOT load),
  ``device_step`` (forward_backward + update; inside it the fused
  step's ``dispatch``, the enqueue of the compiled program),
  ``metric_ft_sync`` (metric update + fault-guard bookkeeping),
  ``callbacks`` (the batch-end callbacks) and ``device_read`` (a wait:
  the loop blocked on a value from the device), with the remainder
  reported honestly as ``unattributed``. Phases NEST: an inner phase's
  time is subtracted from its enclosing phase's self-time, so the
  self-times sum to (at most) the step wall time by construction —
  the fused step attributes its h2d/compile/dispatch from *inside*
  ``fit()``'s outer ``device_step`` span without double counting.
  The run, every step and every phase are spans of the one primitive
  (trace.py): the timeline reads no clock of its own.
- **Byte attribution**: the fused step records XLA cost-analysis
  ``bytes accessed`` / ``flops`` from the *already compiled* program
  (no second compile) into ``step::bytes_accessed`` / ``step::flops``
  gauges and the ``step::arithmetic_intensity_flop_b`` gauge derived
  from them — the measured-objective posture of the fusion pass (r6's
  "strictly fewer bytes" pin), generalized into gauges every run
  exports and ``tools/telemetry.py diff --gate-bytes`` can gate on.

Everything lands in the telemetry registry under ``step::`` (histograms
``step::wall_s``, ``step::phase::<name>_s``) and, when
``MXTPU_TELEMETRY_DIR`` is set, as ``train_step`` milestone events and
periodic snapshots through the durable exporter (export.py).
"""
from __future__ import annotations

import threading

from . import registry
from . import trace as _trace

__all__ = ["StepTimeline", "current", "phase", "null_phase",
           "peak_hbm_bytes_s", "set_step_cost", "PHASES"]

PHASES = ("data_wait", "h2d_stage", "compile", "device_step", "dispatch",
          "metric_ft_sync", "callbacks", "device_read")
# the phases in which this thread is blocked: their spans' kind
_WAITS = frozenset({"data_wait", "device_read"})

# HBM GB/s per chip (public spec sheets); chip_smoke.py reads this
# table through peak_hbm_bytes_s.
_PEAK_HBM_GBS = {
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5p": 2765.0,
    "TPU v5": 2765.0,
    "TPU v4 lite": 614.0,
    "TPU v4": 1228.0,
    "TPU v3": 900.0,
    "TPU v2": 700.0,
}


def peak_hbm_bytes_s(device=None) -> float:
    """Peak HBM bytes/s for ``device`` (default: jax.devices()[0]);
    0.0 when unknown (e.g. the CPU proxy)."""
    if device is None:
        try:
            import jax
            device = jax.devices()[0]
        except Exception:
            return 0.0
    kind = getattr(device, "device_kind", "")
    for k, v in _PEAK_HBM_GBS.items():
        if kind.startswith(k):
            return v * 1e9
    return 0.0


def set_step_cost(flops=None, bytes_accessed=None):
    """THE write point for the ``step::`` cost gauges (``flops``,
    ``bytes_accessed``, ``arithmetic_intensity_flop_b``) — the fused
    step's ``_note_cost`` and :meth:`StepTimeline.note_cost` both
    delegate here so the gauge names, guards, and intensity formula
    can never drift apart. Non-positive / unparseable values (some
    backends report -1 for unavailable) leave the gauges untouched.
    Returns the ``(flops, bytes)`` floats recorded (None where not)."""
    def _pos(v):
        try:
            v = float(v)
        except (TypeError, ValueError):
            return None
        return v if v > 0 else None

    flops, by = _pos(flops), _pos(bytes_accessed)
    if flops:
        registry.gauge("step::flops").set(flops)
    if by:
        registry.gauge("step::bytes_accessed").set(by)
    if flops and by:
        registry.gauge("step::arithmetic_intensity_flop_b").set(
            flops / by)
    return flops, by


class _Phase:
    """Context manager for one phase span; re-entrant across steps
    (the timeline hands out one object per phase name)."""

    __slots__ = ("_tl", "name")

    def __init__(self, tl, name):
        self._tl = tl
        self.name = name

    def __enter__(self):
        self._tl._enter(self.name)
        return self

    def __exit__(self, *exc):
        self._tl._exit()


class _NullPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


_NULL = _NullPhase()


def null_phase():
    return _NULL


# the active timeline (one training loop per process; the fused step
# looks it up per step — two attribute reads when telemetry is idle).
# Pinned to the thread that activated it: the _stack/_acc bookkeeping
# is deliberately lock-free for the hot path, so a DIFFERENT thread
# (a second fit(), a serving loop driving a fused step) must see None
# and attribute nothing rather than corrupt the owner's span stack
_current = None
_current_tid = None


def current():
    if _current is not None and \
            threading.get_ident() == _current_tid:
        return _current
    return None


def phase(name):
    """``current().phase(name)``, or the no-op phase on a thread that
    runs no timeline: for a site that opens one phase."""
    tl = current()
    return tl.phase(name) if tl is not None else _NULL


class StepTimeline:
    """Per-step wall-time attribution for one training run.

    Usage (what ``fit()`` does)::

        tl = StepTimeline(name="fit:resnet").activate()
        try:
            for batch ...:
                tl.step_start()
                with tl.phase("device_step"):
                    ...   # inner code may open nested phases
                with tl.phase("data_wait"):
                    next_batch = next(it)
                tl.step_end()
        finally:
            tl.close()

    Nested phases subtract from their parent's self-time, so the
    recorded phase self-times sum to at most the measured step wall
    time (the gap is ``unattributed``) — the acceptance pin is that
    the named phases cover >= 90% of the wall on the CPU proxy.
    """

    def __init__(self, name="train"):
        self.name = name
        self.steps = 0
        self._stack = []        # open phases: [name, span, child_s]
        self._acc = {}          # this step's per-phase self seconds
        self._flops = None
        self._bytes = None
        self._phases = {}       # name -> _Phase (reused, no per-step alloc)
        self._wall_h = registry.histogram("step::wall_s")
        self._steps_c = registry.counter("step::steps")
        from .. import config
        self._event_every = max(1, int(
            config.get("MXTPU_TELEMETRY_EVENT_STEPS")))
        self._snapshot_every = int(
            config.get("MXTPU_TELEMETRY_SNAPSHOT_STEPS"))
        self._snap_thread = None
        # the run, its steps and their phases are spans of the one
        # primitive (telemetry/trace.py) on one trace, each the child
        # of the innermost span open on this thread. Whether tracing is
        # on is asked once per step, not per phase
        self._trace_on = False
        self._trace_id = None    # one trace per run (fit/epoch loop)
        self._root = None        # the run's span ("fit:<name>")
        self._step = None        # the open step's span

    # -- lifecycle ------------------------------------------------------------
    def activate(self):
        """Install as the current timeline for THIS thread (what the
        fused step attributes into; other threads see None)."""
        global _current, _current_tid
        _current = self
        _current_tid = threading.get_ident()
        self._trace_on = _trace.enabled()
        self._root = _trace.span(self.name, "train", agg=False,
                                 on=self._trace_on).start()
        self._trace_id = self._root.trace_id
        return self

    @property
    def trace_id(self):
        """This run's trace id (None unless tracing) — what fit() hands
        the data pipeline so stage spans link to the run root."""
        return self._trace_id

    @property
    def root_span_id(self):
        return self._root.span_id if self._root is not None else None

    def close(self):
        """Deactivate; flush a final snapshot + event when exporting."""
        global _current, _current_tid
        if _current is self:
            _current = None
            _current_tid = None
        if self._step is not None:
            # the loop raised inside a step: its span must not stay
            # open on this thread's stack
            self._step.stop()
            self._step = None
        if self._root is not None:
            self._root.args = {"steps": self.steps}
            self._root.stop()
            self._root = None
        if _trace.enabled():
            _trace.export_trace()
        from . import export
        if export.enabled():
            export.emit_event("timeline_close", name=self.name,
                              steps=self.steps)
            if self._snap_thread is not None:
                self._snap_thread.join(timeout=30)
            export.export_snapshot(tag=f"{self.name}-final")

    # -- phases ---------------------------------------------------------------
    def phase(self, name):
        p = self._phases.get(name)
        if p is None:
            p = self._phases[name] = _Phase(self, name)
        return p

    def _enter(self, name):
        sp = _trace.span(name, "step",
                         kind="wait" if name in _WAITS else "work",
                         trace=self._trace_id, agg=False,
                         on=self._trace_on).start()
        self._stack.append([name, sp, 0.0])

    def _exit(self):
        if not self._stack:      # defensive: never raise out of a step
            return
        name, sp, child = self._stack.pop()
        dur = sp.stop()
        self._acc[name] = self._acc.get(name, 0.0) + max(0.0, dur - child)
        if self._stack:
            self._stack[-1][2] += dur

    # -- steps ----------------------------------------------------------------
    def step_start(self):
        """Open a step's wall clock. A no-op while a step is already
        open: ``fit()`` opens the first step of an epoch BEFORE the
        epoch-start batch fetch so that (often epoch-heaviest) data
        wait is attributed to the first step rather than discarded —
        the loop's per-batch step_start then must not reset it."""
        if self._step is not None:
            return
        self._trace_on = _trace.enabled()
        self._acc = {}
        self._stack = []
        self._step = _trace.span("step", "step", trace=self._trace_id,
                                 args={"step": self.steps + 1},
                                 agg=False, on=self._trace_on).start()
        if self._trace_id is None:
            # tracing came on after activate(): the run's later steps
            # share the first traced step's trace
            self._trace_id = self._step.trace_id

    def note_cost(self, flops=None, bytes_accessed=None):
        """Record the compiled step program's XLA cost analysis (called
        by the fused step once per program acquisition — the numbers
        come from the already-compiled executable, never a re-lower).
        A program reporting only one half pairs with the other half
        already on record, so the intensity gauge stays live."""
        f, b = set_step_cost(flops=flops, bytes_accessed=bytes_accessed)
        if f:
            self._flops = f
        if b:
            self._bytes = b
        if (f or b) and not (f and b):
            set_step_cost(flops=self._flops, bytes_accessed=self._bytes)

    def step_end(self, **event_fields):
        """Close one step: record wall + per-phase histograms, and
        (exporter on) emit milestone events / periodic snapshots."""
        if self._step is None:
            return None
        wall = self._step.stop()
        self._step = None
        self.steps += 1
        self._steps_c.inc()
        self._wall_h.observe(wall)
        attributed = 0.0
        for name, secs in self._acc.items():
            registry.histogram(f"step::phase::{name}_s").observe(secs)
            attributed += secs
        registry.histogram("step::phase::unattributed_s").observe(
            max(0.0, wall - attributed))
        from . import export
        if export.enabled():
            if self.steps == 1 or self.steps % self._event_every == 0:
                export.emit_event(
                    "train_step", name=self.name, step=self.steps,
                    wall_s=round(wall, 6),
                    phases={n: round(s, 6)
                            for n, s in sorted(self._acc.items())},
                    unattributed_s=round(max(0.0, wall - attributed), 6),
                    bytes_accessed=self._bytes, flops=self._flops,
                    **event_fields)
            if self._snapshot_every > 0 and \
                    self.steps % self._snapshot_every == 0:
                # off-thread: a full report (collector locks, the FT
                # guard's device-counter host sync, a whole-tree JSON
                # write) must not stall the training loop between
                # steps — close() joins before the final snapshot. One
                # at a time: if the last is still writing, skip this
                # milestone rather than queue behind it
                t = self._snap_thread
                if t is None or not t.is_alive():
                    self._snap_thread = threading.Thread(
                        target=export.export_snapshot,
                        kwargs={"tag": f"{self.name}-{self.steps}"},
                        daemon=True)
                    self._snap_thread.start()
        return wall
