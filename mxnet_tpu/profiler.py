"""Profiler facade.

TPU-native rebuild of ``mxnet.profiler`` (reference:
python/mxnet/profiler.py:28-400; native src/profiler/profiler.h:256,
aggregate_stats.cc). Two layers:

- **Device tracing** rides ``jax.profiler``: ``set_state('run')`` starts an
  XLA/XPlane trace into the configured directory (viewable in TensorBoard
  or Perfetto), the analog of the reference's chrome://tracing JSON dump.
- **Host-side op aggregation**: the reference's "aggregate stats" table
  (operator name → count, total/min/max ms) is reproduced by timing the
  imperative op dispatch layer. It times host-visible dispatch+sync, not
  per-kernel device time (XLA fuses ops; per-fused-kernel timing lives in
  the trace above).

Since round 11 both host-side stores live in the unified telemetry
registry (``mxnet_tpu/telemetry/registry.py``): span/op aggregates are
registry :class:`~mxnet_tpu.telemetry.registry.Timer` metrics under the
``prof::`` namespace and :class:`Counter` values are registry gauges —
``profiler.counters()``, ``mx.telemetry.report()`` and every subsystem
mirror (``data::wait_s``, ``ft::skipped_steps``, ``compile::…``) read
ONE store, so the mirrors can never drift, and ``dumps(reset=True)`` is
the registry's atomic snapshot-and-clear (no samples lost between the
read and the clear).

Also provides the Domain/Task/Frame/Event/Counter/Marker object API
(reference: profiler.py:151-400) mapped onto jax.profiler traces or
host-side records.
"""
from __future__ import annotations

import atexit
import json
import os
import time
from typing import Dict, Optional

from .telemetry import registry as _treg
from .telemetry import trace as _ttrace

__all__ = ["set_config", "set_state", "dump", "dumps", "pause", "resume",
           "state", "counters", "Domain", "Task", "Frame", "Event",
           "Counter", "Marker"]

_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": False,
    "profile_imperative": False,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": False,
}
_state = "stop"
_trace_dir: Optional[str] = None
_jax_trace_active = False
_paused = False

# aggregate entries live in the telemetry registry as Timers under this
# namespace; dumps() strips it so table keys stay the bare op/span names
_PROF = "prof::"


def _agg_record(name, dt):
    _treg.timer(_PROF + name).record(dt)


def set_config(**kwargs):
    """Configure the profiler (reference: profiler.py:28-59). Recognized
    keys: filename (trace output dir/file), profile_all, profile_symbolic,
    profile_imperative, profile_memory, profile_api, aggregate_stats."""
    for k, v in kwargs.items():
        if k not in _config:
            raise ValueError(f"unknown profiler config key {k!r}")
        _config[k] = v


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Deprecated alias (reference: profiler.py:60)."""
    set_config(filename=filename,
               profile_symbolic="symbolic" in (mode, "all"),
               profile_all=mode == "all")


def state():
    return _state


def set_state(state="stop"):
    """Start/stop profiling (reference: profiler.py:79-91).

    'run' starts a jax.profiler trace (device + host timeline) and turns on
    host-side op aggregation when aggregate_stats is configured."""
    global _state, _trace_dir, _jax_trace_active
    if state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if state == _state:
        return
    if state == "run":
        base = _config["filename"]
        # the reference writes one JSON file; jax.profiler wants a directory
        _trace_dir = base if not base.endswith(".json") else \
            base[:-len(".json")] + "_trace"
        os.makedirs(_trace_dir, exist_ok=True)
        try:
            import jax
            jax.profiler.start_trace(_trace_dir)
            _jax_trace_active = True
        except Exception:
            _jax_trace_active = False  # e.g. a trace is already running
        _install_op_timer()
    else:
        if _jax_trace_active:
            import jax
            try:
                jax.profiler.stop_trace()
            finally:
                _jax_trace_active = False
        _uninstall_op_timer()
    _state = state


def profiler_set_state(state="stop"):
    """Deprecated alias (reference: profiler.py:92)."""
    set_state(state)


def pause():
    """Suspend aggregation inside a run (reference: profiler.py:141)."""
    global _paused
    _paused = True


def resume():
    global _paused
    _paused = False


def dump(finished=True):
    """Stop tracing and flush (reference: profiler.py:105-118). The XPlane
    trace is written when the jax trace stops; the aggregate table is
    returned by ``dumps()``."""
    if _state == "run" and finished:
        set_state("stop")


def dump_profile():
    """Deprecated alias (reference: profiler.py:119)."""
    dump(True)


def aggregate(reset=False):
    """The aggregate table as ``{name: (count, total_s, min_s, max_s)}``
    — one atomic registry snapshot (``reset=True`` clears in the same
    lock acquisition, so a concurrent span/op can never land in neither
    or both windows). Zero-count rows (a handle created but nothing
    recorded this window, e.g. right after a reset) are omitted: they
    carry no data and their undefined min must never render as
    ``inf``."""
    snap = _treg.snapshot(reset=reset, prefix=_PROF,
                          kinds=("timer", "histogram"))
    return {name[len(_PROF):]: (m["count"], m["total"], m["min"], m["max"])
            for name, m in snap.items() if m["count"]}


def dumps(reset=False, format="table"):
    """Return aggregate operator stats (reference: profiler.py:127-140;
    native aggregate_stats.cc table). Rows sort by total time
    descending with the name as tiebreaker (stable across identical
    totals); zero-count rows render 0.0, never ``inf``."""
    rows = sorted(aggregate(reset=reset).items(),
                  key=lambda kv: (-kv[1][1], kv[0]))
    if format == "json":
        out = json.dumps({
            name: {"count": int(c), "total_ms": t * 1e3,
                   "min_ms": mn * 1e3, "max_ms": mx * 1e3}
            for name, (c, t, mn, mx) in rows})
    else:
        lines = [f"{'operator':<32}{'count':>8}{'total_ms':>12}"
                 f"{'avg_ms':>10}{'min_ms':>10}{'max_ms':>10}"]
        for name, (c, t, mn, mx) in rows:
            avg = t / c if c else 0.0
            lines.append(f"{name:<32}{int(c):>8}{t * 1e3:>12.3f}"
                         f"{avg * 1e3:>10.3f}{mn * 1e3:>10.3f}{mx * 1e3:>10.3f}")
        out = "\n".join(lines)
    return out


def trace_dir():
    """Directory holding the last jax.profiler trace (None before a run)."""
    return _trace_dir


# ---------------------------------------------------------------------------
# op-dispatch timing hook (host-side aggregate table)
# ---------------------------------------------------------------------------
def _install_op_timer():
    if not (_config["aggregate_stats"] or _config["profile_imperative"]
            or _config["profile_all"]):
        return
    from .ndarray import ndarray as _nd_mod
    handles: Dict[str, object] = {}   # op name -> registry Timer

    def timing_hook(impl, name, nd_inputs, attrs):
        if _paused:
            return impl(name, nd_inputs, attrs)
        t0 = time.perf_counter()
        out = impl(name, nd_inputs, attrs)
        dt = time.perf_counter() - t0
        h = handles.get(name)
        if h is None:
            h = handles[name] = _treg.timer(_PROF + name)
        h.record(dt)
        return out

    _nd_mod._PROFILE_HOOK = timing_hook


def _uninstall_op_timer():
    from .ndarray import ndarray as _nd_mod
    _nd_mod._PROFILE_HOOK = None


atexit.register(lambda: _state == "run" and set_state("stop"))


# ---------------------------------------------------------------------------
# object API (reference: profiler.py:151-400)
# ---------------------------------------------------------------------------
class Domain:
    """Profiling domain — a namespace for tasks/counters
    (reference: profiler.py:151)."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_event(self, name):
        return Event(name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)

    def __str__(self):
        return self.name


class _Span:
    """start()/stop() facade over the span primitive
    (telemetry/trace.py): the aggregate table's ``<domain>::<name>`` row
    always, and while tracing is on the trace ring and a
    ``mx:<domain>/<name>`` TraceAnnotation on the profiler's clock."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._span = None

    def start(self):
        # scope=False: the reference API lets a task stop out of order
        # or on another thread, so it is never another span's parent
        self._span = _ttrace.span(self.name, str(self.domain),
                                  scope=False).start()
        return self

    def stop(self):
        if self._span is not None:
            self._span.stop()
            self._span = None
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class Task(_Span):
    """(reference: profiler.py:210)"""


class Frame(_Span):
    """(reference: profiler.py:252)"""


class Event(_Span):
    """(reference: profiler.py:294)"""

    def __init__(self, name):
        super().__init__("event", name)


def counters():
    """Last value of every live gauge, keyed ``domain::name`` — how the
    subsystem gauges (``ft::skipped_steps``, ``data::wait_s``,
    ``step::bytes_accessed``…) surface without a trace viewer. Reads
    the one telemetry registry: a :class:`Counter` created here and a
    gauge set anywhere else under the same name are the SAME metric."""
    return {name: m["value"]
            for name, m in _treg.snapshot(kinds=("gauge",)).items()}


class Counter:
    """Numeric counter (reference: profiler.py:330). Backed by a
    telemetry registry gauge named ``domain::name`` — the process-wide
    :func:`counters` table IS the registry's gauge namespace."""

    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        # the registry gauge starts at 0; do NOT zero it here — a
        # second facade over an existing domain::name (the mirrors are
        # the SAME metric) must never erase another producer's value
        self._gauge = _treg.gauge(f"{domain}::{name}")
        if value is not None:
            self.set_value(value)

    @property
    def value(self):
        return self._gauge.get()

    def set_value(self, value):
        self._gauge.set(value)

    def increment(self, delta=1):
        self._gauge.inc(delta)

    def decrement(self, delta=1):
        self._gauge.inc(-delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    """Instant marker (reference: profiler.py:400)."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        # a zero-length record: count advances, totals stay 0 — the
        # reference's instant-marker row in the aggregate table
        _agg_record(f"{self.domain}::{self.name}::marks", 0.0)


def _collect(reset=False):
    """The ``profiler`` subsystem view in ``mx.telemetry.report()``:
    the live gauge table + the aggregate span/op table."""
    return {
        "counters": counters(),
        "aggregate": {
            name: {"count": int(c), "total_s": round(t, 6),
                   "min_s": round(mn, 6), "max_s": round(mx, 6)}
            for name, (c, t, mn, mx) in aggregate(reset=reset).items()},
    }


_treg.register_collector("profiler", _collect)
