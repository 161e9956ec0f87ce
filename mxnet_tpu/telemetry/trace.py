"""The span primitive: one clock read per interval, three sinks.

The metrics layer (registry/timeline/export) answers "how long do steps
take on average"; this module answers "where did THIS step / THIS
serving request spend its time". :func:`span` is where the program
reads a clock for an interval. A *span* is one named interval of one
``kind``: ``work``, or ``wait`` when this thread is blocked on a queue
or on the device. From one ``t0``/``dur`` it feeds

- its **aggregate** in the registry, always: the Timer
  ``prof::<cat>::<name>`` (what ``mx.telemetry.report()`` and
  ``mx.profiler.dumps()`` show), or the caller's own where it keeps one
  (StepTimeline's per-step ``step::phase::<name>_s`` histograms);
- the **ring**, while tracing is on: name, cat, kind, start, end,
  ``trace_id`` (the request or fit run it belongs to), ``span_id`` and
  ``parent_id`` — the innermost open span on this thread, or an
  explicit parent, so a serving request submitted on a client thread,
  coalesced on the batcher thread and dispatched to a Predictor bucket
  reconstructs as one tree across three threads;
- a ``jax.profiler.TraceAnnotation`` named ``mx:<cat>/<name>``, while
  tracing is on: one prefix, so the program's spans lie on the
  profiler's clock beside ``XLA Ops``.

**Tracing is on** (:func:`enabled`) when ``MXTPU_TRACE_DIR`` is set or
a ``jax.profiler`` trace is running in this process. ``fit()`` and
``TrainStep`` ask once per step and hand the answer to their spans
(``on=``); any other span asks per call. Producers:

- training: ``train/fit:<symbol>`` (the run root) -> ``step/step`` ->
  ``step/data_wait`` (wait), ``step/h2d_stage``, ``step/compile``,
  ``step/device_step`` with the fused step's ``step/dispatch`` inside,
  ``step/metric_ft_sync``, ``step/callbacks``, and ``step/device_read``
  (wait) where the loop blocks on the device; ``TrainStep`` opens
  ``step/step`` with ``step/compile`` / ``step/h2d_stage`` /
  ``step/dispatch`` inside on every call,
- set-up: ``setup/bind``, ``setup/init_optimizer``;
  ``pass/apply:<pass>`` and ``pass/gate:<pass>`` -> ``compile/lower``,
  ``compile/compile``; ``compile/acquire:<program>`` ->
  ``compile/load|compile|serialize``,
- serving: ``serving:request`` (submit -> complete, measured across
  threads, hence :func:`record_span`), ``serving:batch``
  (DynamicBatcher micro-batch; its args carry the member request trace
  ids), ``serving:bucket<b>`` (Predictor dispatch, under the batch),
- data pipeline: ``data:source``/``data:decode``/``data:stage`` on the
  pipeline's worker threads and ``data:wait`` (wait) on the consumer's,
  linked to the fit root via :meth:`DataPipeline.set_trace`.

Hot-path contract (the same one the metrics layer keeps): recording a
completed span is one tuple write into a preallocated ring under a
short lock — no I/O, no syncs, no unbounded growth (``MXTPU_TRACE_RING``
caps it; overwrites count ``trace::dropped``). With tracing off a span
is its two clock reads and its aggregate, and nothing reaches the ring.
Export (:func:`export_trace`, also run at StepTimeline close and
DynamicBatcher stop) belongs to ``MXTPU_TRACE_DIR`` alone: it writes
``trace-<pid>-NNNNN.json`` in Chrome trace-event format — ``X``
(complete) events with ``ts``/``dur`` in microseconds on one monotonic
clock — loadable directly in Perfetto or chrome://tracing, and empties
the ring. Under a bare profiler trace nothing empties it, so a reader
finds the window's spans in :func:`spans` afterwards.
"""
from __future__ import annotations

import itertools
import json
import os
import re
import threading
import time

from . import registry

__all__ = ["enabled", "trace_dir", "new_trace_id", "new_span_id",
           "span", "current", "record_span", "spans", "export_trace",
           "trace_files", "read_trace", "reset", "hlo_scopes"]

# the clock of every span; record_span's callers (intervals measured
# across threads) read their t0 from it too
_now = time.perf_counter

# one monotonic origin for every ts this process emits: Chrome trace
# viewers only need ordering/containment, not wall-clock epoch
_EPOCH = _now()

_lock = threading.Lock()
_ring = []           # preallocated to capacity on first record
_cap = 0
_count = 0           # spans ever recorded; live slot i = (i % _cap)
_exports = 0
_tls = threading.local()
_id_seq = itertools.count(1)
_thread_names = {}   # tid -> name at first record (for "M" metadata)

_PID_TAG = None      # cached f"{pid:x}" id prefix (reset on fork-safety)
_PROFILE_STATE = None  # jax's profiler state; False = not found
_TRACE_ANN = None    # jax.profiler.TraceAnnotation; False = not found


def trace_dir():
    """The effective trace export directory for THIS process (rank-
    qualified in multi-process runs, like the event log), or ''."""
    from .. import config
    base = str(config.get("MXTPU_TRACE_DIR") or "")
    if not base:
        return ""
    from .export import rank_subdir
    return rank_subdir(base)


def _profiler_running():
    """True while a ``jax.profiler`` trace runs in this process. jax
    0.9.0 keeps the session in ``jax._src.profiler._profile_state``; a
    jax that moves it turns this off, never the program."""
    global _PROFILE_STATE
    if _PROFILE_STATE is None:
        try:
            from jax._src import profiler as _jp
            _jp._profile_state.profile_session
            _PROFILE_STATE = _jp._profile_state
        except Exception:
            _PROFILE_STATE = False
    return bool(_PROFILE_STATE) and \
        _PROFILE_STATE.profile_session is not None


def enabled():
    """Tracing is on: ``MXTPU_TRACE_DIR`` is set, or a ``jax.profiler``
    trace is running in this process. The producers' guard: one
    attribute read and one env read, no path construction."""
    # the registered variable is a plain string: read where it lives,
    # a typed config.get costs as much as the span it guards
    return _profiler_running() or bool(os.environ.get("MXTPU_TRACE_DIR"))


def _pid_tag():
    global _PID_TAG
    pid = os.getpid()
    if _PID_TAG is None or _PID_TAG[0] != pid:
        _PID_TAG = (pid, f"{pid:x}")
    return _PID_TAG[1]


def new_trace_id():
    """A process-unique trace id (pid-prefixed so rank files merge
    without collisions)."""
    return f"t{_pid_tag()}-{next(_id_seq):x}"


def new_span_id():
    return f"s{_pid_tag()}-{next(_id_seq):x}"


def record_span(name, cat, t0, dur_s, trace_id=None, span_id=None,
                parent_id=None, args=None, tid=None, kind="work"):
    """Record one COMPLETED interval into the ring: the ring sink of
    :func:`span`, and the entry for intervals measured across threads
    (a serving request's submit -> complete), which already hold a
    measured ``t0``/``dur``. ``t0`` is a ``time.perf_counter()``
    reading; never raises and never blocks beyond the ring lock."""
    global _ring, _cap, _count
    try:
        ts_us = (t0 - _EPOCH) * 1e6
        rec = (ts_us, max(0.0, dur_s) * 1e6, str(name), str(cat),
               tid if tid is not None else threading.get_ident(),
               trace_id, span_id, parent_id, args, kind)
        with _lock:
            if _cap == 0:
                from .. import config
                _cap = max(64, int(config.get("MXTPU_TRACE_RING")))
                _ring = [None] * _cap
            _ring[_count % _cap] = rec
            _count += 1
            t = rec[4]
            if t not in _thread_names:
                _thread_names[t] = threading.current_thread().name
    except Exception:
        pass


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _annotate(label):
    """An entered ``jax.profiler.TraceAnnotation`` (the one place the
    program makes one), or None where jax has none to give."""
    global _TRACE_ANN
    try:
        if _TRACE_ANN is None:
            import jax
            _TRACE_ANN = jax.profiler.TraceAnnotation
        ann = _TRACE_ANN(label)
        ann.__enter__()
        return ann
    except Exception:
        _TRACE_ANN = _TRACE_ANN or False
        return None


class _Span:
    """One interval: a context manager, or :meth:`start` / :meth:`stop`
    where the interval does not fit a ``with`` block (the profiler
    facade's tasks, StepTimeline's phases). One clock read at each end;
    ``dur`` holds the seconds once stopped, and :meth:`stop` returns
    them."""

    __slots__ = ("name", "cat", "kind", "trace_id", "span_id",
                 "parent_id", "args", "dur", "_agg", "_on", "_scope",
                 "_t0", "_ann")

    def __init__(self, name, cat, kind, trace_id, parent_id, args, agg,
                 on, scope):
        self.name = name
        self.cat = cat
        self.kind = kind
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.span_id = None
        self.args = args
        self.dur = 0.0
        self._agg = agg
        self._on = on
        self._scope = scope
        self._t0 = 0.0
        self._ann = None

    def start(self):
        if self._on is None:
            self._on = enabled()
        if self._on:
            self.span_id = new_span_id()
            st = _stack()
            if st:
                top = st[-1]
                if self.parent_id is None:
                    self.parent_id = top.span_id
                if self.trace_id is None:
                    self.trace_id = top.trace_id
            if self.trace_id is None:
                self.trace_id = new_trace_id()
            if self._scope:
                st.append(self)
            self._ann = _annotate(f"mx:{self.cat}/{self.name}")
        self._t0 = _now()
        return self

    def stop(self):
        dur = self.dur = _now() - self._t0
        agg = self._agg
        if agg is None:
            registry.timer(f"prof::{self.cat}::{self.name}").record(dur)
        elif agg is not False:
            agg(dur)
        if self._on:
            self._on = False          # a second stop() records nothing
            if self._ann is not None:
                try:
                    self._ann.__exit__(None, None, None)
                except Exception:
                    pass
                self._ann = None
            st = _stack() if self._scope else ()
            if st and st[-1] is self:
                st.pop()
            elif self in st:          # mismatched exits must not wedge TLS
                st.remove(self)
            record_span(self.name, self.cat, self._t0, dur,
                        trace_id=self.trace_id, span_id=self.span_id,
                        parent_id=self.parent_id, args=self.args,
                        kind=self.kind)
        return dur

    __enter__ = start

    def __exit__(self, *exc):
        self.stop()
        return False


def span(name, cat="host", kind="work", trace=None, parent=None,
         args=None, agg=None, on=None, scope=True):
    """Open an interval (context manager, or ``.start()``/``.stop()``).

    ``kind`` is ``work`` or ``wait`` (this thread is blocked on a queue
    or on the device). ``agg`` is the aggregate the span feeds on every
    exit, tracing on or off: by default the registry Timer
    ``prof::<cat>::<name>``; a callable takes the seconds instead; and
    ``False`` says the caller keeps its own from ``dur`` (StepTimeline's
    per-step phase histograms). ``on`` is the caller's own reading of
    :func:`enabled` where it asks once for several spans; without it
    the span asks. While tracing is on the span also lands in the ring —
    inheriting trace and parent from the innermost open span on this
    thread unless given — and in the profiler's trace as
    ``mx:<cat>/<name>``. ``scope=False`` keeps it from becoming that
    innermost span itself: for an interval that need not end where it
    began or in order (the profiler facade's start()/stop() objects),
    which would otherwise be left on the thread's stack as every later
    span's parent."""
    return _Span(name, cat, kind, trace, parent, args, agg, on, scope)


def current():
    """The innermost open span on this thread, or None."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def spans():
    """The ring's live records, oldest first, as dicts (test/export
    surface; ts/dur in microseconds on the module's monotonic clock)."""
    with _lock:
        if _count <= _cap:
            live = _ring[:_count]
        else:
            head = _count % _cap
            live = _ring[head:] + _ring[:head]
    out = []
    for rec in live:
        if rec is None:
            continue
        (ts, dur, name, cat, tid, trace_id, span_id, parent_id, args,
         kind) = rec
        out.append({"ts": ts, "dur": dur, "name": name, "cat": cat,
                    "kind": kind, "tid": tid, "trace_id": trace_id,
                    "span_id": span_id, "parent_id": parent_id,
                    "args": args})
    out.sort(key=lambda s: s["ts"])
    return out


def dropped():
    """Spans overwritten before export (ring wrapped)."""
    with _lock:
        return max(0, _count - _cap) if _cap else 0


def export_trace(path=None, clear=True):
    """Write the ring as one Chrome trace-event JSON file (``{"trace
    Events": [...]}``, "X" complete events + thread-name metadata) and
    return its path — None when tracing is disabled/empty or the write
    fails (export must never take down the caller). Runs off the hot
    path: StepTimeline.close() and DynamicBatcher.stop() call it, and
    ``clear=True`` empties the ring so back-to-back exports don't
    duplicate spans."""
    global _ring, _count, _exports
    try:
        recs = spans()
        if not recs:
            return None
        d = None
        if path is None:
            d = trace_dir()
            if not d:
                return None
        pid = os.getpid()
        events = [{"ph": "M", "name": "process_name", "pid": pid,
                   "tid": 0, "args": {"name": "mxnet_tpu"}}]
        with _lock:
            names = dict(_thread_names)
        for tid in sorted({r["tid"] for r in recs}):
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid,
                           "args": {"name": names.get(tid, str(tid))}})
        n_dropped = dropped()
        for r in recs:
            args = dict(r["args"] or {})
            for k in ("trace_id", "span_id", "parent_id"):
                if r[k] is not None:
                    args[k] = r[k]
            if r["kind"] != "work":
                args["kind"] = r["kind"]
            events.append({"name": r["name"], "cat": r["cat"],
                           "ph": "X", "ts": round(r["ts"], 3),
                           "dur": round(r["dur"], 3), "pid": pid,
                           "tid": r["tid"], "args": args})
        tree = {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"producer": "mxnet_tpu.telemetry.trace",
                              "dropped_spans": n_dropped}}
        with _lock:
            if path is None:
                _exports += 1
                path = os.path.join(d, f"trace-{pid}-{_exports:05d}.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        from ..base import atomic_write
        with atomic_write(path, mode="w") as f:
            json.dump(tree, f)
        if n_dropped:
            registry.counter("trace::dropped").inc(n_dropped)
        if clear:
            with _lock:
                _count = 0
                _ring = [None] * _cap if _cap else []
        return path
    except Exception:
        try:
            from .. import fault
            fault.count("telemetry.write_errors")
        except Exception:
            pass
        return None


def trace_files(directory=None):
    """Exported trace files, oldest first."""
    import glob
    d = directory or trace_dir()
    if not d:
        return []
    return sorted(glob.glob(os.path.join(d, "trace-*.json")),
                  key=os.path.getmtime)


def read_trace(path):
    """Load one exported file back as its event list (CLI/test
    round-trip helper)."""
    with open(path, encoding="utf-8") as f:
        tree = json.load(f)
    return tree.get("traceEvents", [])


def reset():
    """Empty the ring and the export sequence (between test cases).
    Also drops the allocated capacity so the next record re-reads
    ``MXTPU_TRACE_RING`` — tests resize the ring through this."""
    global _ring, _cap, _count, _exports
    with _lock:
        _cap = 0
        _count = 0
        _exports = 0
        _ring = []
        _thread_names.clear()


_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"", re.M)


def hlo_scopes(hlo_text, prefix="mx_", path=False):
    """From a compiled program's HLO text, ``{instruction name: scope}``
    for every instruction whose ``op_name`` passes through a
    ``jax.named_scope`` that starts with ``prefix`` (the innermost such
    scope; a backward instruction carries its forward scope's name inside
    ``transpose(jvp(...))``). With ``path`` the scope is every such scope
    from the outermost in, joined by ``/`` (``mx_loop_body/mx_attn_fwd``
    for the attention inside a scanned body). The device trace names its
    events by instruction, so this is what puts the program's own names
    on them."""
    # a path component of its own or inside jvp(...)/transpose(...); the
    # jitted function's own name, "jit(mx_train_step)", is no scope
    scope = re.compile(r"(?<!jit\()\b(" + re.escape(prefix) + r"\w+)")
    out = {}
    for name, op_name in _HLO_INSTRUCTION.findall(hlo_text):
        found = scope.findall(op_name)
        if found:
            out[name] = "/".join(found) if path else found[-1]
    return out
