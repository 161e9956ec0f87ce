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
- set-up: ``setup/bind``, ``setup/init_optimizer`` (``Module``);
  ``setup/materialize``, ``setup/init_state``, ``setup/build_step``
  (inside ``TrainStep``'s first ``step/compile``);
  ``pass/apply:<pass>`` and ``pass/gate:<pass>`` -> ``compile/lower``,
  ``compile/compile``; ``compile/acquire:<program>`` ->
  ``compile/load|compile|serialize``; ``compile/jax:<phase>:<function>``
  for every trace, lowering and compile or cache load JAX makes
  (``compile/registry.py``'s listener: JAX says so when the interval is
  over, hence :func:`record_span` and no annotation; under
  ``MXTPU_TRACE_DIR`` alone, :func:`exporting`),
- serving: ``serving:request`` (submit -> complete, measured across
  threads, hence :func:`record_span`), ``serving:batch``
  (DynamicBatcher micro-batch; its args carry the member request trace
  ids), ``serving:bucket<b>`` (Predictor dispatch, under the batch),
- data pipeline: ``data:source``/``data:decode``/``data:stage`` on the
  pipeline's worker threads and ``data:wait`` (wait) on the consumer's,
  linked to the fit root via :meth:`DataPipeline.set_trace`.

The device's half of "where did this step spend its time" is the step
program's own: ``jax.named_scope("mx_...")`` where the work is emitted
(the vocabulary is in ``docs/observability.md``), and :func:`scope_table`
puts those names on a compiled program's instructions, which is what a
profiler trace calls its device events. Scopes run while a step is
traced, never per step; the table is built when somebody asks.

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

import contextlib
import itertools
import json
import os
import re
import threading
import time
import weakref

from . import registry

__all__ = ["enabled", "exporting", "trace_dir", "new_trace_id", "new_span_id",
           "span", "current", "record_span", "spans", "export_trace",
           "trace_files", "read_trace", "reset", "hlo_scopes",
           "scope_table", "note_program", "shapes_of", "XLA_NAMED"]

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


def exporting():
    """``MXTPU_TRACE_DIR`` is set: the ring will be written out as a
    Chrome trace. The guard of a producer whose records are for that
    file alone (``compile/registry.py``'s ``jax:*`` spans, which have no
    annotation on a profiler's clock and no reader in the ring)."""
    # the registered variable is a plain string: read where it lives,
    # a typed config.get costs as much as the span it guards
    return bool(os.environ.get("MXTPU_TRACE_DIR"))


def enabled():
    """Tracing is on: ``MXTPU_TRACE_DIR`` is set, or a ``jax.profiler``
    trace is running in this process. The producers' guard: one
    attribute read and one env read, no path construction."""
    return _profiler_running() or exporting()


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
_HLO_LOOP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? while\(", re.M)

#: instructions XLA names itself, dropping the ``op_name`` they were
#: traced under (``lax.ragged_dot`` lowered for a TPU is XLA's own grouped
#: kernel: ``ragged-dot-none.2``, ``ragged-dot-metadata``), by the start
#: of their name, and the scope this program files them under: its one
#: ragged product is the pooled experts' (``ops.seq.pooled_gated_product``,
#: inside ``mx_moe_gmm_up`` / ``mx_moe_gmm_down``, at the shapes its own
#: kernels do not take). The compiled text keeps nothing of their call
#: site, so this is a stated fallback.
XLA_NAMED = {"ragged-dot": "mx_moe_gmm_ragged"}


def hlo_scopes(hlo_text, prefix="mx_", path=False, loops=True,
               xla_named=None):
    """From a compiled program's HLO text, ``{instruction name: scope}``
    for every instruction whose ``op_name`` passes through a
    ``jax.named_scope`` that starts with ``prefix`` (the innermost such
    scope; a backward instruction carries its forward scope's name inside
    ``transpose(jvp(...))``). With ``path`` the scope is every such scope
    from the outermost in, joined by ``/`` (``mx_loop_body/mx_attn_fwd``
    for the attention inside a scanned body). The device trace names its
    events by instruction, so this is what puts the program's own names
    on them.

    ``loops=False`` leaves the ``while`` instructions out: a trace holds
    an event for a loop and one for each operation of its every trip, so
    a reader that adds up or unites events wants the trips alone.
    ``xla_named`` (``{start of an instruction's name: scope}``, as
    :data:`XLA_NAMED`) files the instructions XLA names itself."""
    # a path component of its own or inside jvp(...)/transpose(...); the
    # jitted function's own name, "jit(mx_train_step)", is no scope
    scope = re.compile(r"(?<!jit\()\b(" + re.escape(prefix) + r"\w+)")
    out = {}
    for name, op_name in _HLO_INSTRUCTION.findall(hlo_text):
        found = scope.findall(op_name)
        if found:
            out[name] = "/".join(found) if path else found[-1]
        elif xla_named:
            for start, filed in xla_named.items():
                if name.startswith(start):
                    out[name] = filed
    if not loops:
        for name in _HLO_LOOP.findall(hlo_text):
            out.pop(name, None)
    return out


# ---------------------------------------------------------------------------
# the scope table of an acquired program
# ---------------------------------------------------------------------------
_programs = {}       # XLA module name -> _Program, the one acquired last


@contextlib.contextmanager
def _no_compile_cache():
    """JAX's persistent compilation cache neither read nor written
    inside (the ``.mxprog`` entries of ``compile/cache.py`` are not on a
    ``Lowered.compile()``'s way at all)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # the cache is decided on once
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


class _Program:
    """What the scope table of one acquired program is built from, held
    from the acquisition until the table is built: the traced program
    (``jit(f).trace(*shapes)``, or a function that gives it: the jaxpr
    and the arguments' shapes, no buffer and no Python closure) and,
    weakly, the executable where the step holds one. The record of the
    program acquired last under a name outlives its step, so that a
    trace can be read after the step was closed."""

    def __init__(self, name, traced, executable=None):
        self.name = name
        self._traced = traced
        self._executable = None if executable is None \
            else weakref.ref(executable)
        self._table = None
        # the executable was another tree's; None: not known
        self.stale = None

    def table(self):
        """``{HLO instruction name: scope path}`` (see
        :func:`scope_table`), built at the first call."""
        if self._table is None:
            t0 = _now()
            self._table = self._build()
            self._traced = self._executable = None
            registry.gauge("trace::scope_table_s").set(_now() - t0)
        return self._table

    def _build(self):
        traced = self._traced if hasattr(self._traced, "lower") \
            else self._traced()
        exe = self._executable and self._executable()
        if traced is None:      # an AOT entry nobody can trace again
            return _table_of(exe.as_text()) if exe is not None else {}
        lowered = traced.lower()
        if exe is None:
            # the lowering the jit's own call compiled, which still holds
            # that executable; else JAX's cache may
            exe = lowered.compile()
        text = exe.as_text()
        # op_name is metadata, which JAX strips before it hashes a program
        # for its cache: an executable found there may have been compiled
        # from another tree, before a scope of this one existed. A
        # program's text lists the Python frames it was traced through
        # (files, functions, lines); where the executable's list is not
        # this lowering's, or either text shows none, its names are taken
        # to be somebody else's
        mine = _frames(lowered.as_text(dialect="hlo", debug_info=True))
        self.stale = mine is None or _frames(text) != mine
        if not self.stale:
            return _table_of(text)
        # compile the same program once more beside the caches: the
        # optimised program is the same, so its instructions have the
        # cached one's names, which is what a trace's events are matched
        # by
        registry.gauge("trace::scope_table_recompiles").inc()
        with _no_compile_cache():
            fresh = lowered.compile(compiler_options=_COMPILE_AGAIN)
        return _table_of(fresh.as_text())


#: ``Lowered.compile()`` hands back the executable it has, and JAX keeps the
#: executable of an equal module in memory; given an option it compiles.
#: This one is at its default and dumps nothing (no ``xla_dump_to``).
_COMPILE_AGAIN = {"xla_dump_hlo_as_text": False}

_FRAMES = re.compile(r"^FileNames\n.*?^StackFrames\n.*?\n\n", re.M | re.S)
_FRAME_FILE = re.compile(r'^(\d+) ".*?([^/"]+)"$', re.M)


def _frames(hlo_text):
    """The frame tables at the head of a program's HLO text (``FileNames``
    to ``StackFrames``), each file by its last component: two checkouts
    of one tree are one source. None where the text shows no such table
    (another XLA's format): the caller then trusts no name."""
    found = _FRAMES.search(hlo_text)
    return _FRAME_FILE.sub(r'\1 "\2"', found.group(0)) if found else None


def _table_of(text):
    return hlo_scopes(text, path=True, loops=False, xla_named=XLA_NAMED)


def shapes_of(args):
    """``args`` with every array replaced by its shape and dtype
    (``jax.ShapeDtypeStruct``): what a program can be traced and lowered
    from again once its arguments' buffers are donated. No sharding: a
    program over a mesh states its own (``in_shardings``), and one
    without lowers for the default device exactly as its first call did,
    so that JAX's cache finds that call's executable."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") and hasattr(a, "dtype") else a, args)


def note_program(name, traced, executable=None):
    """The one stored reference of an acquisition: ``name`` is the XLA
    module's (``jit_mx_train_step``), the rest :class:`_Program`'s.
    Returns the record whose ``table()`` the step object hands out; the
    record acquired last under a name answers :func:`scope_table`."""
    prog = _programs[name] = _Program(name, traced, executable)
    return prog


def scope_table(name):
    """``{HLO instruction name: scope path}`` of the program acquired
    last under the XLA module name ``name`` (``jit_mx_train_step``:
    ``parallel.TrainStep``; ``jit_mx_fused_step``: ``Module``'s fused
    step), or None where none was: every ``mx_*`` scope of an
    instruction from the outermost in, joined by ``/``, without the
    ``while`` instructions and with XLA's own grouped kernel under
    :data:`XLA_NAMED`'s name. A profiler trace names its device events by
    instruction, so this table puts the program's names on them
    (``TrainStep.scope_table()`` / ``FusedSymbolStep.scope_table()`` are
    the same table by the object).

    Built when first asked for, never on a step's path: the program is
    lowered again from the acquisition's traced form (no trace is taken
    twice) and its executable's text read, compiled or loaded from
    JAX's cache where the step holds none. **The table knows whose
    names it carries**: JAX strips names before it hashes a program for
    its cache, so an executable found there may have been compiled from
    another tree, before a scope of this one existed. Where the Python
    frames listed at the head of the executable's text (files,
    functions, lines) are not this lowering's, or a text lists none,
    the table is read from one compile beside the caches instead
    (counted by the gauge
    ``trace::scope_table_recompiles``; seconds to a minute, once a
    process). The gauge ``trace::scope_table_s`` holds the seconds the
    table built last took."""
    prog = _programs.get(name)
    return None if prog is None else prog.table()
