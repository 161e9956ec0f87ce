"""Program registry: compile observability + AOT load-or-compile.

Every jitted entry point in the framework registers its programs here
under a canonical :class:`~.key.ProgramKey`:

- ``FusedSymbolStep`` (module/fused.py) and ``serving.Predictor``
  route their compiles through :func:`load_or_compile` — full AOT: a
  populated persistent cache turns a cold start's XLA compile storm
  into file loads (``deserialize_and_load``), skipping tracing AND
  compilation.
- ``Executor`` (executor.py) routes its forward / forward+grad jits
  through :func:`shared_programs` + :class:`JitProgram` — identical
  program keys (e.g. two BucketingModule buckets with identical
  shapes) share ONE jitted callable, traces are counted at trace time,
  and first-call wall time is attributed as compile time.

``compile_report()`` (exported as ``mx.compile_report``) is the one
observability surface: per-program compile wall time, cache
hit/miss/error counters, and the retrace guard — per entry point, how
many times it recompiled and the diverging argument signature (or key
material) that caused it. Every acquisition is a
``compile/acquire:<program>`` span (telemetry/trace.py) with
``compile/load``, ``compile/compile`` and ``compile/serialize`` inside,
so cold-start cost shows up in ``mx.profiler`` dumps (``compile::``
rows) next to the ``serving::``/``ft::`` domains and, in a traced run,
on the profiler's clock. An entry point that stays a plain ``jax.jit``
(``parallel.TrainStep``) reports its first call through
:func:`jit_acquire`.

Beside the programs somebody registers, ``compile_report()["jax"]``
files EVERY program JAX builds in the process by phase (trace, lower,
backend: the XLA compile or the load from JAX's persistent cache) and
by the jitted function's name, with hit or miss a program: one
``jax.monitoring`` listener pair, registered when this module is
imported (the package's import, before anything can compile), feeds a
``prof::jax::<phase>:<name>`` aggregate, a bounded log and, under
``MXTPU_TRACE_DIR``, the ring (for the Chrome export).
"""
from __future__ import annotations

import collections
import contextlib
import logging
import pickle
import re
import threading
import time
import weakref

import jax.monitoring

from ..telemetry import registry as _treg
from ..telemetry import trace as _trace
from .cache import CacheEntryError, default_cache
from .key import arg_signature  # noqa: F401  (re-export for callers)

__all__ = ["ProgramRecord", "load_or_compile", "jit_acquire",
           "shared_programs", "JitProgram", "guarded_loaded_program",
           "note_entry_point", "get_record", "compile_report",
           "donation_supported", "reset"]

logger = logging.getLogger("mxnet_tpu.compile")

_lock = threading.Lock()
_records = {}            # digest -> ProgramRecord
_entry_points = {}       # name -> (ProgramKey, arg_sig)
_retraces = {}           # name -> {"count": int, "events": [...]}
_shared = weakref.WeakValueDictionary()   # digest -> live shared holder
_MAX_RETRACE_EVENTS = 8


class ProgramRecord:
    """Counters for one canonical program (one key digest)."""

    __slots__ = ("name", "kind", "digest", "compiles", "cache_hits",
                 "cache_misses", "cache_errors", "compile_s", "load_s",
                 "serialize_s", "serialized", "arg_sig", "source",
                 "peak_bytes")

    def __init__(self, key):
        self.name = key.name
        self.kind = key.kind
        self.digest = key.digest
        self.compiles = 0        # fresh XLA compiles (traces taken)
        self.cache_hits = 0      # AOT executables loaded from disk
        self.cache_misses = 0    # cache enabled but no entry yet
        self.cache_errors = 0    # corrupt/stale entries rejected
        self.compile_s = 0.0
        self.load_s = 0.0
        self.serialize_s = 0.0
        self.serialized = False  # an entry for this digest was written
        self.arg_sig = None
        self.source = None       # "compile" | "cache" (last acquisition)
        self.peak_bytes = None   # memory_analysis peak (telemetry.memory)

    def as_dict(self):
        out = {
            "name": self.name, "kind": self.kind,
            "digest": self.digest[:10],
            "compiles": self.compiles, "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_errors": self.cache_errors,
            "compile_s": round(self.compile_s, 4),
            "load_s": round(self.load_s, 4),
            "serialized": self.serialized,
            "source": self.source,
        }
        if self.peak_bytes is not None:
            out["peak_bytes"] = self.peak_bytes
        return out


def get_record(key_or_digest):
    digest = getattr(key_or_digest, "digest", key_or_digest)
    with _lock:
        return _records.get(digest)


def _ensure(key):
    with _lock:
        rec = _records.get(key.digest)
        if rec is None:
            rec = _records[key.digest] = ProgramRecord(key)
        return rec


def _restore_record(rec):
    """Re-attach a live record after a ``reset()`` evicted it (long-
    lived JitPrograms keep counting across report windows): the current
    registry entry wins; an evicted record re-registers itself."""
    with _lock:
        cur = _records.get(rec.digest)
        if cur is not None:
            return cur
        _records[rec.digest] = rec
        return rec


def _count(name, delta=1):
    try:
        from .. import fault
        fault.count(name, delta)
    except Exception:
        pass


def _emit_event(key, source, secs):
    """Durable ``compile`` event (telemetry exporter; no-op unless
    MXTPU_TELEMETRY_DIR is set — cold-start storms become visible in
    the fleet event stream, not just the in-process report)."""
    try:
        from ..telemetry import export as _texp
        if _texp.enabled():
            _texp.emit_event("compile", name=key.name, kind=key.kind,
                             digest=key.digest[:10], source=source,
                             secs=round(secs, 4))
    except Exception:
        pass


def _note_memory(key, rec, exe):
    """Record the executable's ``memory_analysis()`` next to its cost
    record (telemetry.memory) — read off the program already in hand,
    never a second compile. Runs on BOTH acquisition paths (fresh
    compile and AOT cache load), so a warm start still reports HBM."""
    try:
        from ..telemetry import memory as _tmem
        stats = _tmem.record(key.name, key.kind, key.digest, exe)
        if stats:
            rec.peak_bytes = stats.get("peak_bytes")
    except Exception:
        pass


def note_entry_point(name, key, sig=None):
    """Retrace guard: one entry point (a fused step, a predictor, an
    executor) acquiring a program under a NEW key or argument signature
    after it already held one is a retrace — record how many and what
    diverged (the ISSUE-facing 'why did this recompile' answer)."""
    with _lock:
        prev = _entry_points.get(name)
        _entry_points[name] = (key, sig)
        if prev is None:
            return
        prev_key, prev_sig = prev
        if prev_key.digest == key.digest and prev_sig == sig:
            return
        ent = _retraces.setdefault(name, {"count": 0, "events": []})
        ent["count"] += 1
        if len(ent["events"]) < _MAX_RETRACE_EVENTS:
            ent["events"].append({
                "changed": key.diff(prev_key),
                "from_sig": _sig_summary(prev_sig),
                "to_sig": _sig_summary(sig),
            })


def _sig_summary(sig, limit=6):
    if sig is None:
        return None
    sig = list(sig)
    body = [f"{tuple(s)}:{d}" for s, d in sig[:limit]]
    if len(sig) > limit:
        body.append(f"...+{len(sig) - limit}")
    return body


def donation_supported(backend=None):
    """Whether the backend implements buffer donation. The CPU backend
    does not and warns per compile — the one place that policy lives
    (serving used to carry a local workaround; bench proxies inherit
    this too)."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    return backend != "cpu"


# ---------------------------------------------------------------------------
# AOT path: FusedSymbolStep / Predictor
# ---------------------------------------------------------------------------
def load_or_compile(key, lower, cache=None):
    """Acquire the compiled executable for ``key``.

    ``lower`` is a thunk returning the ``jax.stages.Lowered`` for the
    program (called only on a cache miss). Returns ``(executable,
    source)`` with source ``"cache"`` (AOT-deserialized, zero fresh
    compiles) or ``"compile"`` (fresh trace+compile; the executable is
    then serialized back into the cache best-effort).

    A corrupt or version-stale entry is rejected LOUDLY — warning log,
    ``cache_errors`` counter, ``compile.cache_corrupt``/``_stale``
    fault counters — and falls back to the fresh compile, which
    overwrites the bad entry. It can never produce a wrong program: the
    digest pins every trace input and the CRC pins the bytes.
    """
    with _trace.span(f"acquire:{key.name}", "compile"):
        return _load_or_compile(key, lower, cache)


def _load_or_compile(key, lower, cache):
    rec = _ensure(key)
    if cache is None:
        cache = default_cache()
    payload = None
    if cache is not None:
        try:
            payload = cache.get(key.digest)
            if payload is None:
                rec.cache_misses += 1
        except CacheEntryError as e:
            rec.cache_errors += 1
            _count(f"compile.cache_{e.reason}")
            logger.warning("%s", e)
            payload = None
    if payload is not None:
        try:
            from jax.experimental import serialize_executable
            with _trace.span("load", "compile") as sp:
                blob, in_tree, out_tree, dev_ids = pickle.loads(payload)
                # load onto the devices the program was compiled for:
                # the default is EVERY device of the backend, and a
                # one-device program loaded eight-wide rejects its
                # first call
                import jax
                by_id = {d.id: d for d in jax.devices()}
                exe = serialize_executable.deserialize_and_load(
                    blob, in_tree, out_tree,
                    execution_devices=[by_id[i] for i in dev_ids])
            load_s = sp.dur
            rec.load_s += load_s
            rec.cache_hits += 1
            rec.source = "cache"
            _count("compile.cache_hits")
            _note_memory(key, rec, exe)
            _refresh_prof_counters()
            _emit_event(key, "cache", load_s)
            return exe, "cache"
        except Exception as e:
            # an entry that validated but won't deserialize (e.g. a
            # pickle from an incompatible stack that slipped the
            # fingerprint) — same loud fallback as corruption
            rec.cache_errors += 1
            _count("compile.cache_deserialize_errors")
            logger.warning(
                "compile-cache entry %s failed to deserialize (%s); "
                "falling back to a fresh compile", key.short, e)
    with _trace.span("compile", "compile") as sp:
        lowered = lower()
        exe = lowered.compile()
    compile_s = sp.dur
    rec.compile_s += compile_s
    rec.compiles += 1
    rec.source = "compile"
    _count("compile.fresh_compiles")
    if cache is not None:
        sp = _trace.span("serialize", "compile").start()
        try:
            from jax.experimental import serialize_executable
            blob, in_tree, out_tree = serialize_executable.serialize(exe)
            dev_ids = [d.id for d in exe._executable
                       ._unloaded_executable.device_list]
            cache.put(key, pickle.dumps(
                (blob, in_tree, out_tree, dev_ids)))
            rec.serialized = True
        except Exception as e:
            # backends without executable serialization (or unpicklable
            # shardings): the program still runs, it just isn't AOT
            # reusable — record why, don't fail the step
            _count("compile.serialize_unsupported")
            logger.debug("compile-cache serialize skipped for %s: %s",
                         key.short, e)
        rec.serialize_s += sp.stop()
    _note_memory(key, rec, exe)
    _refresh_prof_counters()
    _emit_event(key, "compile", compile_s)
    return exe, "compile"


# ---------------------------------------------------------------------------
# plain-jit path: parallel.TrainStep
# ---------------------------------------------------------------------------
# every program JAX builds, by phase and name: the one listener pair
_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # wraps the XLA compile OR the load from JAX's persistent cache
    "/jax/core/compile/backend_compile_duration": "backend",
}
_JAX_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
              "/jax/compilation_cache/cache_misses": "miss"}
_JIT_NAME = re.compile(r"^jit\((.*)\)$")
_MAX_JAX_NAMES = 256     # later names fold into "other"
_MAX_JAX_EVENTS = 4096   # the log; the oldest event goes first
_jax_programs = {}       # name -> _jax_row()
_jax_events = collections.deque(maxlen=_MAX_JAX_EVENTS)
_jax_tls = threading.local()   # .cache: this thread's last hit or miss;
#                                .acquiring / .verdict: jit_acquire's


def _jax_row():
    """What JAX spent on the programs of one name (a jitted function's):
    ``trace_s`` is inclusive of the traces nested in it, ``programs``
    counts the backend events (compiled or loaded)."""
    return {"traces": 0, "trace_s": 0.0, "lower_s": 0.0, "programs": 0,
            "backend_s": 0.0, "cache_hits": 0, "cache_misses": 0}


def _on_jax_cache(event, **kwargs):
    found = _JAX_CACHE.get(event)
    if found is not None:
        _jax_tls.cache = found


def _on_jax_duration(event, duration, fun_name=None, **kwargs):
    phase = _JAX_PHASES.get(event)
    if phase is not None:
        try:
            _file_jax_phase(phase, duration, fun_name)
        except Exception:
            # JAX calls from inside its compile: the record may fail, the
            # user's program may not
            logger.warning("jax %s event of %s not filed", phase, fun_name,
                           exc_info=True)


def _file_jax_phase(phase, duration, fun_name):
    """One phase of one program is over: JAX says so when the interval
    has ended, from inside its trace, lowering or compile. Feeds the
    aggregate ``prof::jax::<phase>:<name>``, the table and the log behind
    ``compile_report()["jax"]`` and, under ``MXTPU_TRACE_DIR``, the ring."""
    end = _trace._now()
    name = _JIT_NAME.sub(r"\1", str(fun_name))
    cache = None
    if phase == "backend":
        # JAX reports a hit or a miss on the compiling thread, inside the
        # interval; neither where it did not ask its persistent cache or
        # kept no entry of a compile under its threshold
        cache = getattr(_jax_tls, "cache", None) or "none"
        _jax_tls.cache = None
        if getattr(_jax_tls, "acquiring", None) == name:
            _jax_tls.verdict = cache
    ts = (end - duration - _trace._EPOCH) * 1e6
    tid = threading.get_ident()
    folded = dropped = False
    with _lock:
        row = _jax_programs.get(name)
        if row is None:
            if len(_jax_programs) >= _MAX_JAX_NAMES:
                name, folded = "other", True
                row = _jax_programs.get(name)
            if row is None:
                row = _jax_programs[name] = _jax_row()
        row[phase + "_s"] += duration
        if phase == "trace":
            row["traces"] += 1
        elif phase == "backend":
            row["programs"] += 1
            row["cache_hits"] += cache == "hit"
            row["cache_misses"] += cache == "miss"
        if phase != "backend":
            # the traces inside this interval ended before it (every jnp
            # function is a jit of its own, and a lowering rule traces
            # some: thousands a step): the log keeps the outermost, the
            # table has counted them all
            aside = []
            while _jax_events and _jax_events[-1][0] >= ts:
                inner = _jax_events.pop()
                if inner[2] != "trace" or inner[4] != tid:
                    aside.append(inner)
            _jax_events.extend(reversed(aside))
        dropped = len(_jax_events) == _MAX_JAX_EVENTS
        _jax_events.append((ts, duration * 1e6, phase, name, tid, cache))
    _treg.timer(f"prof::jax::{phase}:{name}").record(duration)
    if folded:
        _count("compile.jax_names_folded")
    if dropped:
        _count("compile.jax_events_dropped")
    if _trace.exporting():
        # for the Chrome export, beside the step that paid for it; no
        # TraceAnnotation: the interval is over when the event arrives
        parent = _trace.current()
        _trace.record_span(
            f"jax:{phase}:{name}", "compile", end - duration, duration,
            trace_id=parent.trace_id if parent else _trace.new_trace_id(),
            span_id=_trace.new_span_id(),
            parent_id=parent and parent.span_id,
            args=cache and {"cache": cache})


jax.monitoring.register_event_listener(_on_jax_cache)
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _union_s(intervals):
    """Seconds the ``(start, duration)`` intervals cover together."""
    total, upto = 0.0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if upto is None or start > upto:
            total, upto = total + dur, stop
        elif stop > upto:
            total, upto = total + stop - upto, stop
    return total


def _jax_report():
    """``compile_report()["jax"]``; the caller holds ``_lock``."""
    programs = [dict({k: round(v, 6) for k, v in row.items()}, name=name)
                for name, row in _jax_programs.items()]
    events = [dict({"ts": ts, "dur": dur, "phase": phase, "name": name,
                    "tid": tid}, **({"cache": cache} if cache else {}))
              for ts, dur, phase, name, tid, cache in _jax_events]
    by_thread = {}
    for e in events:
        if e["phase"] == "trace":
            by_thread.setdefault(e["tid"], []).append((e["ts"], e["dur"]))
    return {
        "programs": sorted(
            programs, key=lambda p: (-(p["trace_s"] + p["lower_s"]
                                       + p["backend_s"]), p["name"])),
        "events": events,
        "totals": {
            # a trace inside a trace counts once; over the log's events
            "trace_s": round(1e-6 * sum(map(_union_s, by_thread.values())),
                             6),
            "lower_s": round(sum(p["lower_s"] for p in programs), 6),
            "backend_s": round(sum(p["backend_s"] for p in programs), 6),
            "programs": sum(p["programs"] for p in programs),
            "cache_hits": sum(p["cache_hits"] for p in programs),
            "cache_misses": sum(p["cache_misses"] for p in programs),
        },
    }


@contextlib.contextmanager
def jit_acquire(name, kind, args):
    """Report the first call of a plain ``jax.jit`` entry point — the
    call that traces and compiles — as one program acquisition: a
    ``compile/acquire:<name>`` span and a row in ``compile_report()``
    keyed on the call's argument signature. It counts as loaded when
    JAX found the program named ``name`` in its persistent cache (the
    ``cache`` of that program's own backend event, whatever else was
    compiled meanwhile), as a fresh compile otherwise. The program
    itself is not routed through the AOT cache."""
    from .key import program_key
    _jax_tls.acquiring, _jax_tls.verdict = name, None
    try:
        with _trace.span(f"acquire:{name}", "compile") as sp:
            yield
    finally:
        verdict, _jax_tls.acquiring = _jax_tls.verdict, None
    sig = arg_signature(args)
    key = program_key(kind, name, input_sigs=sig)
    rec = _ensure(key)
    rec.arg_sig = sig
    if verdict == "hit":
        rec.load_s += sp.dur
        rec.cache_hits += 1
        rec.source = "cache"
        _count("compile.cache_hits")
    else:
        rec.compile_s += sp.dur
        rec.compiles += 1
        rec.source = "compile"
        _count("compile.fresh_compiles")
    _refresh_prof_counters()
    _emit_event(key, rec.source, sp.dur)


def guarded_loaded_program(exe, fallback, what, on_reject=None):
    """Wrap a cache-loaded executable so its FIRST call is guarded: an
    aval/layout mismatch the key failed to anticipate degrades to the
    ``fallback`` jit (a fresh in-process compile) with a warning and a
    counter — never a broken step. Argument checking happens before
    execution, so no donated buffer is consumed by the failed attempt.
    Once one call succeeds the guard is dropped. ``on_reject`` lets the
    caller repoint its program table at the fallback."""
    state = {"proven": False}

    def call(*args):
        if state["proven"]:
            return exe(*args)
        try:
            out = exe(*args)
            state["proven"] = True
            return out
        except Exception as err:
            logger.warning(
                "cache-loaded %s executable rejected at call time (%s); "
                "recompiling fresh", what, err)
            _count("compile.load_call_fallback")
            if on_reject is not None:
                on_reject()
            return fallback(*args)

    return call


# ---------------------------------------------------------------------------
# shared-jit path: Executor
# ---------------------------------------------------------------------------
class SharedPrograms:
    """Weakly-shared holder of an executor's jitted callables. Live
    executors with the same program key hold the same instance, so
    identical binds (two buckets with identical shapes) share one XLA
    program; when the last executor dies the programs are collectable."""

    def __init__(self, programs):
        self.programs = programs

    def __getitem__(self, name):
        return self.programs[name]


def shared_programs(key, builder):
    """Memoize ``builder()`` (a dict of jitted callables) on the key
    digest, weakly. Returns (SharedPrograms, was_shared)."""
    with _lock:
        holder = _shared.get(key.digest)
        if holder is not None:
            return holder, True
    built = builder()
    holder = SharedPrograms(built)
    with _lock:
        # a racing builder may have landed first — prefer the shared one
        existing = _shared.get(key.digest)
        if existing is not None:
            return existing, True
        _shared[key.digest] = holder
    return holder, False


class JitProgram:
    """Registry-aware wrapper around one ``jax.jit`` callable.

    Counts traces at trace time (a probe in the wrapped body runs only
    while tracing — the steady-state call adds two perf_counter reads
    and nothing else), attributes the wall time of any call that traced
    as compile time, and feeds the retrace guard with the argument
    signature that diverged. Used by Executor, where programs stay
    shape-polymorphic jits (eval/train static args, optional head
    grads) rather than AOT executables.
    """

    def __init__(self, fn, key, **jit_kwargs):
        import jax
        self.key = key
        self.rec = _ensure(key)

        def probed(*args, **kwargs):
            # runs at trace time only; re-attach the record in case a
            # compile_report(reset=True) window evicted it — a trace
            # after the reset must still be visible in the report
            rec = self.rec = _restore_record(self.rec)
            rec.compiles += 1
            _count("compile.fresh_compiles")
            return fn(*args, **kwargs)

        self._jfn = jax.jit(probed, **jit_kwargs)

    def __call__(self, *args):
        before_rec = self.rec
        before = before_rec.compiles
        t0 = time.perf_counter()
        out = self._jfn(*args)
        rec = self.rec       # the probe may have swapped the record
        if rec is not before_rec or rec.compiles != before:
            rec.compile_s += time.perf_counter() - t0
            rec.source = "compile"
            sig = arg_signature(args)
            note_entry_point(rec.name, self.key, sig)
            rec.arg_sig = sig
            _refresh_prof_counters()
        return out

    def lower(self, *args, **kwargs):
        return self._jfn.lower(*args, **kwargs)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------
_prof_counters = [None]


def _refresh_prof_counters():
    """Mirror the registry totals into ``compile::`` profiler counters
    (profiler.counters()) so live jobs expose them without a report."""
    try:
        from .. import profiler
        if _prof_counters[0] is None:
            dom = profiler.Domain("compile")
            _prof_counters[0] = {
                "fresh_compiles": profiler.Counter(dom, "fresh_compiles"),
                "cache_hits": profiler.Counter(dom, "cache_hits"),
            }
        with _lock:
            fresh = sum(r.compiles for r in _records.values())
            hits = sum(r.cache_hits for r in _records.values())
        _prof_counters[0]["fresh_compiles"].set_value(fresh)
        _prof_counters[0]["cache_hits"].set_value(hits)
    except Exception:
        pass


def _collect(reset=False):
    """Aggregate compile observability (``mx.compile_report()``):

    - ``programs``: one row per canonical program — fresh compiles,
      cache hits/misses/rejections, compile + AOT-load wall seconds;
    - ``retraces``: per entry point, recompile count with the diverging
      argument signature / key material that caused each;
    - ``totals``: summed counters (the subprocess warm-start tests pin
      ``fresh_compiles == 0`` on these);
    - ``cache``: the persistent-cache configuration in effect;
    - ``jax``: every program JAX built in this process, whoever asked for
      it, from the one ``jax.monitoring`` listener pair: ``programs``
      (one row a jitted function's name: traces and their seconds,
      inclusive of the traces nested in them, seconds lowering, programs
      compiled or loaded and their seconds, hits and misses of JAX's
      persistent cache; by total seconds), ``events`` (the log of the
      last 4096 phases, oldest first: ``ts`` and ``dur`` in microseconds
      on the clock of ``telemetry.trace.spans()``, ``phase``, ``name``,
      ``tid`` and, of a ``backend`` phase, ``cache``: ``hit``, ``miss``
      or ``none``) and ``totals`` (``trace_s`` is the union of the log's
      trace intervals a thread).

    ``reset=True`` reads and clears inside ONE lock acquisition — a
    compile landing between the read and the clear counts in exactly
    one report window.
    """
    from .cache import cache_enabled
    from .. import config
    with _lock:
        programs = [r.as_dict() for r in _records.values()]
        retraces = {n: {"count": e["count"],
                        "events": list(e["events"])}
                    for n, e in _retraces.items()}
        jax_programs = _jax_report()
        if reset:
            _clear()
    if reset:
        _refresh_prof_counters()
    totals = {
        "programs": len(programs),
        "fresh_compiles": sum(p["compiles"] for p in programs),
        "cache_hits": sum(p["cache_hits"] for p in programs),
        "cache_misses": sum(p["cache_misses"] for p in programs),
        "cache_errors": sum(p["cache_errors"] for p in programs),
        "compile_s": round(sum(p["compile_s"] for p in programs), 4),
        "load_s": round(sum(p["load_s"] for p in programs), 4),
        "retraces": sum(e["count"] for e in retraces.values()),
    }
    return {
        "programs": sorted(programs,
                           key=lambda p: (-p["compile_s"], p["name"])),
        "retraces": retraces,
        "totals": totals,
        "jax": jax_programs,
        "cache": {
            "enabled": cache_enabled(),
            "dir": str(config.get("MXTPU_COMPILE_CACHE_DIR") or "") or
            None,
        },
    }


compile_report = _treg.collector_view("compile", _collect)


def reset():
    """Clear every record/retrace counter (between measurement windows
    or test cases). Live programs keep running; their records recreate
    on the next acquisition."""
    with _lock:
        _clear()
    _refresh_prof_counters()


def _clear():
    """The caller holds ``_lock``."""
    _records.clear()
    _entry_points.clear()
    _retraces.clear()
    _jax_programs.clear()
    _jax_events.clear()
