"""Seconds or counts of the XLA programs that set-up built, by phase and
name, from the program's own log of them:
``mx.compile_report()["jax"]["events"]``, one record a phase of a program
(``trace``, ``lower``, or ``backend``: the XLA compile or the load from
JAX's persistent cache, with ``cache`` ``hit`` / ``miss`` / ``none``),
``ts`` and ``dur`` in microseconds on the clock of the program's ring.

``params["phase"]`` is a phase or a list of phases, ``params["name"]`` an
optional pattern of the jitted function's name, and ``params["what"]``:

- ``union_s``: the seconds the events cover, a thread at a time (a trace
  inside a trace counts once);
- ``sum_s``: their summed seconds;
- ``count_not_hit``: how many of them did not come out of the cache.

**Set-up alone.** This runs after the plain reference, which builds
programs of its own after the window. The events kept are those that
ended before the window's first step began (``window_spans.window_steps``
over ``mx.telemetry.trace.spans()``: the ring holds the window's steps
because the profiler turns tracing on at its start, and the window itself
builds nothing, ``programs_built_in_window``; a span that set-up records
into the ring itself, ``setup/router_bias``, starts no window). Nothing
where the ring holds no such steps or the report has no ``jax``: a
program from before the log.
"""
import os
import re

import harness
import trace_reduce

window_spans = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "window_spans.py"))


def before(events, steps):
    """The events that ended before the first of ``steps`` began."""
    if not steps:
        return None
    start = min(s["ts"] for s in steps)
    return [e for e in events if e["ts"] + e["dur"] <= start]


def reduce_events(events, phase, what, name=None):
    phases = {phase} if isinstance(phase, str) else set(phase)
    rx = re.compile(name or "")
    kept = [e for e in events
            if e["phase"] in phases and rx.search(e["name"])]
    if what == "count_not_hit":
        return sum(e.get("cache") != "hit" for e in kept)
    if what == "sum_s":
        return 1e-6 * sum(e["dur"] for e in kept)
    if what == "union_s":
        by_thread = {}
        for e in kept:
            by_thread.setdefault(e["tid"], []).append((e["ts"], e["dur"]))
        return 1e-6 * sum(map(trace_reduce.busy_seconds,
                              by_thread.values()))
    raise ValueError(f"what={what!r}: union_s, sum_s or count_not_hit")


def read(params, facts):
    import mxnet_tpu as mx
    log = mx.compile_report().get("jax")
    events = log and before(log["events"], window_spans.window_steps(
        mx.telemetry.trace.spans(), facts["window"].get("steps")))
    if events is None:
        return None
    return reduce_events(events, params["phase"], params["what"],
                         params.get("name"))
