"""Host milliseconds per step of the traced window, from the program's
own spans: the ring of ``mx.telemetry.trace`` (``spans()``), which the
program fills while the profiler runs and nothing empties.

The window's steps are the ``step/step`` spans under the last
``train/fit:*`` span of the ring (``Module.fit``), or without one the
last ``facts["window"]["steps"]`` of them (a step object called in a
loop). ``params["what"]``:

- ``self_less_wait``: a step's duration less the part of it that its
  descendants of kind ``wait`` cover (the thread blocked on a queue or
  on the device): what the host itself spends on a step;
- ``child`` with ``params["name"]``: the summed duration of a step's
  descendants of category ``step`` and that name (``data_wait``).

The mean over the window's steps. Nothing where the ring holds no such
steps: a program from before the spans, or a ring that has wrapped.
"""
import trace_reduce


def window_steps(spans, n_steps):
    """The window's ``step/step`` spans, oldest first."""
    steps = [s for s in spans if s["cat"] == "step" and s["name"] == "step"]
    fits = [s for s in spans
            if s["cat"] == "train" and s["name"].startswith("fit:")]
    if fits:
        return [s for s in steps if s["parent_id"] == fits[-1]["span_id"]]
    return steps[-n_steps:] if n_steps and len(steps) >= n_steps else []


def descendants(span, children):
    out, todo = [], [span]
    while todo:
        for c in children.get(todo.pop()["span_id"], ()):
            out.append(c)
            if c["span_id"] is not None:
                todo.append(c)
    return out


def covered_us(span, inside):
    """Microseconds of ``span`` that the spans of ``inside`` cover:
    their union, cut to the span."""
    lo, hi = span["ts"], span["ts"] + span["dur"]
    cut = [(max(s["ts"], lo), min(s["ts"] + s["dur"], hi)) for s in inside]
    return sum(e - s for s, e in
               trace_reduce.merge((s, e - s) for s, e in cut if e > s))


def per_step_us(spans, n_steps, what, name=None):
    """One number a step of the window, in microseconds."""
    children = {}
    for s in spans:
        if s["parent_id"] is not None:
            children.setdefault(s["parent_id"], []).append(s)
    out = []
    for step in window_steps(spans, n_steps):
        below = descendants(step, children)
        if what == "self_less_wait":
            waits = [s for s in below if s.get("kind") == "wait"]
            out.append(step["dur"] - covered_us(step, waits))
        else:
            out.append(sum(s["dur"] for s in below
                           if s["cat"] == "step" and s["name"] == name))
    return out


def read(params, facts):
    import mxnet_tpu as mx
    values = per_step_us(mx.telemetry.trace.spans(),
                         facts["window"].get("steps"),
                         params["what"], params.get("name"))
    return 1e-3 * sum(values) / len(values) if values else None
