"""Total seconds of the program's spans whose always-on aggregate (the
rows ``<cat>::<name>`` of ``mx.profiler.aggregate()``: count and total
seconds of every ``mx.telemetry.trace.span``, tracing on or off) match
``params["pattern"]``. For spans that run in set-up, before the
profiler starts and so outside the ring: ``pass::gate:<pass>`` (each
before/after measurement of the pass gate) and
``compile::acquire:<program>`` (each program loaded or compiled). The
window adds nothing to them (``programs_built_in_window`` is held to
0). Nothing when no row matches: a program from before the spans."""
import re


def total_seconds(table, pattern):
    rx = re.compile(pattern)
    rows = [row for name, row in table.items() if rx.search(name)]
    return sum(total for _count, total, *_ in rows) if rows else None


def read(params, facts):
    import mxnet_tpu as mx
    return total_seconds(mx.profiler.aggregate(), params["pattern"])
