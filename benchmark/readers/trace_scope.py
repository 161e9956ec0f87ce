"""Device time of the operations that belong to the program's own named
scopes (``jax.named_scope("mx_...")`` in the step program), on the first
device plane. The trace names its events by HLO instruction; the
configuration's ``scope_table()`` maps instruction names to scopes from
the compiled program's text (``mx.telemetry.trace.hlo_scopes``).

``params["scopes"]`` is a pattern over scope names. ``params["what"]``:

- ``share``: percent of the device's operation time in those scopes;
- ``roofline``: the least time the chip could take for what
  ``params["cost"]`` (a function of the configuration's module, ``sizes ->
  (operations, bytes)`` a step) says a step needs, the larger of
  operations over ``peaks.json``'s ``params["peak_flops"]`` and bytes over
  its ``params["peak_bytes_per_s"]``, over the scopes' device time a
  step, in percent. Recomputed operations count in the time, not in the
  need.

Nothing where the configuration has no ``scope_table`` (a program from
before the scopes), where the table is empty or where no traced event
matches. The first metric read in a process also prints every scope's
milliseconds a step, for PERF.md's breakdown.
"""
import json
import re

import harness
import trace_reduce


def scope_seconds(ops, table, pattern):
    """Summed duration of the events whose instruction belongs to a scope
    matching ``pattern``."""
    rx = re.compile(pattern)
    total = 0.0
    for _, dur, text in ops:
        scope = table.get(text.split(" = ", 1)[0].strip().lstrip("%"))
        if scope is not None and rx.search(scope):
            total += dur
    return total


_PRINTED = []


def scope_ms_per_step(ops, table, steps):
    """``{scope: milliseconds a step}`` over every scope of the table."""
    per = {}
    for _, dur, text in ops:
        scope = table.get(text.split(" = ", 1)[0].strip().lstrip("%"))
        if scope is not None:
            per[scope] = per.get(scope, 0.0) + dur
    return {k: round(1e3 * v / steps, 4) for k, v in sorted(per.items())}


def read(params, facts):
    cell = facts["cell"]
    table_of = getattr(cell.model, "scope_table", None)
    if table_of is None or not facts["trace"].devices:
        return None
    table = table_of()
    if not table:
        return None
    ops = facts["trace"].devices[0].ops
    steps = facts["window"].get("steps")
    if steps and not _PRINTED:
        _PRINTED.append(True)
        print("trace_scope: ms a step by scope "
              + json.dumps(scope_ms_per_step(ops, table, steps)))
    seconds = scope_seconds(ops, table, params["scopes"])
    if seconds <= 0:
        return None
    if params["what"] == "share":
        return 100.0 * seconds / trace_reduce.total_op_seconds(ops)
    if not steps or cell.rehearsal:
        return None
    peaks = harness.peaks_for(facts["device"]["kind"])
    operations, moved = getattr(cell.model, params["cost"])(cell.sizes)
    least = max(operations / peaks[params["peak_flops"]],
                moved / peaks[params["peak_bytes_per_s"]])
    return 100.0 * least / (seconds / steps)
