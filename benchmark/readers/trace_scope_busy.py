"""Share of the device's busy time in which an operation of the program's
named scopes ran, on the first device plane, in percent: the union of the
intervals of the events whose scope (the configuration's
``scope_table()``: instruction name -> scope) matches
``params["scopes"]``, over the union of all events' intervals.

A union and not a sum, because a trace holds an event for a ``while``
loop and one for each operation of its every trip: summed, a loop and
its body count twice (``trace_scope``'s ``share`` divides by such a
sum, which is right for a program without loops). Nothing where the
configuration has no ``scope_table``, where the table is empty or where
no traced event matches.
"""
import re

import trace_reduce


def read(params, facts):
    table_of = getattr(facts["cell"].model, "scope_table", None)
    if table_of is None or not facts["trace"].devices:
        return None
    table = table_of()
    if not table:
        return None
    ops = facts["trace"].devices[0].ops
    rx = re.compile(params["scopes"])
    mine = []
    for start, dur, text in ops:
        scope = table.get(text.split(" = ", 1)[0].strip().lstrip("%"))
        if scope is not None and rx.search(scope):
            mine.append((start, dur))
    busy = trace_reduce.busy_seconds(ops)
    if not mine or busy <= 0:
        return None
    return 100.0 * trace_reduce.busy_seconds(mine) / busy
