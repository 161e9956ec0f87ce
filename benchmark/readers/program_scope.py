"""Share of the device's busy time in which an operation of the program's
named scopes ran, on the first device plane, in percent, by the
program's own table: ``mx.telemetry.trace.scope_table(<XLA module>)``
maps the instructions of the step program acquired last to the
``jax.named_scope("mx_...")`` paths they were traced under
(``mx_loop_body/mx_norm``), leaves the ``while`` instructions out and
answers for whose names an executable found in a cache carries. The
module asked for is the one the trace shows running (``XLA Modules``).

``params["scopes"]`` is a pattern over scope paths. The number is the
union of the matching events' intervals over the union of all operation
events' intervals: a trace holds an event for a loop and one for each
operation of its trips, and a union counts the loop once.

Nothing where the program has no ``scope_table`` (one from before it
owned its table), where no program of the trace has a table or where no
traced event matches. The first metric read in a process prints, for
PERF.md's breakdown, every scope's milliseconds a step, what is left
under no scope with its largest operations, and what the table cost.
"""
import json
import re

import trace_reduce

# an event that spans the events of the computations it calls
_LOOP = re.compile(r" (while|conditional)\(")
_PRINTED = []


def instruction(text):
    """``%fusion.2 = bf16[...] fusion(...)`` -> ``fusion.2``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def program_table(dev):
    """The scope table of the program that ran longest in the trace and
    has one, or None."""
    import mxnet_tpu as mx
    table_of = getattr(mx.telemetry.trace, "scope_table", None)
    if table_of is None:
        return None
    seconds = {}
    for _, dur, name in dev.modules:
        name = name.split("(", 1)[0]
        seconds[name] = seconds.get(name, 0.0) + dur
    # a CPU rehearsal's stand-in plane has no module line
    names = sorted(seconds, key=seconds.get, reverse=True) \
        or ["jit_mx_train_step", "jit_mx_fused_step"]
    for name in names:
        table = table_of(name)
        if table:
            return table
    return None


def matching(ops, table, pattern):
    """The ``(start, duration)`` of the events whose instruction's scope
    path matches ``pattern``."""
    rx = re.compile(pattern)
    mine = []
    for start, dur, text in ops:
        scope = table.get(instruction(text))
        if scope is not None and rx.search(scope):
            mine.append((start, dur))
    return mine


def breakdown(ops, table, steps):
    """``({scope path: ms a step}, ms a step under no scope, its largest
    operations [[name, ms a step], ...])``, loops and conditionals left
    out."""
    by_scope, bare = {}, {}
    for start, dur, text in ops:
        if _LOOP.search(text):
            continue
        name = instruction(text)
        scope = table.get(name)
        if scope is None:
            bare[name] = bare.get(name, 0.0) + dur
        else:
            by_scope.setdefault(scope, []).append((start, dur))
    per = {k: round(1e3 * trace_reduce.busy_seconds(v) / steps, 4)
           for k, v in sorted(by_scope.items())}
    top = sorted(bare.items(), key=lambda kv: -kv[1])[:12]
    return per, round(1e3 * sum(bare.values()) / steps, 4), \
        [[k, round(1e3 * v / steps, 4)] for k, v in top]


def _print_once(ops, table, steps):
    import mxnet_tpu as mx
    _PRINTED.append(True)
    per, bare, top = breakdown(ops, table, steps)
    print("program_scope: ms a step by scope " + json.dumps(per))
    print(f"program_scope: ms a step under no scope {bare}, largest "
          + json.dumps(top))
    gauges = {k: row["value"] for k, row in
              mx.telemetry.snapshot(prefix="trace::scope_table").items()}
    print("program_scope: the table's cost " + json.dumps(gauges))


def read(params, facts):
    if not facts["trace"].devices:
        return None
    dev = facts["trace"].devices[0]
    table = program_table(dev)
    if not table:
        return None
    steps = facts["window"].get("steps")
    if steps and not _PRINTED:
        _print_once(dev.ops, table, steps)
    mine = matching(dev.ops, table, params["scopes"])
    busy = trace_reduce.busy_seconds(dev.ops)
    if not mine or busy <= 0:
        return None
    return 100.0 * trace_reduce.busy_seconds(mine) / busy
