"""Gauges of the program's own registry (``mx.telemetry``) whose names
match ``params["pattern"]``, reduced over the matching names by
``params["reduce"]`` (``sum``, ``max``, ``mean``) and multiplied by
``params.get("scale", 1)``. Nothing where no gauge matches: a program
that does not publish them."""
import re
import statistics

_REDUCE = {"sum": sum, "max": max, "mean": statistics.fmean}


def reduce_gauges(snapshot, pattern, how):
    rx = re.compile(pattern)
    values = [row["value"] for name, row in snapshot.items()
              if rx.search(name) and row.get("kind") == "gauge"]
    return _REDUCE[how](values) if values else None


def read(params, facts):
    import mxnet_tpu as mx
    value = reduce_gauges(mx.telemetry.snapshot(), params["pattern"],
                          params["reduce"])
    return None if value is None else value * params.get("scale", 1)
