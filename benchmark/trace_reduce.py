"""From a profiler trace (``*.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. What
a TPU trace holds (looked at by hand, PR 23, ``testdata/``): one plane
``/device:TPU:<n>`` per chip, with a line ``XLA Modules`` (one event per
program execution, named ``jit_<fn>(<hash>)``) and a line ``XLA Ops``
(one event per HLO instruction executed, named by the instruction's
text: ``%fusion.2 = bf16[...] fusion(...), kind=kOutput, calls=...``);
and one plane ``/host:CPU`` whose lines are host threads, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.
Times are nanoseconds from the start of the trace. The device's clock
and the host's differ by a millisecond or two in these files, so a gap
is attributed to a host span only by overlap, and gaps shorter than that
are attributed loosely.

A CPU trace has no device plane. With ``host_ops=True`` (rehearsal only)
the events of the host plane that carry an ``hlo_module`` stat stand in
for device operations, so that the readers have something to read.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
_KIND = re.compile(r"kind=(k\w+)")


@dataclass
class DeviceTrace:
    name: str
    ops: list = field(default_factory=list)       # (start_s, dur_s, text)
    modules: list = field(default_factory=list)   # (start_s, dur_s, name)


@dataclass
class Trace:
    devices: list
    host_spans: list                               # (start_s, dur_s, name)


def find_xplane(log_dir):
    """The one ``*.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one xplane under {log_dir}, "
                           f"found {found}")
    return found[0]


def load(path, host_ops=False):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host_spans = [], []
    host_dev = DeviceTrace("/host:CPU (stand-in)")
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = [(e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                e.name) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev.modules = [(e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9, e.name)
                                   for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host_spans.append((e.start_ns * 1e-9,
                                           e.duration_ns * 1e-9, e.name))
                    elif host_ops and not e.name.startswith(("$", "end: ")) \
                            and e.duration_ns > 0 \
                            and any(k == "hlo_module" for k, _ in e.stats):
                        host_dev.ops.append((e.start_ns * 1e-9,
                                             e.duration_ns * 1e-9, e.name))
    if not devices and host_ops and host_dev.ops:
        devices = [host_dev]
    devices.sort(key=lambda d: d.name)
    host_spans.sort()
    return Trace(devices, host_spans)


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------
def merge(intervals):
    """Union of ``(start, duration, ...)`` intervals as a sorted list of
    ``(start, end)``."""
    out = []
    for start, end in sorted((s, s + d) for s, d, *_ in intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def busy_seconds(ops):
    """Seconds in which at least one operation ran."""
    return sum(e - s for s, e in merge(ops))


def mean_busy_seconds(trace):
    """Busy seconds averaged over the device planes of the trace."""
    if not trace.devices:
        return 0.0
    return sum(busy_seconds(d.ops) for d in trace.devices) \
        / len(trace.devices)


def short_name(text):
    """``%fusion.2 = bf16[...] fusion(...), kind=kOutput`` ->
    ``fusion.2 kOutput``."""
    head = text.split(" = ", 1)[0].lstrip("%")
    kind = _KIND.search(text)
    return f"{head} {kind.group(1)}" if kind else head


def time_matching(ops, pattern):
    """Summed duration of the operations whose text matches ``pattern``
    (not a union: a share of operation time, not of the window)."""
    rx = re.compile(pattern)
    return sum(d for _, d, text in ops if rx.search(text))


def total_op_seconds(ops):
    return sum(d for _, d, _ in ops)


def top_ops(ops, n=10):
    """The ``n`` operations with most summed time: ``[[name, s], ...]``."""
    by = {}
    for _, d, text in ops:
        k = short_name(text)
        by[k] = by.get(k, 0.0) + d
    return [[k, v] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def module_executions(dev, pattern=None):
    """``(count, summed seconds)`` of the program executions whose name
    matches ``pattern`` (all of them without one)."""
    rx = re.compile(pattern) if pattern else None
    hit = [d for _, d, name in dev.modules
           if rx is None or rx.search(name)]
    return len(hit), sum(hit)


def idle_gaps(ops, host_spans, min_gap_s=50e-6, n=10):
    """Idle time between the first and the last operation, by what the
    host was doing: each gap of at least ``min_gap_s`` goes to the
    benchmark span that overlaps it most (``no benchmark span`` when none
    does). Returns the ``n`` labels with most idle time,
    ``[[label, seconds], ...]``."""
    busy = merge(ops)
    by = {}
    for (_, end), (start, _) in zip(busy, busy[1:]):
        gap = start - end
        if gap < min_gap_s:
            continue
        best, best_overlap = "no benchmark span", 0.0
        for s, d, name in host_spans:
            if s >= start:
                break
            overlap = min(start, s + d) - max(end, s)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        by[best] = by.get(best, 0.0) + gap
    return [[k, v] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace, n=10):
    """The result line's ``breakdown``, from the first device plane."""
    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    dev = trace.devices[0]
    return {"device_ops": top_ops(dev.ops, n),
            "idle_gaps": idle_gaps(dev.ops, trace.host_spans, n=n)}
