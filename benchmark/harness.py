"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the look for a chip, counting compilations, the
arithmetic of the comparisons that decide ``correct``, and the result
line.

Nothing in this file knows a workload, a configuration, a traffic mix, a
driver, a reader or a metric by name. A cell names its configuration and
its traffic; ``configs/<config>.json`` names its module,
``traffic/<traffic>.json`` its driver, ``layer_metrics/<metric>.json``
its reader, and each is loaded from the file of that name.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import math
import os
import statistics
import sys
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import a file by its path; the file's name need not be an
    identifier (``lstm-lm-650x2.py``)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace("-", "_").rsplit(".", 1)[0]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def benchmark_json(root=ROOT, proposed=True):
    """``BENCHMARK.json``, and with ``proposed`` the entries of
    ``benchmark/proposed/*.json`` after it: cells the harness can run
    that the benchmark does not hold to bounds yet."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    if proposed:
        for path in sorted(glob.glob(os.path.join(
                root, "benchmark", "proposed", "*.json"))):
            extra = load_json(path)
            for kind in ("configs", "workloads", "end_to_end", "per_layer"):
                have = {e["name"] for e in bench[kind]}
                bench[kind] += [e for e in extra.get(kind, [])
                                if e["name"] not in have]
    return bench


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    model: object
    driver: object
    sizes: dict
    rehearsal: bool
    bench: dict = field(repr=False, default_factory=dict)

    @property
    def limits(self):
        return self.config["limits"][self.traffic["role"]]


def load_cell(workload, rehearsal=False, root=ROOT):
    bench = benchmark_json(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(by_name)}")
    w = by_name[workload]
    (cfg_entry,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    cfg_dir = os.path.dirname(os.path.join(root, cfg_entry["file"]))
    bench_dir = os.path.dirname(cfg_dir)
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    model = load_module(os.path.join(cfg_dir, config["module"]))
    driver = load_module(os.path.join(bench_dir, "drivers",
                                      traffic["driver"] + ".py"))
    sizes = dict(config["rehearsal_sizes"] if rehearsal
                 else config["sizes"])
    if rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return Cell(workload, int(w["chips"]), config, traffic, model, driver,
                sizes, rehearsal, bench)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------
def peaks_for(kind):
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"device_kind {kind!r} is not in peaks.json "
                         f"(it has {sorted(table)}): add it with its "
                         "source, there is no default")
    return table[kind]


def find_device(chips, rehearsal):
    """The devices this run stands on, as the result line names them.
    Exits unless JAX's default backend is a TPU with at least ``chips``
    chips; a rehearsal accepts whatever is there."""
    import jax
    devs = jax.devices()
    desc = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearsal:
        if desc["platform"] != "tpu":
            raise SystemExit(f"no accelerator: JAX's default backend is "
                             f"{desc['platform']!r}; this benchmark "
                             "measures a TPU and never falls back")
        if len(devs) < chips:
            raise SystemExit(f"the cell needs {chips} chip(s), JAX sees "
                             f"{len(devs)}")
        peaks_for(desc["kind"])
    return desc


def memory_peak_bytes():
    """Peak bytes taken on the fullest local device, 0 where the backend
    keeps no such count (the CPU). The TPU runtime counts the buffers a
    process holds (``peak_bytes_in_use``) apart from the scratch it
    reserves for the programs' temporaries (``peak_bytes_reserved``,
    which ``bytes_reservable_limit`` shows to come out of the same
    memory: a 4.29 GB temporary read 34 MB in use and 4.29 GB reserved,
    my chip run, PR 23). The chip's memory taken is their sum."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


class CompileCounter:
    """Counts the programs JAX builds or loads for the backend (a fresh
    compile and a load from the persistent cache alike): every one is a
    shape the warm-up did not cover."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# the comparisons that decide `correct`
# ---------------------------------------------------------------------------
def worst_leaf_gap(got, want, leaves=None):
    """The largest gap between a leaf's norm in ``got`` and in ``want``,
    against ``want``'s norm of that leaf or of the median leaf, whichever
    is larger, over ``leaves`` (all of ``want``'s without). Returns
    ``(gap, leaf)``."""
    leaves = list(want) if leaves is None else leaves
    median = statistics.median(want[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        ref = want[k]
        gap = abs(got[k] - ref) / max(ref, median, 1e-300)
        if not math.isfinite(gap):
            gap = math.inf
        if where is None or gap > worst:
            worst, where = gap, k
    return worst, where


def update_difference(got, want):
    """The distance between two first steps over all parameters at once:
    the norm of the difference over the norm of ``want``. Rounding noise
    leaves a leaf's norm where it was and shows only here; taken over
    all leaves together, the leaves whose gradient is all but zero do
    not count."""
    import numpy as np
    diff = ref = 0.0
    for k, b in want.items():
        a = np.asarray(got[k], np.float64)
        b = np.asarray(b, np.float64)
        diff += float(np.sum(np.square(a - b)))
        ref += float(np.sum(np.square(b)))
    value = math.sqrt(diff) / max(math.sqrt(ref), 1e-300)
    return value if math.isfinite(value) else math.inf


def compare_training(got, want, limits):
    """Rows ``(name, value, limit, note)`` for a training cell: the loss
    of every step, the first gradient and the parameters' change by the
    worst leaf's norm, and the first step's distance from the
    reference's."""
    loss_gap = max(abs(a - b) / abs(b) if math.isfinite(a) else math.inf
                   for a, b in zip(got["losses"], want["losses"]))
    # the norm of a leaf of three numbers is those three numbers: leaves
    # under the configuration's floor are left to first_step_diff
    floor = limits.get("leaf_floor", {}).get("elements", 1)
    leaves = [k for k, v in want["first_update"].items() if v.size >= floor]
    grad_gap, grad_leaf = worst_leaf_gap(got["first_grad_norms"],
                                         want["first_grad_norms"], leaves)
    change_gap, change_leaf = worst_leaf_gap(got["change_norms"],
                                             want["change_norms"], leaves)
    return [
        ("loss_rel", loss_gap, limits["loss_rel"]["limit"],
         f"losses {got['losses']} against {want['losses']}"),
        ("first_grad_rel", grad_gap, limits["first_grad_rel"]["limit"],
         f"worst leaf {grad_leaf}"),
        ("change_rel", change_gap, limits["change_rel"]["limit"],
         f"worst leaf {change_leaf}"),
        ("first_step_diff",
         update_difference(got["first_update"], want["first_update"]),
         limits["first_step_diff"]["limit"], "all leaves together"),
    ]


def compare_outputs(got, want, limits):
    """Rows for a serving cell: ``got`` and ``want`` are lists of one
    array a request. The number compared is the worst request's distance
    from the reference, in the L2 norm, over the reference's norm."""
    import numpy as np
    worst, where = 0.0, None
    for i, (a, b) in enumerate(zip(got, want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        gap = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300)) \
            if a.shape == b.shape and np.isfinite(a).all() else math.inf
        if where is None or gap > worst:
            worst, where = gap, i
    return [("output_rel_l2", worst, limits["output_rel_l2"]["limit"],
             f"worst of {len(want)} sampled requests: number {where}")]


def print_rows(rows, label="check"):
    ok = True
    for name, value, limit, note in rows:
        passed = value <= limit
        ok = ok and passed
        print(f"{label} {name}: value={value!r} limit={limit!r} "
              f"{'ok' if passed else 'OVER'} ({note})")
    return ok
