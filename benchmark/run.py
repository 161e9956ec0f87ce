"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by name, builds seeded weights on the device,
warms up the cell's shapes, measures for ``--seconds``, then compares
what the timed program produced with the plain reference and prints the
result line last. With ``--trace 1`` the window is the traffic file's
``trace_seconds`` long at most, runs under the profiler, and the metrics
are the per-layer ones. Without a TPU the run fails; ``--rehearsal 1``
(the tests' switch) accepts any backend, shrinks every size to the
configuration's ``rehearsal_sizes`` and marks the result as no
measurement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--list", action="store_true",
                    help="print the cells of BENCHMARK.json and exit")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not args.list and None in (args.workload, args.seed, args.seconds):
        ap.error("--workload, --seed and --seconds are required")
    return args


def _metrics_of(kind, cell):
    """The entries of ``kind`` (``end_to_end`` / ``per_layer``) that this
    cell reports: those that list it, and those that list no cell (a
    per-layer metric of that sort goes with the end-to-end metric it
    moves)."""
    def lists(m):
        return cell.name in m.get("workloads", [cell.name])

    mine = {m["name"] for m in cell.bench["end_to_end"] if lists(m)}
    return [m for m in cell.bench[kind]
            if lists(m) and ("workloads" in m or kind == "end_to_end"
                             or m["moves"] in mine)]


def _read_layer_metrics(cell, facts):
    out = {}
    for m in _metrics_of("per_layer", cell):
        spec = harness.load_json(os.path.join(
            harness.BENCH, "layer_metrics", m["name"] + ".json"))
        reader = harness.load_module(os.path.join(
            harness.BENCH, "readers", spec["reader"] + ".py"))
        value = reader.read(spec.get("params", {}), facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _program_counts():
    """Counts the program keeps of itself, read at the end of set-up."""
    import mxnet_tpu as mx
    sites = 0
    for pipe in mx.pass_report()["pipelines"]:
        for e in pipe["passes"]:
            if e["status"] == "applied":
                sites += len(e["sites"])
    return {"fresh_compiles": mx.compile_report()["totals"]["fresh_compiles"],
            "pass_sites": sites}


def main(argv=None):
    args = _args(argv)
    if args.list:
        for w in harness.benchmark_json()["workloads"]:
            print(f"{w['name']}: {w['config']} under {w['traffic']}, "
                  f"{w['chips']} chip(s)")
        return
    rehearsal = bool(args.rehearsal)
    # a run that hangs says where and ends, with no result line: set-up
    # inside a cold run's 1200 s, the rest inside a warm run's 360 s
    faulthandler.dump_traceback_later(1150, exit=True,
                                      file=sys.__stderr__)
    cell = harness.load_cell(args.workload, rehearsal)
    device = harness.find_device(cell.chips, rehearsal)
    compiles = harness.CompileCounter()
    print(f"run: cell {cell.name} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace} on {device}"
          f"{' (REHEARSAL: not a measurement)' if rehearsal else ''}")
    import jax
    session = cell.driver.setup(cell, args.seed)
    print(f"run: jax compile cache at "
          f"{jax.config.jax_compilation_cache_dir}")
    counts = _program_counts()
    counts["xla_programs"] = compiles.count
    setup_s = time.perf_counter() - T_START
    print(f"run: set-up {setup_s:.2f} s, {compiles.count} XLA program(s) "
          f"built or loaded, program counts {counts}")

    seconds = args.seconds
    trace_dir = None
    if args.trace:
        seconds = min(seconds, cell.traffic["trace_seconds"])
        trace_dir = os.path.join(harness.ROOT, ".bench_trace",
                                 f"{cell.name}-{os.getpid()}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    in_window = compiles.count
    faulthandler.dump_traceback_later(4 * seconds + 240, exit=True,
                                      file=sys.__stderr__)
    t0 = time.perf_counter()
    try:
        result = cell.driver.window(cell, session, seconds)
    finally:
        window_s = time.perf_counter() - t0
        if args.trace:
            jax.profiler.stop_trace()
    in_window = compiles.count - in_window
    peak = harness.memory_peak_bytes()
    print(f"run: window {window_s:.2f} s, facts {result['facts']}")
    print(f"run: memory after the window "
          f"{jax.local_devices()[0].memory_stats()}")

    rows = cell.driver.check(cell, session, result)
    rows.append(("programs_built_in_window", in_window, 0,
                 "XLA programs built or loaded inside the window"))
    correct = harness.print_rows(rows) and result["failed"] == 0
    cell.driver.close(session)
    faulthandler.cancel_dump_traceback_later()

    device["memory_peak_bytes"] = peak
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        import trace_reduce
        trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                                  host_ops=rehearsal)
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = trace_reduce.mean_busy_seconds(trace)
        device["busy_s"], device["window_s"] = busy, window_s
        facts = {"cell": cell, "trace": trace, "busy_s": busy,
                 "window_s": window_s, "window": result["facts"],
                 "counts": counts, "device": device,
                 "memory_peak_bytes": peak}
        line["metrics"] = _read_layer_metrics(cell, facts)
        line["breakdown"] = trace_reduce.breakdown(trace)
    else:
        values = dict(result["metrics"], setup_s=setup_s)
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in _metrics_of("end_to_end", cell)}
    line["device"] = device
    if rehearsal:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
