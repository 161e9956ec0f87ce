#!/usr/bin/env python
"""Operate on durable telemetry exports (MXTPU_TELEMETRY_DIR).

The telemetry subsystem (``mxnet_tpu/telemetry/``) writes a rotating
JSONL event log plus periodic full-report snapshots. This CLI is the
operational surface:

    telemetry.py tail    [--dir D] [-n N] [--json] [--kind K]
    telemetry.py summary [--dir D] [--json]
    telemetry.py diff    A.json B.json [--json]
                         [--gate-bytes] [--gate-peak-mem]
                         [--tolerance PCT]
    telemetry.py render  [--dir D]
    telemetry.py fleet   [--dir D] [--json] [--straggler-factor F]
    telemetry.py trace   [PATH] [--dir D] [--json]

``tail`` prints the last N events across the rotated segments (a line
torn by a mid-write kill is skipped and counted, never fatal — the
log stays tailable after any crash); ``summary`` aggregates the whole
event stream (train-step phase attribution, serving batches,
checkpoint/compile events) plus the newest snapshot's headline gauges;
``diff`` compares two snapshot files metric by metric — and with
``--gate-bytes`` exits 2 when ``step::bytes_accessed`` (XLA's count for
the step's compiled program) grew beyond ``--tolerance`` between them;
``render`` emits the newest snapshot in Prometheus text format for a
scrape endpoint or textfile collector.

Round 14 adds the fleet and trace surfaces: ``fleet`` merges the
per-rank ``rank-<r>/`` exporter directories a multi-process run writes
under one base dir into fleet-wide step-time p50/p99 plus a per-rank
skew table, flagging ranks whose median step wall exceeds
``--straggler-factor`` x the fleet median (the straggler detector);
``trace`` loads a Chrome trace-event JSON written under
``MXTPU_TRACE_DIR`` (newest file by default), validates the event
schema, and prints a per-category span summary — open the same file in
``chrome://tracing`` / Perfetto for the visual timeline. ``diff
--gate-peak-mem`` is the HBM sibling of ``--gate-bytes``: exit 2 when
``mem::process_peak_bytes`` grew beyond tolerance between snapshots.

Round 17 (serving fleet): ``fleet`` additionally aggregates the
FleetRouter's ``fleet_route`` / ``fleet_redispatch`` / ``fleet_shed`` /
``fleet_drain`` / ``fleet_replace`` events into a per-replica routing
table plus per-request timelines (a request's hops across replicas,
keyed by its propagated trace id).

Pure file-level operations: no accelerator backend is initialized.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

BYTES_METRIC = "step::bytes_accessed"
PEAK_MEM_METRIC = "mem::process_peak_bytes"


def _dir(args):
    d = args.dir or os.environ.get("MXTPU_TELEMETRY_DIR", "")
    if not d:
        sys.exit("no telemetry directory: pass --dir or set "
                 "MXTPU_TELEMETRY_DIR")
    return d


def _read_events(directory):
    from mxnet_tpu.telemetry.export import read_events
    return read_events(directory)


def _newest_snapshot(directory):
    from mxnet_tpu.telemetry.export import snapshot_files
    files = snapshot_files(directory)
    return files[-1] if files else None


def cmd_tail(args):
    events, torn = _read_events(_dir(args))
    if args.kind:
        events = [e for e in events if e.get("kind") == args.kind]
    events = events[-args.n:]
    if torn:
        print(f"(skipped {torn} torn line(s) — mid-write kill; "
              "harmless)", file=sys.stderr)
    for e in events:
        if args.json:
            print(json.dumps(e))
        else:
            ts = e.pop("ts", None)
            kind = e.pop("kind", "?")
            rest = " ".join(f"{k}={v}" for k, v in e.items())
            print(f"{ts:.3f}  {kind:<16} {rest}" if ts
                  else f"{kind:<16} {rest}")
    return 0


def _mean(vals):
    return sum(vals) / len(vals) if vals else None


def summarize(directory):
    """Aggregate the event stream + newest snapshot into one dict
    (the ``summary --json`` payload; tests round-trip through it)."""
    events, torn = _read_events(directory)
    kinds = {}
    for e in events:
        kinds[e.get("kind", "?")] = kinds.get(e.get("kind", "?"), 0) + 1
    steps = [e for e in events if e.get("kind") == "train_step"]
    serving = [e for e in events if e.get("kind") == "serving_batch"]
    out = {
        "dir": directory,
        "events": len(events),
        "torn_lines": torn,
        "by_kind": kinds,
    }
    if steps:
        phases = {}
        for e in steps:
            for name, secs in (e.get("phases") or {}).items():
                phases.setdefault(name, []).append(float(secs))
        last = steps[-1]
        out["train"] = {
            "milestones": len(steps),
            "last_step": last.get("step"),
            "mean_wall_s": round(_mean(
                [float(e["wall_s"]) for e in steps
                 if e.get("wall_s") is not None]) or 0.0, 6),
            "mean_phase_s": {n: round(_mean(v), 6)
                             for n, v in sorted(phases.items())},
            "bytes_accessed": last.get("bytes_accessed"),
            "flops": last.get("flops"),
        }
    if serving:
        out["serving"] = {
            "batches": len(serving),
            "rows": sum(int(e.get("rows", 0)) for e in serving),
            "requests": sum(int(e.get("requests", 0)) for e in serving),
        }
    snap_path = _newest_snapshot(directory)
    if snap_path:
        try:
            with open(snap_path) as f:
                snap = json.load(f)
            metrics = snap.get("metrics", {})
            headline = {}
            for key in (BYTES_METRIC, "step::flops",
                        "step::arithmetic_intensity_flop_b"):
                m = metrics.get(key)
                if m is not None:
                    headline[key] = m.get("value")
            wall = metrics.get("step::wall_s")
            if wall:
                headline["step::wall_s.mean"] = wall.get("mean")
                headline["step::wall_s.count"] = wall.get("count")
            out["snapshot"] = {"path": snap_path, "headline": headline}
        except (OSError, ValueError) as e:
            out["snapshot"] = {"path": snap_path, "error": str(e)}
    return out


def cmd_summary(args):
    out = summarize(_dir(args))
    if args.json:
        print(json.dumps(out, indent=1))
        return 0
    print(f"telemetry dir: {out['dir']}")
    kinds = ", ".join(f"{k}={v}" for k, v in sorted(out["by_kind"].items()))
    print(f"events: {out['events']} ({kinds})")
    if out.get("torn_lines"):
        print(f"torn lines skipped: {out['torn_lines']}")
    tr = out.get("train")
    if tr:
        print(f"train: {tr['milestones']} milestone(s), last step "
              f"{tr['last_step']}, mean wall {tr['mean_wall_s']}s")
        for n, v in tr["mean_phase_s"].items():
            print(f"  phase {n:<18} {v}s")
        if tr.get("bytes_accessed"):
            print(f"  bytes/step {tr['bytes_accessed']:.3e}")
    sv = out.get("serving")
    if sv:
        print(f"serving: {sv['batches']} micro-batch(es), "
              f"{sv['rows']} rows, {sv['requests']} requests")
    sn = out.get("snapshot")
    if sn:
        print(f"newest snapshot: {sn['path']}")
        for k, v in sn.get("headline", {}).items():
            print(f"  {k} = {v}")
    return 0


# ---------------------------------------------------------------------------
# diff / regression gates over two telemetry snapshots
# ---------------------------------------------------------------------------
def _gauge(tree, metric, path, hint):
    """The value of one gauge of a snapshot file; exits when the run
    recorded none (zero bytes or zero peak is no reading)."""
    m = tree.get("metrics", {}).get(metric)
    if isinstance(m, dict) and m.get("value"):
        return float(m["value"])
    sys.exit(f"{path}: no {metric} metric — not a telemetry snapshot, "
             f"or the run recorded no {hint}")


def _flat_values(tree):
    """metric -> comparable scalar for the metric-by-metric diff."""
    out = {}
    for name, m in tree.get("metrics", {}).items():
        if not isinstance(m, dict):
            continue
        if "value" in m:
            out[name] = m["value"]
        elif "count" in m:
            out[name + ".count"] = m["count"]
            if m.get("mean") is not None:
                out[name + ".mean"] = m["mean"]
    return out


def cmd_diff(args):
    trees = []
    for path in (args.old, args.new):
        try:
            with open(path) as f:
                trees.append(json.load(f))
        except (OSError, ValueError) as e:
            sys.exit(f"cannot read snapshot {path}: {e}")
    old_t, new_t = trees
    old_v, new_v = _flat_values(old_t), _flat_values(new_t)
    changes = {}
    for name in sorted(set(old_v) | set(new_v)):
        a, b = old_v.get(name), new_v.get(name)
        if a != b:
            changes[name] = {"old": a, "new": b}
    result = {"old": args.old, "new": args.new, "changed": changes}
    tol = args.tolerance / 100.0
    bytes_failed = mem_failed = False
    if args.gate_bytes:
        old_b = _gauge(old_t, BYTES_METRIC, args.old, "step costs")
        new_b = _gauge(new_t, BYTES_METRIC, args.new, "step costs")
        bytes_failed = new_b > old_b * (1.0 + tol)
        result["gate_bytes"] = {
            "old_bytes_per_step": old_b,
            "new_bytes_per_step": new_b,
            "delta_pct": round((new_b / old_b - 1.0) * 100.0, 4),
            "tolerance_pct": args.tolerance,
            "regressed": bytes_failed,
        }
    if args.gate_peak_mem:
        old_m = _gauge(old_t, PEAK_MEM_METRIC, args.old,
                       "program memory analyses")
        new_m = _gauge(new_t, PEAK_MEM_METRIC, args.new,
                       "program memory analyses")
        mem_failed = new_m > old_m * (1.0 + tol)
        result["gate_peak_mem"] = {
            "old_peak_bytes": old_m,
            "new_peak_bytes": new_m,
            "delta_pct": round((new_m / old_m - 1.0) * 100.0, 4),
            "tolerance_pct": args.tolerance,
            "regressed": mem_failed,
        }
    if args.json:
        print(json.dumps(result, indent=1))
    else:
        for name, c in changes.items():
            print(f"{name}: {c['old']} -> {c['new']}")
        if args.gate_bytes:
            g = result["gate_bytes"]
            print(f"bytes/step: {g['old_bytes_per_step']:.6g} -> "
                  f"{g['new_bytes_per_step']:.6g} "
                  f"({g['delta_pct']:+.3f}%, tolerance "
                  f"{args.tolerance}%)")
        if args.gate_peak_mem:
            g = result["gate_peak_mem"]
            print(f"peak HBM: {g['old_peak_bytes']:.6g} -> "
                  f"{g['new_peak_bytes']:.6g} "
                  f"({g['delta_pct']:+.3f}%, tolerance "
                  f"{args.tolerance}%)")
    if bytes_failed:
        print(f"BYTES REGRESSION: {BYTES_METRIC} grew "
              f"{result['gate_bytes']['delta_pct']:+.3f}% (> "
              f"{args.tolerance}% tolerance) — the step's compiled "
              "program moves more bytes by XLA's count than the "
              "baseline snapshot's. Fix the pass or re-baseline "
              "deliberately.", file=sys.stderr)
    if mem_failed:
        print(f"PEAK-MEM REGRESSION: {PEAK_MEM_METRIC} grew "
              f"{result['gate_peak_mem']['delta_pct']:+.3f}% (> "
              f"{args.tolerance}% tolerance) — the process now needs "
              "more HBM at peak than the baseline; on a real device "
              "that margin is the difference between fitting and an "
              "OOM at scale-up. Check donation/rematerialization or "
              "re-baseline deliberately.", file=sys.stderr)
    if bytes_failed or mem_failed:
        return 2
    if args.gate_bytes:
        print("bytes gate OK", file=sys.stderr)
    if args.gate_peak_mem:
        print("peak-mem gate OK", file=sys.stderr)
    return 0


# ---------------------------------------------------------------------------
# fleet aggregation / straggler detection (round 14)
# ---------------------------------------------------------------------------
def _pct(sorted_vals, q):
    """Nearest-rank percentile of an already-sorted list."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _rank_dirs(base):
    """``rank-<r>`` subdirectories of a fleet base dir, sorted by rank.

    A single-process run writes straight into the base dir (no
    ``rank-*`` layer), so when no subdirs exist the base itself is
    treated as rank 0 — ``fleet`` degrades to a one-row table instead
    of erroring.
    """
    out = []
    try:
        for name in os.listdir(base):
            if name.startswith("rank-"):
                try:
                    r = int(name[len("rank-"):])
                except ValueError:
                    continue
                path = os.path.join(base, name)
                if os.path.isdir(path):
                    out.append((r, path))
    except OSError as e:
        sys.exit(f"cannot list fleet dir {base}: {e}")
    out.sort()
    return out or [(0, base)]


def fleet_summary(base, straggler_factor=1.5):
    """Merge per-rank exporter dirs into one fleet view (the
    ``fleet --json`` payload; the multi-process straggler test pins
    this shape)."""
    ranks = []
    pooled = []
    fleet_events = []
    for r, path in _rank_dirs(base):
        events, torn = _read_events(path)
        fleet_events.extend(e for e in events
                            if str(e.get("kind", "")).startswith("fleet_"))
        walls = sorted(float(e["wall_s"]) for e in events
                       if e.get("kind") == "train_step"
                       and e.get("wall_s") is not None)
        row = {
            "rank": r,
            "dir": path,
            "events": len(events),
            "torn_lines": torn,
            "steps": len(walls),
        }
        if walls:
            row["mean_wall_s"] = round(_mean(walls), 6)
            row["p50_wall_s"] = round(_pct(walls, 50), 6)
            row["p99_wall_s"] = round(_pct(walls, 99), 6)
            pooled.extend(walls)
        ranks.append(row)
    # skew is judged on each rank's MEDIAN step wall, not its mean: the
    # first step of every rank is compile-dominated and would mask a
    # slow rank behind a shared multi-second outlier
    p50s = sorted(r["p50_wall_s"] for r in ranks if "p50_wall_s" in r)
    median = _pct(p50s, 50) if p50s else None
    stragglers = []
    for row in ranks:
        if median and row.get("p50_wall_s"):
            skew = row["p50_wall_s"] / median
            row["skew"] = round(skew, 4)
            row["straggler"] = skew >= straggler_factor
            if row["straggler"]:
                stragglers.append(row["rank"])
    pooled.sort()
    out = {
        "dir": base,
        "ranks": ranks,
        "world": len(ranks),
        "straggler_factor": straggler_factor,
        "stragglers": stragglers,
    }
    if pooled:
        out["fleet"] = {
            "steps": len(pooled),
            "mean_wall_s": round(_mean(pooled), 6),
            "p50_wall_s": round(_pct(pooled, 50), 6),
            "p99_wall_s": round(_pct(pooled, 99), 6),
            "median_rank_p50_s": round(median, 6),
        }
    if fleet_events:
        out["serving"] = _serving_fleet_summary(fleet_events)
    return out


def _serving_fleet_summary(events):
    """Aggregate the FleetRouter's ``fleet_*`` event stream (round 17)
    into per-replica routing counts plus per-request timelines: every
    hop of a request across replicas, keyed by the trace id the router
    propagated — the whole-fleet request view the per-replica latency
    histograms cannot give."""
    counts = {}
    by_replica = {}
    requests = {}
    for e in sorted(events, key=lambda e: e.get("ts", 0)):
        kind = e["kind"]
        counts[kind] = counts.get(kind, 0) + 1
        replica = e.get("replica") or e.get("from_replica")
        if kind == "fleet_route" and replica:
            by_replica[replica] = by_replica.get(replica, 0) + 1
        tid = e.get("trace_id")
        if tid:
            hop = {"event": kind, "ts": e.get("ts")}
            if replica:
                hop["replica"] = replica
            requests.setdefault(tid, []).append(hop)
    routes = counts.get("fleet_route", 0)
    sheds = counts.get("fleet_shed", 0)
    return {
        "events": counts,
        "routes_by_replica": dict(sorted(by_replica.items())),
        "shed_rate": round(sheds / max(1, routes + sheds), 6),
        "redispatched_requests": sum(
            1 for hops in requests.values()
            if any(h["event"] == "fleet_redispatch" for h in hops)),
        "requests": requests,
    }


def cmd_fleet(args):
    out = fleet_summary(_dir(args), args.straggler_factor)
    if args.json:
        print(json.dumps(out, indent=1))
        return 0
    print(f"fleet dir: {out['dir']}  ({out['world']} rank(s))")
    fl = out.get("fleet")
    if fl:
        print(f"fleet steps: {fl['steps']}  mean {fl['mean_wall_s']}s  "
              f"p50 {fl['p50_wall_s']}s  p99 {fl['p99_wall_s']}s")
    for row in out["ranks"]:
        if "mean_wall_s" not in row:
            print(f"  rank {row['rank']}: no train_step events")
            continue
        flag = "  <-- STRAGGLER" if row.get("straggler") else ""
        print(f"  rank {row['rank']}: {row['steps']} step(s), mean "
              f"{row['mean_wall_s']}s, p99 {row['p99_wall_s']}s, "
              f"skew x{row.get('skew', 1.0)}{flag}")
    if out["stragglers"]:
        print(f"stragglers (>= x{out['straggler_factor']} median rank "
              f"p50): {out['stragglers']}", file=sys.stderr)
    sv = out.get("serving")
    if sv:
        ev = sv["events"]
        print(f"serving fleet: {ev.get('fleet_route', 0)} route(s), "
              f"{ev.get('fleet_redispatch', 0)} redispatch(es), "
              f"{ev.get('fleet_shed', 0)} shed(s), "
              f"{ev.get('fleet_drain', 0)} drain(s), "
              f"{ev.get('fleet_replace', 0)} replace(s); shed rate "
              f"{sv['shed_rate']}")
        for replica, n in sv["routes_by_replica"].items():
            print(f"  {replica}: {n} request(s)")
        for tid, hops in sv["requests"].items():
            if len(hops) < 2:     # timelines: the multi-hop requests
                continue
            path = " -> ".join(
                f"{h['event'].replace('fleet_', '')}"
                + (f"@{h['replica']}" if h.get("replica") else "")
                for h in hops)
            print(f"  request {tid}: {path}")
    return 0


# ---------------------------------------------------------------------------
# Chrome-trace inspection (round 14)
# ---------------------------------------------------------------------------
_TRACE_PH_REQUIRED = {
    "X": ("name", "cat", "ph", "ts", "dur", "pid", "tid"),
    "M": ("name", "ph", "pid"),
}


def validate_trace(tree, path="<trace>"):
    """Chrome trace-event schema check; returns the event list.

    Exits with a message naming the first offending event — the same
    validation the trace tests run, so a file this accepts loads in
    ``chrome://tracing``/Perfetto.
    """
    events = tree.get("traceEvents")
    if not isinstance(events, list):
        sys.exit(f"{path}: no traceEvents list — not a Chrome trace")
    for i, e in enumerate(events):
        ph = e.get("ph")
        req = _TRACE_PH_REQUIRED.get(ph)
        if req is None:
            sys.exit(f"{path}: event {i} has unsupported ph={ph!r}")
        for field in req:
            if field not in e:
                sys.exit(f"{path}: event {i} (ph={ph}) missing "
                         f"required field {field!r}")
        if ph == "X" and (not isinstance(e["ts"], (int, float))
                          or e["ts"] < 0 or e["dur"] < 0):
            sys.exit(f"{path}: event {i} has invalid ts/dur")
    return events


def cmd_trace(args):
    path = args.path
    if not path:
        from mxnet_tpu.telemetry import trace as _trace
        directory = args.dir or _trace.trace_dir()
        if not directory:
            sys.exit("no trace file: pass PATH, --dir, or set "
                     "MXTPU_TRACE_DIR")
        files = _trace.trace_files(directory)
        if not files:
            sys.exit(f"no trace-*.json under {directory}")
        path = files[-1]
    try:
        with open(path) as f:
            tree = json.load(f)
    except (OSError, ValueError) as e:
        sys.exit(f"cannot read trace {path}: {e}")
    events = validate_trace(tree, path)
    spans = [e for e in events if e.get("ph") == "X"]
    if args.json:
        cats = {}
        for e in spans:
            c = cats.setdefault(e.get("cat", "?"),
                                {"spans": 0, "total_us": 0.0})
            c["spans"] += 1
            c["total_us"] = round(c["total_us"] + e["dur"], 3)
        print(json.dumps({
            "path": path,
            "events": len(events),
            "spans": len(spans),
            "dropped_spans": tree.get("otherData", {})
                                 .get("dropped_spans", 0),
            "by_cat": cats,
        }, indent=1))
        return 0
    print(f"trace: {path}")
    print(f"events: {len(events)} ({len(spans)} span(s))")
    dropped = tree.get("otherData", {}).get("dropped_spans", 0)
    if dropped:
        print(f"dropped spans (ring overflow): {dropped}")
    by_name = {}
    for e in spans:
        key = (e.get("cat", "?"), e["name"])
        cnt, tot = by_name.get(key, (0, 0.0))
        by_name[key] = (cnt + 1, tot + e["dur"])
    for (cat, name), (cnt, tot) in sorted(
            by_name.items(), key=lambda kv: -kv[1][1]):
        print(f"  {cat:<8} {name:<28} x{cnt:<5} {tot / 1e3:.3f} ms")
    print("open in chrome://tracing or https://ui.perfetto.dev for "
          "the timeline view")
    return 0


def cmd_render(args):
    snap_path = _newest_snapshot(_dir(args))
    if not snap_path:
        sys.exit("no snapshot-*.json in the telemetry directory")
    with open(snap_path) as f:
        snap = json.load(f)
    from mxnet_tpu.telemetry.export import render_prometheus
    sys.stdout.write(render_prometheus(snap.get("metrics", {})))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Tail / summarize / diff durable telemetry exports")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("tail", help="print the last N events")
    p.add_argument("--dir", default=None)
    p.add_argument("-n", type=int, default=20)
    p.add_argument("--kind", default=None,
                   help="only events of this kind")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_tail)

    p = sub.add_parser("summary",
                       help="aggregate the event stream + newest snapshot")
    p.add_argument("--dir", default=None)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_summary)

    p = sub.add_parser("diff",
                       help="compare two snapshots; --gate-bytes fails "
                            "on a bytes-accessed regression")
    p.add_argument("old")
    p.add_argument("new")
    p.add_argument("--gate-bytes", action="store_true",
                   help="exit 2 when step::bytes_accessed grew beyond "
                        "--tolerance")
    p.add_argument("--gate-peak-mem", action="store_true",
                   help="exit 2 when mem::process_peak_bytes grew "
                        "beyond --tolerance")
    p.add_argument("--tolerance", type=float, default=0.0,
                   help="allowed growth in percent (default 0: "
                        "strictly no regression)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_diff)

    p = sub.add_parser("fleet",
                       help="merge per-rank exporter dirs; flag "
                            "straggler ranks")
    p.add_argument("--dir", default=None,
                   help="fleet base dir holding rank-<r>/ subdirs")
    p.add_argument("--straggler-factor", type=float, default=1.5,
                   help="flag ranks whose median step wall exceeds this "
                        "multiple of the fleet median (default 1.5)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_fleet)

    p = sub.add_parser("trace",
                       help="validate + summarize a Chrome trace-event "
                            "JSON (newest under MXTPU_TRACE_DIR by "
                            "default)")
    p.add_argument("path", nargs="?", default=None)
    p.add_argument("--dir", default=None,
                   help="trace directory (default: MXTPU_TRACE_DIR)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("render",
                       help="newest snapshot in Prometheus text format")
    p.add_argument("--dir", default=None)
    p.set_defaults(fn=cmd_render)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
