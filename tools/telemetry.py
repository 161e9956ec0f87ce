#!/usr/bin/env python
"""Operate on durable telemetry exports (MXTPU_TELEMETRY_DIR).

The telemetry subsystem (``mxnet_tpu/telemetry/``) writes a rotating
JSONL event log plus periodic full-report snapshots. This CLI is the
operational surface:

    telemetry.py tail    [--dir D] [-n N] [--json] [--kind K]
    telemetry.py summary [--dir D] [--json]
    telemetry.py diff    A.json B.json [--json]
                         [--gate-bytes] [--gate-peak-mem]
                         [--gate-shed-rate] [--gate-slo]
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
``--gate-bytes`` exits nonzero when ``step::bytes_accessed`` regressed
between them: the r6 "strictly fewer bytes" pin generalized into the
scriptable regression gate every fusion/pass PR runs (ROADMAP item 2);
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

Round 17 (serving fleet): ``diff --gate-shed-rate`` exits 2 when the
fraction of fleet-admitted requests shed (``fleet::shed_rate`` gauge,
or a BENCH file's ``fleet_serving.shed_rate``) regressed — the serving
twin of the straggler gate; and ``fleet`` additionally aggregates the
FleetRouter's ``fleet_route`` / ``fleet_redispatch`` / ``fleet_shed`` /
``fleet_drain`` / ``fleet_replace`` events into a per-replica routing
table plus per-request timelines (a request's hops across replicas,
keyed by its propagated trace id).

Round 18 (mesh-native training): ``diff`` also reads a BENCH file's
``multichip_fused`` section — per-device step bytes of the 8-device
fused program and the ZeRO-1 vs replicated optimizer HBM — and under
``--gate-bytes`` additionally gates the per-device bytes when BOTH
files carry the section (a baseline predating round 18 reports the new
reading without gating). Driver-wrapped BENCH files (``{"parsed":
{...}}`` envelopes) unwrap transparently everywhere.

Round 19 (quantization): ``diff`` also reads a BENCH file's
``quantized_serving`` section — the int8-PTQ serving program's bytes
as a fraction of the f32 pipeline's, and the int8-KV decode step's
bytes as a fraction of the f32-cache step's — and under
``--gate-bytes`` gates BOTH ratios when the two files carry the
section (a pre-r19 baseline reports the new readings ungated, the
``multichip_fused`` precedent). A growing ratio means quantization is
buying fewer bytes than it used to — a quantization regression even
when absolute bytes shrank for other reasons.

Round 21 (speculative decode): ``diff`` also reads a BENCH file's
``speculative_decode`` section — bytes-moved-per-ACCEPTED-token as a
fraction of the plain decode step's bytes-per-token, plus the
accepted-tokens-per-verify-round reading it stands on — and under
``--gate-bytes`` gates the ratio when BOTH files carry the section (a
pre-r21 baseline reports the new readings ungated, the
``quantized_serving`` precedent). A growing ratio means speculation is
amortizing less per token actually kept — a draft-quality or
verify-cost regression even when raw tok/s moved the other way.

Round 20 (autoscaling + multi-tenancy): ``diff --gate-slo`` reads a
BENCH file's ``fleet_autoscale`` section — per-tenant
``slo_violations`` counts from the chaos-drilled ramp (requests that
completed over the tenant's latency target, or failed after
admission) — and exits 2 when ANY tenant in the NEW run violated.
Unlike the relative gates this one is absolute: the tenant contract
is zero violations, so a pre-r20 baseline without the section only
changes the report's note, never the verdict.

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
SHED_RATE_METRIC = "fleet::shed_rate"


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
# diff / bytes-accessed regression gate
# ---------------------------------------------------------------------------
def _unwrap_bench(tree):
    """The driver wraps bench.py's JSON line in ``{"n", "cmd", "rc",
    "tail", "parsed": {...}}`` — operate on the parsed payload when the
    envelope is present."""
    parsed = tree.get("parsed") if isinstance(tree, dict) else None
    if isinstance(parsed, dict) and ("metric" in parsed
                                     or "metrics" in parsed):
        return parsed
    return tree


def _load_multichip(tree):
    """The BENCH ``multichip_fused`` section's gateable readings, or
    None when the file predates round 18 (or the section errored)."""
    mc = tree.get("multichip_fused")
    if not isinstance(mc, dict) or "dp" not in mc:
        return None
    dp = mc.get("dp") or {}
    hbm = dp.get("optimizer_hbm") or {}
    return {
        "per_device_step_bytes": dp.get("per_device_step_bytes"),
        "zero1_per_device_bytes": hbm.get("zero1_per_device_bytes"),
        "replicated_per_device_bytes":
            hbm.get("replicated_per_device_bytes"),
        "zero1_ratio": hbm.get("zero1_ratio"),
    }


def _load_quantized(tree):
    """The BENCH ``quantized_serving`` section's gateable readings, or
    None when the file predates round 19 (or the section errored)."""
    q = tree.get("quantized_serving")
    if not isinstance(q, dict) or "serving_bytes_ratio" not in q:
        return None
    return {
        "serving_bytes_ratio": q.get("serving_bytes_ratio"),
        "decode_step_bytes_ratio": q.get("decode_step_bytes_ratio"),
        "kv_cache_ratio": q.get("kv_cache_ratio"),
    }


def _load_speculative(tree):
    """The BENCH ``speculative_decode`` section's gateable readings, or
    None when the file predates round 21 (or the section errored)."""
    s = tree.get("speculative_decode")
    if not isinstance(s, dict) or \
            "bytes_per_accepted_token_ratio" not in s:
        return None
    return {
        "bytes_per_accepted_token_ratio":
            s.get("bytes_per_accepted_token_ratio"),
        "accepted_per_step": s.get("accepted_per_step"),
        "acceptance_rate": s.get("acceptance_rate"),
    }


def _load_bytes(tree, path):
    """bytes-accessed-per-step from a snapshot (metrics gauge), a
    BENCH JSON (bench.py's ``xla_bytes_accessed_per_step``), or — for
    a multichip-only BENCH file (``bench.py multichip_fused``
    standalone mode, where no single-chip step runs) — the 8-device
    program's per-device bytes."""
    m = tree.get("metrics", {}).get(BYTES_METRIC)
    if isinstance(m, dict) and m.get("value"):
        return float(m["value"])
    v = tree.get("xla_bytes_accessed_per_step")
    if v:
        return float(v)
    t = tree.get("telemetry", {})
    m = t.get("metrics", {}).get(BYTES_METRIC) if isinstance(t, dict) \
        else None
    if isinstance(m, dict) and m.get("value"):
        return float(m["value"])
    mc = _load_multichip(tree)
    if mc and mc.get("per_device_step_bytes"):
        return float(mc["per_device_step_bytes"])
    # quantized-only BENCH file (bench.py quantized_serving standalone
    # mode): the quantized decode program's step bytes — the program
    # that run benchmarks
    q = tree.get("quantized_serving")
    if isinstance(q, dict) and q.get("decode_step_bytes_int8"):
        return float(q["decode_step_bytes_int8"])
    # speculative-only BENCH file (bench.py speculative_decode
    # standalone mode): the plain decode step's per-token bytes — the
    # baseline the speculative ratio in that run is measured against
    s = tree.get("speculative_decode")
    if isinstance(s, dict) and s.get("plain_decode_bytes_per_token"):
        return float(s["plain_decode_bytes_per_token"])
    sys.exit(f"{path}: no {BYTES_METRIC} metric (and no "
             "xla_bytes_accessed_per_step, multichip_fused, "
             "quantized_serving, or speculative_decode field) — not a "
             "telemetry snapshot/BENCH file, or the run recorded no "
             "step costs")


def _bytes_source(tree):
    """Which program _load_bytes would read for this file: ``step``
    (the single-chip train step) or ``multichip`` (the 8-device
    per-device fallback). Two files with DIFFERENT sources measured
    different programs — the primary gate records their delta but does
    not fail on it (the multichip sibling gate handles like-for-like
    multichip comparisons)."""
    m = tree.get("metrics", {}).get(BYTES_METRIC)
    if isinstance(m, dict) and m.get("value"):
        return "step"
    if tree.get("xla_bytes_accessed_per_step"):
        return "step"
    t = tree.get("telemetry", {})
    m = t.get("metrics", {}).get(BYTES_METRIC) if isinstance(t, dict) \
        else None
    if isinstance(m, dict) and m.get("value"):
        return "step"
    mc = _load_multichip(tree)
    if mc and mc.get("per_device_step_bytes"):
        return "multichip"
    return "quantized"


def _load_peak_mem(tree, path):
    """process-peak HBM bytes from a snapshot (``mem::`` gauge) or a
    BENCH JSON (bench.py's ``memory.process_peak_bytes``)."""
    m = tree.get("metrics", {}).get(PEAK_MEM_METRIC)
    if isinstance(m, dict) and m.get("value"):
        return float(m["value"])
    mem = tree.get("memory")
    if isinstance(mem, dict) and mem.get("process_peak_bytes"):
        return float(mem["process_peak_bytes"])
    t = tree.get("telemetry", {})
    m = t.get("metrics", {}).get(PEAK_MEM_METRIC) if isinstance(t, dict) \
        else None
    if isinstance(m, dict) and m.get("value"):
        return float(m["value"])
    sys.exit(f"{path}: no {PEAK_MEM_METRIC} metric (and no "
             "memory.process_peak_bytes field) — not a telemetry "
             "snapshot/BENCH file, or the run recorded no program "
             "memory analyses")


def _load_shed_rate(tree, path):
    """Fleet shed rate (shed requests / routed requests) from a
    snapshot (``fleet::shed_rate`` gauge) or a BENCH JSON (bench.py's
    ``fleet_serving.shed_rate``). Zero is a meaningful reading — the
    healthy fleet sheds nothing — so presence, not truthiness, decides."""
    m = tree.get("metrics", {}).get(SHED_RATE_METRIC)
    if isinstance(m, dict) and "value" in m:
        return float(m["value"])
    fs = tree.get("fleet_serving")
    if isinstance(fs, dict) and "shed_rate" in fs:
        return float(fs["shed_rate"])
    t = tree.get("telemetry", {})
    m = t.get("metrics", {}).get(SHED_RATE_METRIC) if isinstance(t, dict) \
        else None
    if isinstance(m, dict) and "value" in m:
        return float(m["value"])
    sys.exit(f"{path}: no {SHED_RATE_METRIC} metric (and no "
             "fleet_serving.shed_rate field) — not a telemetry "
             "snapshot/BENCH file, or the run served no fleet traffic")


def _load_slo_violations(tree, path, required=True):
    """Per-tenant SLO-violation counts from a BENCH JSON's
    ``fleet_autoscale`` section (round 20): ``tenants.<name>.
    slo_violations`` counts requests that completed over the tenant's
    latency target PLUS requests the fleet failed after admission.
    Returns {tenant: count}, or None when the file predates the
    section (required=False)."""
    fa = tree.get("fleet_autoscale")
    if isinstance(fa, dict) and isinstance(fa.get("tenants"), dict):
        out = {}
        for name, t in fa["tenants"].items():
            if isinstance(t, dict) and "slo_violations" in t:
                out[name] = int(t["slo_violations"])
        if out:
            return out
    if required:
        sys.exit(f"{path}: no fleet_autoscale.tenants.*.slo_violations "
                 "readings — not a round-20 BENCH file, or the run "
                 "drove no multi-tenant fleet traffic")
    return None


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
    old_t, new_t = (_unwrap_bench(t) for t in trees)
    old_v, new_v = _flat_values(old_t), _flat_values(new_t)
    changes = {}
    for name in sorted(set(old_v) | set(new_v)):
        a, b = old_v.get(name), new_v.get(name)
        if a != b:
            changes[name] = {"old": a, "new": b}
    result = {"old": args.old, "new": args.new, "changed": changes}
    gate_failed = False
    if args.gate_bytes:
        old_b = _load_bytes(old_t, args.old)
        new_b = _load_bytes(new_t, args.new)
        tol = args.tolerance / 100.0
        src_old, src_new = _bytes_source(old_t), _bytes_source(new_t)
        comparable = src_old == src_new
        bound = old_b * (1.0 + tol)
        gate_failed = comparable and new_b > bound
        result["gate_bytes"] = {
            "old_bytes_per_step": old_b,
            "new_bytes_per_step": new_b,
            "delta_pct": round((new_b / old_b - 1.0) * 100.0, 4),
            "tolerance_pct": args.tolerance,
            "regressed": gate_failed,
        }
        if not comparable:
            result["gate_bytes"]["note"] = (
                f"readings measure different programs ({src_old} vs "
                f"{src_new}) — delta recorded, not gated")
        # round-18 sibling reading: the 8-device fused program's
        # per-device bytes. Gated only when BOTH files carry the
        # multichip_fused section — against a pre-r18 baseline the new
        # reading is reported ungated (it becomes the baseline)
        old_mc, new_mc = _load_multichip(old_t), _load_multichip(new_t)
        if new_mc is not None:
            entry = dict(new_mc)
            ob = (old_mc or {}).get("per_device_step_bytes")
            nb = new_mc.get("per_device_step_bytes")
            if ob and nb:
                entry["old_per_device_step_bytes"] = ob
                entry["delta_pct"] = round((nb / ob - 1.0) * 100.0, 4)
                entry["regressed"] = nb > ob * (1.0 + tol)
                gate_failed = gate_failed or entry["regressed"]
            else:
                entry["regressed"] = False
                entry["baseline"] = "no multichip_fused section in "\
                    f"{args.old} (pre-r18) — reading recorded, not gated"
            result["gate_bytes_multichip"] = entry
        # round-19 sibling: the quantized_serving section's bytes
        # RATIOS (quantized program / f32 program) — ratio, not
        # absolute, so the gate judges what quantization buys
        # independently of model-size drift. Gated only when BOTH files
        # carry the section; a pre-r19 baseline reports the new
        # readings ungated (they become the baseline)
        old_q, new_q = _load_quantized(old_t), _load_quantized(new_t)
        if new_q is not None:
            entry = dict(new_q)
            orq = (old_q or {}).get("serving_bytes_ratio")
            nrq = new_q.get("serving_bytes_ratio")
            odr = (old_q or {}).get("decode_step_bytes_ratio")
            ndr = new_q.get("decode_step_bytes_ratio")
            if orq and nrq:
                entry["old_serving_bytes_ratio"] = orq
                entry["old_decode_step_bytes_ratio"] = odr
                entry["regressed"] = bool(
                    nrq > orq * (1.0 + tol)
                    or (odr and ndr and ndr > odr * (1.0 + tol)))
                gate_failed = gate_failed or entry["regressed"]
            else:
                entry["regressed"] = False
                entry["baseline"] = (
                    "no quantized_serving section in "
                    f"{args.old} (pre-r19) — reading recorded, not gated")
            result["gate_bytes_quantized"] = entry
        # round-21 sibling: the speculative_decode section's
        # bytes-per-ACCEPTED-token RATIO (speculative path / plain
        # decode step). Ratio, not absolute — the gate judges what
        # speculation amortizes per kept token independently of
        # model-size drift. Gated only when BOTH files carry the
        # section; a pre-r21 baseline reports the new readings ungated
        old_s, new_s = _load_speculative(old_t), _load_speculative(new_t)
        if new_s is not None:
            entry = dict(new_s)
            ors = (old_s or {}).get("bytes_per_accepted_token_ratio")
            nrs = new_s.get("bytes_per_accepted_token_ratio")
            if ors and nrs:
                entry["old_bytes_per_accepted_token_ratio"] = ors
                entry["regressed"] = bool(nrs > ors * (1.0 + tol))
                gate_failed = gate_failed or entry["regressed"]
            else:
                entry["regressed"] = False
                entry["baseline"] = (
                    "no speculative_decode section in "
                    f"{args.old} (pre-r21) — reading recorded, not gated")
            result["gate_bytes_speculative"] = entry
    mem_failed = False
    if args.gate_peak_mem:
        old_m = _load_peak_mem(old_t, args.old)
        new_m = _load_peak_mem(new_t, args.new)
        tol = args.tolerance / 100.0
        mem_failed = new_m > old_m * (1.0 + tol)
        result["gate_peak_mem"] = {
            "old_peak_bytes": old_m,
            "new_peak_bytes": new_m,
            "delta_pct": round((new_m / old_m - 1.0) * 100.0, 4),
            "tolerance_pct": args.tolerance,
            "regressed": mem_failed,
        }
    shed_failed = False
    if args.gate_shed_rate:
        old_s = _load_shed_rate(old_t, args.old)
        new_s = _load_shed_rate(new_t, args.new)
        tol = args.tolerance / 100.0
        # relative tolerance against a zero baseline is meaningless —
        # a healthy fleet sheds nothing, so ANY shedding regresses it
        shed_failed = new_s > old_s * (1.0 + tol) + 1e-12
        result["gate_shed_rate"] = {
            "old_shed_rate": old_s,
            "new_shed_rate": new_s,
            "delta_pct": round((new_s / old_s - 1.0) * 100.0, 4)
            if old_s else None,
            "tolerance_pct": args.tolerance,
            "regressed": shed_failed,
        }
    slo_failed = False
    if args.gate_slo:
        new_slo = _load_slo_violations(new_t, args.new)
        old_slo = _load_slo_violations(old_t, args.old, required=False)
        # the SLO gate is ABSOLUTE, not relative: a tenant's contract
        # is "zero admitted requests violated", so ANY violation in
        # the new run fails regardless of what the baseline did
        bad = {t: v for t, v in sorted(new_slo.items()) if v > 0}
        slo_failed = bool(bad)
        result["gate_slo"] = {
            "old_slo_violations": old_slo,
            "new_slo_violations": new_slo,
            "violating_tenants": bad,
            "regressed": slo_failed,
        }
        if old_slo is None:
            result["gate_slo"]["note"] = (
                f"{args.old} has no fleet_autoscale section (pre-r20 "
                "baseline) — the gate is absolute on the new run "
                "anyway")
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
                  f"{args.tolerance}%)"
                  + (f" [{g['note']}]" if g.get("note") else ""))
            mc = result.get("gate_bytes_multichip")
            if mc:
                if "old_per_device_step_bytes" in mc:
                    print(f"multichip per-device bytes/step: "
                          f"{mc['old_per_device_step_bytes']:.6g} -> "
                          f"{mc['per_device_step_bytes']:.6g} "
                          f"({mc['delta_pct']:+.3f}%)")
                else:
                    print(f"multichip per-device bytes/step: "
                          f"{mc['per_device_step_bytes']:.6g} "
                          "(new baseline, ungated)")
                if mc.get("zero1_ratio") is not None:
                    print(f"multichip ZeRO-1 optimizer bytes/replica: "
                          f"{mc['zero1_per_device_bytes']:.6g} vs "
                          f"replicated "
                          f"{mc['replicated_per_device_bytes']:.6g} "
                          f"(ratio {mc['zero1_ratio']})")
            q = result.get("gate_bytes_quantized")
            if q:
                if "old_serving_bytes_ratio" in q:
                    print(f"quantized serving bytes ratio: "
                          f"{q['old_serving_bytes_ratio']:.4f} -> "
                          f"{q['serving_bytes_ratio']:.4f}; decode step "
                          f"{q.get('old_decode_step_bytes_ratio')} -> "
                          f"{q.get('decode_step_bytes_ratio')}")
                else:
                    print(f"quantized serving bytes ratio: "
                          f"{q['serving_bytes_ratio']:.4f}, decode step "
                          f"{q.get('decode_step_bytes_ratio')}, KV cache "
                          f"{q.get('kv_cache_ratio')} "
                          "(new baseline, ungated)")
            sp = result.get("gate_bytes_speculative")
            if sp:
                if "old_bytes_per_accepted_token_ratio" in sp:
                    print(f"speculative bytes/accepted-token ratio: "
                          f"{sp['old_bytes_per_accepted_token_ratio']:.4f}"
                          f" -> "
                          f"{sp['bytes_per_accepted_token_ratio']:.4f}; "
                          f"accepted/step "
                          f"{sp.get('accepted_per_step')}")
                else:
                    print(f"speculative bytes/accepted-token ratio: "
                          f"{sp['bytes_per_accepted_token_ratio']:.4f}, "
                          f"accepted/step {sp.get('accepted_per_step')} "
                          "(new baseline, ungated)")
        if args.gate_peak_mem:
            g = result["gate_peak_mem"]
            print(f"peak HBM: {g['old_peak_bytes']:.6g} -> "
                  f"{g['new_peak_bytes']:.6g} "
                  f"({g['delta_pct']:+.3f}%, tolerance "
                  f"{args.tolerance}%)")
        if args.gate_shed_rate:
            g = result["gate_shed_rate"]
            print(f"shed rate: {g['old_shed_rate']:.6g} -> "
                  f"{g['new_shed_rate']:.6g} (tolerance "
                  f"{args.tolerance}%)")
        if args.gate_slo:
            g = result["gate_slo"]
            readings = ", ".join(f"{t}={v}" for t, v in
                                 sorted(g["new_slo_violations"].items()))
            print(f"per-tenant SLO violations: {readings}"
                  + (f" [{g['note']}]" if g.get("note") else ""))
    if gate_failed:
        if result["gate_bytes"]["regressed"]:
            print(f"BYTES REGRESSION: {BYTES_METRIC} grew "
                  f"{result['gate_bytes']['delta_pct']:+.3f}% (> "
                  f"{args.tolerance}% tolerance) — the step moves MORE "
                  "HBM bytes than the baseline snapshot; in the "
                  "bandwidth-bound regime that is a throughput "
                  "regression (ROADMAP item 2's currency). Fix the "
                  "pass or re-baseline deliberately.", file=sys.stderr)
        mc = result.get("gate_bytes_multichip") or {}
        if mc.get("regressed"):
            print("BYTES REGRESSION (multichip): the 8-device fused "
                  f"program's per-device bytes grew "
                  f"{mc['delta_pct']:+.3f}% (> {args.tolerance}% "
                  "tolerance) — the sharded train step moves more HBM "
                  "per chip than the baseline (a mesh-pass or "
                  "partitioning regression). Fix it or re-baseline "
                  "deliberately.", file=sys.stderr)
        q = result.get("gate_bytes_quantized") or {}
        if q.get("regressed"):
            print("BYTES REGRESSION (quantized): the int8 serving/"
                  "decode programs now move a LARGER fraction of the "
                  f"f32 programs' bytes (serving ratio "
                  f"{q.get('old_serving_bytes_ratio')} -> "
                  f"{q.get('serving_bytes_ratio')}, decode step "
                  f"{q.get('old_decode_step_bytes_ratio')} -> "
                  f"{q.get('decode_step_bytes_ratio')}) — quantization "
                  "is buying less than the baseline (a dequantize "
                  "stopped fusing, or a site stopped quantizing). Fix "
                  "the pass or re-baseline deliberately.",
                  file=sys.stderr)
        sp = result.get("gate_bytes_speculative") or {}
        if sp.get("regressed"):
            print("BYTES REGRESSION (speculative): bytes moved per "
                  "ACCEPTED token grew as a fraction of the plain "
                  "decode step's bytes-per-token ("
                  f"{sp.get('old_bytes_per_accepted_token_ratio')} -> "
                  f"{sp.get('bytes_per_accepted_token_ratio')}, "
                  f"accepted/step {sp.get('accepted_per_step')}) — the "
                  "draft accepts less or the verify program costs more "
                  "than the baseline. Fix the draft/depth or "
                  "re-baseline deliberately.", file=sys.stderr)
    if mem_failed:
        print(f"PEAK-MEM REGRESSION: {PEAK_MEM_METRIC} grew "
              f"{result['gate_peak_mem']['delta_pct']:+.3f}% (> "
              f"{args.tolerance}% tolerance) — the process now needs "
              "more HBM at peak than the baseline; on a real device "
              "that margin is the difference between fitting and an "
              "OOM at scale-up. Check donation/rematerialization or "
              "re-baseline deliberately.", file=sys.stderr)
    if shed_failed:
        g = result["gate_shed_rate"]
        print(f"SHED-RATE REGRESSION: {SHED_RATE_METRIC} grew "
              f"{g['old_shed_rate']:.6g} -> {g['new_shed_rate']:.6g} "
              f"(> {args.tolerance}% tolerance) — the fleet now "
              "rejects a larger fraction of admitted requests than the "
              "baseline: capacity shrank, replicas are sicker, or the "
              "router stopped re-dispatching. Each shed is a client "
              "retry or a dropped answer. Fix the fleet or re-baseline "
              "deliberately.", file=sys.stderr)
    if slo_failed:
        g = result["gate_slo"]
        viol = ", ".join(f"{t}: {v}" for t, v in
                         g["violating_tenants"].items())
        print(f"SLO VIOLATION: tenants violated their contract during "
              f"the autoscale run ({viol}) — an admitted request "
              "either completed over its tenant's latency target or "
              "failed after admission. The contract is absolute "
              "(zero): fix the fleet (capacity, hysteresis, the "
              "degradation ladder) — there is no re-baselining an SLO "
              "away.", file=sys.stderr)
    if gate_failed or mem_failed or shed_failed or slo_failed:
        return 2
    if args.gate_bytes:
        print("bytes gate OK", file=sys.stderr)
    if args.gate_peak_mem:
        print("peak-mem gate OK", file=sys.stderr)
    if args.gate_shed_rate:
        print("shed-rate gate OK", file=sys.stderr)
    if args.gate_slo:
        print("slo gate OK", file=sys.stderr)
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
    p.add_argument("--gate-slo", action="store_true",
                   help="exit 2 when any tenant in the new BENCH "
                        "file's fleet_autoscale section counted an "
                        "SLO violation (absolute gate: the contract "
                        "is zero)")
    p.add_argument("--gate-shed-rate", action="store_true",
                   help="exit 2 when the fleet shed rate "
                        "(fleet::shed_rate / fleet_serving.shed_rate) "
                        "grew beyond --tolerance")
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
