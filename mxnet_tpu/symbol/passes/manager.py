"""Pass manager: ordered pipeline + measured bytes-accessed gate.

The rewrites exist to cut HBM traffic, so
XLA's own bytes count is the gate's currency and every rewrite must
EARN its place by measurement, in the spirit of TVM's
measurement-driven optimization (PAPERS.md). The manager runs the registered passes in order over a
symbol graph and, for each pass that fired, lowers + compiles the
program proxy before and after the rewrite and reads XLA cost
analysis's "bytes accessed": a pass that does not STRICTLY reduce
bytes on the program it rewrote is rejected at apply time — r6's
"strictly fewer bytes" test pin and r11's ``tools/telemetry.py diff
--gate-bytes`` generalized into the framework's built-in invariant.

Gating (``MXTPU_PASS_GATE_BYTES``): ``auto`` (default) measures and
gates passes that auto-enabled, and trusts passes the user explicitly
forced on (``<flag>=1`` means "I want this rewrite" — and keeps the
measurement compiles off the test/CI hot path); ``1`` measures and
gates everything; ``0`` trusts everything. Measurements are memoized
per (graph, shapes, mode) so an unchanged graph is never re-lowered.

Every decision is observable: per-pass ``passes::<name>::bytes_delta``
/ ``::sites`` metrics, ``passes::applied`` / ``rejected`` / ``skipped``
(+ per-reason) counters — mesh-bind skips are COUNTED with a reason,
not silently dropped per-site like the r6 hook — and ``pass_report()``
(telemetry collector ``passes``) carries the full pipeline records.
``fusion_report()`` remains the legacy-compatible filtered view of the
same store (symbol/fusion.py delegates here).
"""
from __future__ import annotations

import contextlib
import hashlib
import threading
from typing import Dict, List, Optional, Tuple

from ... import config
from ...telemetry import registry as _treg
from ...telemetry import trace as _trace
from .base import GraphPass, PassContext, flag_active

__all__ = ["PassManager", "default_manager", "apply_pipeline",
           "pass_report", "legacy_fusion_entry", "pipeline_key_material",
           "measure_symbol_bytes", "collect_fusion",
           "measure_memo_scope", "reset_measure_memo"]

# pipeline records, most recent last (shared by pass_report and the
# legacy fusion_report view; each view consumes independently via its
# own seen-flag so their reset semantics stay per-surface)
_RECORDS: List[dict] = []
_MAX_RECORDS = 64
_LOCK = threading.RLock()

# (graph digest, shapes, mode) -> measured bytes-accessed
_MEASURE_MEMO: Dict[tuple, Optional[float]] = {}
_MEASURE_MEMO_MAX = 128


def reset_measure_memo():
    """Drop every memoized bytes measurement. The memo key is (graph,
    shapes, mode, hoist set) ONLY — anything that changes the LOWERING
    of an unchanged graph (``MXTPU_PALLAS_TILES``, a backend flip) must
    reset it or a later measurement silently reuses a number taken
    under the old regime."""
    with _LOCK:
        _MEASURE_MEMO.clear()


@contextlib.contextmanager
def measure_memo_scope():
    """Isolate the measurement memo for one scope (the tuner wraps
    every trial in this): entries memoized before the scope are not
    visible inside it, and entries measured inside are discarded on
    exit. Two trials differing only in env regime — same graph JSON,
    different ``MXTPU_PALLAS_TILES`` — therefore never share a
    measurement, while the ambient memo (binds outside any trial) is
    preserved across the search."""
    with _LOCK:
        saved = dict(_MEASURE_MEMO)
        _MEASURE_MEMO.clear()
    try:
        yield
    finally:
        with _LOCK:
            _MEASURE_MEMO.clear()
            _MEASURE_MEMO.update(saved)


def _record(report: dict):
    with _LOCK:
        _RECORDS.append(report)
        del _RECORDS[:-_MAX_RECORDS]


def record_legacy_fusion(tag: str, rep: dict, status: str):
    """Entry point for symbol/fusion.py's standalone ``maybe_fuse``:
    its rewrites land in the same store the pipeline fills, so
    fusion_report()/pass_report() cover direct callers too."""
    _record({
        "tag": tag, "mode": "?",
        "passes": [{"pass": "pallas_fusion", "flag": "on",
                    "status": status, "sites": rep.get("sites", []),
                    "bailouts": rep.get("bailouts", [])}],
        "baseline_bytes": None, "final_bytes": None,
        "_seen": {"passes": False, "fusion": False},
    })


# ---------------------------------------------------------------------------
# bytes measurement (the gate's objective function)
# ---------------------------------------------------------------------------
def _mesh_material(mesh):
    """Memo-key material for a mesh: axis names/sizes + device ids.
    None for single-device binds so keys stay byte-identical with
    pre-mesh entries."""
    if mesh is None:
        return None
    try:
        return (tuple((str(k), int(v)) for k, v in mesh.shape.items()),
                tuple(int(d.id) for d in mesh.devices.flat))
    except Exception:
        return ("mesh",)


def measure_symbol_bytes(sym, shapes, mode="train", data_names=None,
                         mesh=None, batch_names=None, data_axis="data"):
    """XLA cost-analysis "bytes accessed" of the program proxy for
    ``sym``: the jitted forward (eval mode) for ``infer``/``serving``
    programs, the jitted implicit-loss gradient program for ``train``
    (the backward is where the analytic-VJP fusion savings live, so a
    train-mode gate must see it). With ``data_names`` (serving), the
    proxy applies the Predictor's parameter-expression hoisting
    (hoist.py) so the gate judges the frozen program actually run, not
    one that re-evaluates weight-constant arithmetic per call.

    With ``mesh`` (round 18), the proxy lowers under the mesh with
    ``batch_names`` inputs sharded over ``data_axis`` and everything
    else replicated, inside ``pallas_fused.mesh_scope`` so the fused
    ops shard_map themselves — XLA's cost analysis of a sharded program
    reports PER-DEVICE bytes, which is the number the multi-chip step
    actually moves and therefore the number the gate must judge.
    Returns None when the backend exposes no cost analysis — the gate
    then counts the pass ``unmeasured`` instead of guessing. A program
    the backend REFUSES to compile is not that case: the error
    propagates and fails the bind, because the real program would be
    refused too. Memoized per (graph JSON, shapes, mode, hoist set,
    mesh, batch set)."""
    kind = "train" if mode == "train" else "infer"
    try:
        digest = hashlib.sha256(sym.tojson().encode("utf-8")).hexdigest()
        key = (digest,
               tuple(sorted((n, tuple(s)) for n, s in shapes.items())),
               kind, tuple(sorted(data_names)) if data_names else None,
               _mesh_material(mesh),
               tuple(sorted(batch_names)) if batch_names else None,
               data_axis if mesh is not None else None)
    except Exception:
        key = None
    if key is not None:
        with _LOCK:
            if key in _MEASURE_MEMO:
                return _MEASURE_MEMO[key]
    with _trace.span("lower", "compile"):
        lowered = _lower_proxy(sym, shapes, kind, data_names, mesh=mesh,
                               batch_names=batch_names, data_axis=data_axis)
    val = None
    if lowered is not None:
        with _trace.span("compile", "compile"):
            compiled = lowered.compile()
        val = _bytes_accessed(compiled)
    if key is not None:
        with _LOCK:
            if len(_MEASURE_MEMO) >= _MEASURE_MEMO_MAX:
                _MEASURE_MEMO.clear()
            _MEASURE_MEMO[key] = val
    return val


def _integer_feed_names(sym):
    """Variable names consumed as embedding ids (the ids input of
    ``Embedding``/``_contrib_SparseEmbedding``, looked through
    Reshape/Flatten/Cast chains). The bytes proxy synthesizes int32 for
    them: float ids would trace a cast-inserting program the real bind
    never runs, and ``jax.grad`` cannot differentiate wrt integer args,
    so the train proxy also excludes them from its argnums. Computed
    ids (a non-pass-through producer) resolve to no variable and keep
    the plain float32 synthesis."""
    _PASS_THROUGH = ("Reshape", "reshape", "Flatten", "flatten", "Cast",
                     "cast")
    names = set()
    for node in sym._topo_nodes():
        if node.op not in ("Embedding", "_contrib_SparseEmbedding") \
                or not node.inputs:
            continue
        p, _ = node.inputs[0]
        while p.op in _PASS_THROUGH and p.inputs:
            p = p.inputs[0][0]
        if p.op is None:
            names.add(p.name)
    return names


def _lower_proxy(sym, shapes, kind, data_names=None, mesh=None,
                 batch_names=None, data_axis="data"):
    """The program proxy of :func:`measure_symbol_bytes`, lowered; None
    where a shape is missing."""
    import numpy as np
    import jax
    from ...executor import build_graph_fns
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    if any(n not in shapes for n in arg_names + aux_names):
        return None
    int_names = _integer_feed_names(sym)

    def in_sharding(n):
        # batch-carrying feeds shard over the data axis (when the
        # bound batch divides it); weights/aux replicate — the DP
        # layout the fused step binds, so the measured program is
        # the per-device program the mesh actually runs
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P()
        if batch_names and n in batch_names:
            ndev = int(mesh.shape.get(data_axis, 1))
            shp = shapes[n]
            if ndev > 1 and shp and int(shp[0]) % ndev == 0:
                spec = P(data_axis)
        return NamedSharding(mesh, spec)

    def sds(n):
        dt = np.int32 if n in int_names else np.float32
        return jax.ShapeDtypeStruct(tuple(shapes[n]), dt)

    if kind == "infer" and data_names:
        from .hoist import hoist_plan, hoist_values
        keys, live = hoist_plan(sym, data_names)
        names = [n for n in arg_names + aux_names
                 if n in data_names or n in live]
        hstructs = jax.eval_shape(
            lambda m: hoist_values(sym, keys, m),
            {n: sds(n) for n in arg_names + aux_names
             if n not in data_names}) if keys else ()
        hoist_ids = [(id(n), i) for n, i in keys]

        def fn(vals, hvals, key):
            amap = dict(zip(names, vals))
            outs, _ = sym.eval_arrays_ex(
                amap, training=False, rng_key=key,
                preset=dict(zip(hoist_ids, hvals)))
            return tuple(outs)

        lowered = jax.jit(fn).lower(
            tuple(sds(n) for n in names), tuple(hstructs),
            jax.random.PRNGKey(0))
    else:
        arg_s = tuple(sds(n) for n in arg_names)
        aux_s = tuple(sds(n) for n in aux_names)
        fwd, fwd_loss, _ = build_graph_fns(sym)
        if kind == "train" and int_names:
            # differentiate wrt the float args only — integer id
            # feeds take no gradient and jax.grad rejects int dtypes
            fidx = [i for i, n in enumerate(arg_names)
                    if n not in int_names]

            def fn(arg_vals, aux_vals, key):
                def loss(fvals):
                    full = list(arg_vals)
                    for j, i in enumerate(fidx):
                        full[i] = fvals[j]
                    return fwd_loss(tuple(full), aux_vals, None, key)
                return jax.grad(loss, has_aux=True)(
                    tuple(arg_vals[i] for i in fidx))
        elif kind == "train":
            def fn(arg_vals, aux_vals, key):
                return jax.grad(fwd_loss, argnums=0, has_aux=True)(
                    arg_vals, aux_vals, None, key)
        else:
            def fn(arg_vals, aux_vals, key):
                return fwd(arg_vals, aux_vals, key, False)
        if mesh is not None:
            from ...ops import pallas_fused as _pf
            jitted = jax.jit(
                fn, in_shardings=(
                    tuple(in_sharding(n) for n in arg_names),
                    tuple(in_sharding(n) for n in aux_names),
                    None))
            with _pf.mesh_scope(mesh, data_axis):
                lowered = jitted.lower(arg_s, aux_s,
                                       jax.random.PRNGKey(0))
        else:
            lowered = jax.jit(fn).lower(arg_s, aux_s,
                                        jax.random.PRNGKey(0))
    return lowered


def _bytes_accessed(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost = dict(cost) if cost else {}
    by = float(cost.get("bytes accessed", 0.0) or 0.0)
    return by if by > 0 else None


# ---------------------------------------------------------------------------
# the manager
# ---------------------------------------------------------------------------
class PassManager:
    """An ordered pipeline of :class:`GraphPass` instances."""

    def __init__(self, passes: List[GraphPass]):
        self.passes = list(passes)

    def run(self, sym, shapes, *, tag, mode="train", mesh=None,
            compute_dtype=None, data_names=None, batch_names=None,
            data_axis="data") -> Tuple[Optional[object], dict]:
        """Run the pipeline over ``sym``. ``shapes`` maps every
        argument AND aux name to its bound shape (applicability checks
        and the bytes proxy both need concrete shapes). On mesh binds,
        ``batch_names`` (the data/label feeds) + ``data_axis`` tell the
        bytes proxy which inputs shard so the gate measures the
        per-device program. Returns ``(final_sym | None, report)`` —
        None means no pass survived and callers keep the original
        graph."""
        shapes = {n: tuple(s) for n, s in shapes.items()}
        ctx = PassContext(tag=tag, mode=mode, mesh=mesh,
                          compute_dtype=compute_dtype, shapes=shapes,
                          data_names=data_names, batch_names=batch_names,
                          data_axis=data_axis)
        gate = str(config.get("MXTPU_PASS_GATE_BYTES", "auto")
                   ).strip().lower()
        report = {"tag": tag, "mode": mode, "passes": [],
                  "baseline_bytes": None, "final_bytes": None,
                  "_seen": {"passes": False, "fusion": False}}
        cur = sym
        changed = False
        cur_bytes = None
        for p in self.passes:
            flag = p.resolve()
            entry = {"pass": p.name, "flag": flag, "status": "?",
                     "reason": None, "sites": [], "bailouts": [],
                     "bytes_before": None, "bytes_after": None,
                     "bytes_delta": None}
            report["passes"].append(entry)
            if not flag_active(flag):
                entry["status"] = "disabled"
                continue
            if mesh is not None and not p.mesh_safe:
                # per-pass reason (mesh_bind:<pass>) so a partially
                # supported pipeline is diagnosable from pass_report();
                # the aggregate counter stays for dashboards pinned to
                # the r12 name
                self._skip(entry, p, f"mesh_bind:{p.name}")
                _treg.counter("passes::skipped::mesh_bind").inc()
                continue
            if mode not in p.modes:
                # structural inapplicability (e.g. BN folding on a
                # training program) — reported, but not a "skip" in the
                # counted, something-was-missed sense
                entry["status"] = "inapplicable"
                entry["reason"] = f"mode:{mode}"
                continue
            ctx.symbol = cur     # graph-content prechecks see the
            reason = p.precheck(ctx)  # CURRENT (possibly rewritten) graph
            if reason:
                self._skip(entry, p, reason)
                continue
            # a pass that throws fails the bind: carrying on with the
            # unrewritten graph would report a program nobody asked for
            with _trace.span(f"apply:{p.name}", "pass"):
                new_sym, prep = p.apply(cur, shapes, ctx)
            entry["sites"] = list(prep.get("sites", ()))
            entry["bailouts"] = list(prep.get("bailouts", ()))
            if new_sym is None or not entry["sites"]:
                entry["status"] = "no_match"
                continue
            if (set(new_sym.list_arguments()) != set(cur.list_arguments())
                    or set(new_sym.list_auxiliary_states())
                    != set(cur.list_auxiliary_states())):
                # a pass may permute the variable order (executors feed
                # by the final graph's order) but never change the SET —
                # a dropped variable would silently unbind a parameter
                self._reject(entry, p,
                             "rewrite changed the argument/aux name set")
                continue
            measure = gate == "1" or (gate not in ("0", "false", "off")
                                      and flag == "auto")
            if measure:
                # the gate: the program before (once a pipeline) and
                # after this pass, each lowered and compiled for its
                # bytes
                with _trace.span(f"gate:{p.name}", "pass"):
                    if cur_bytes is None:
                        cur_bytes = measure_symbol_bytes(
                            cur, shapes, mode, data_names=ctx.data_names,
                            mesh=mesh, batch_names=ctx.batch_names,
                            data_axis=ctx.data_axis)
                        if report["baseline_bytes"] is None:
                            report["baseline_bytes"] = cur_bytes
                    new_bytes = measure_symbol_bytes(
                        new_sym, shapes, mode, data_names=ctx.data_names,
                        mesh=mesh, batch_names=ctx.batch_names,
                        data_axis=ctx.data_axis) \
                        if cur_bytes is not None else None
                if cur_bytes is None or new_bytes is None:
                    _treg.counter("passes::unmeasured").inc()
                else:
                    entry["bytes_before"] = cur_bytes
                    entry["bytes_after"] = new_bytes
                    entry["bytes_delta"] = new_bytes - cur_bytes
                    _treg.gauge(f"passes::{p.name}::bytes_delta").set(
                        new_bytes - cur_bytes)
                    if new_bytes >= cur_bytes:
                        self._reject(
                            entry, p,
                            f"bytes not strictly reduced "
                            f"({cur_bytes:.0f} -> {new_bytes:.0f})")
                        continue
                    cur_bytes = new_bytes
            entry["status"] = "applied"
            _treg.counter("passes::applied").inc()
            _treg.counter(f"passes::{p.name}::sites").inc(
                len(entry["sites"]))
            cur = new_sym
            changed = True
        report["final_bytes"] = cur_bytes
        # an all-disabled pipeline (the common CPU default) records
        # nothing — reports would otherwise drown in no-op entries from
        # every bind; any enabled pass (fired or not, skipped, or
        # rejected) makes the run reportable
        if any(e["status"] != "disabled" for e in report["passes"]):
            _record(report)
        return (cur if changed else None), report

    @staticmethod
    def _skip(entry, p, reason):
        entry["status"] = "skipped"
        entry["reason"] = reason
        _treg.counter("passes::skipped").inc()
        _treg.counter(f"passes::skipped::{reason}").inc()

    @staticmethod
    def _reject(entry, p, reason):
        entry["status"] = "rejected"
        entry["reason"] = reason
        _treg.counter("passes::rejected").inc()
        _treg.counter(f"passes::rejected::{p.name}").inc()


_default = [None]


def default_manager() -> PassManager:
    """The process-wide pipeline, in order: Pallas BN(+ReLU)→1×1-conv
    fusion (r6's pass, ported), residual-chain fusion (BN(+ReLU)→conv
    of any geometry onto the analytic-backward composite op),
    inference-time BN constant-folding, int8 weight PTQ (after bn_fold
    so it quantizes the FOLDED weights, before bf16_cast which bails on
    quantized sites), bf16 activation-traffic widening."""
    if _default[0] is None:
        from .pallas_fusion import PallasFusionPass
        from .residual_fusion import ResidualFusionPass
        from .bn_fold import BNFoldPass
        from .int8_ptq import Int8PTQPass
        from .bf16_cast import Bf16CastPass
        _default[0] = PassManager([PallasFusionPass(),
                                   ResidualFusionPass(),
                                   BNFoldPass(),
                                   Int8PTQPass(),
                                   Bf16CastPass()])
    return _default[0]


def apply_pipeline(sym, shapes, *, tag, mode="train", mesh=None,
                   compute_dtype=None, data_names=None, batch_names=None,
                   data_axis="data"):
    """Executor entry point: run the default pipeline (see
    :func:`default_manager`) over a bound symbol."""
    return default_manager().run(sym, shapes, tag=tag, mode=mode,
                                 mesh=mesh, compute_dtype=compute_dtype,
                                 data_names=data_names,
                                 batch_names=batch_names,
                                 data_axis=data_axis)


def pipeline_key_material(report) -> Optional[list]:
    """The pipeline's contribution to a compiled program's cache key:
    per-pass (name, resolved flag, status, rewritten-site count). Two
    builds that resolved the pipeline differently — a flag flipped, a
    pass fired on one and not the other, the gate rejected one — are
    different programs and must never share a cached executable."""
    if not report:
        return None
    return [(e["pass"], e["flag"], e.get("status"),
             len(e.get("sites") or ()))
            for e in report["passes"]]


def legacy_fusion_entry(report) -> Optional[dict]:
    """The pallas-fusion slice of a pipeline report, in the legacy
    ``maybe_fuse`` report shape ({tag, sites, bailouts}) the executors
    expose as ``_fusion_report`` / ``fusion_report`` attributes. None
    when the pass was disabled (the legacy 'pass did not run'
    signal)."""
    if not report:
        return None
    for e in report["passes"]:
        if e["pass"] != "pallas_fusion":
            continue
        if e["status"] == "disabled":
            return None
        out = {"tag": report["tag"], "sites": list(e["sites"]),
               "bailouts": list(e["bailouts"])}
        if e["status"] == "rejected":
            out["bailouts"] = out["bailouts"] + [{
                "conv": None, "bn": None,
                "reason": f"rewrite rejected: {e['reason']}"}]
            out["sites"] = []
        return out
    return None


# ---------------------------------------------------------------------------
# report surfaces
# ---------------------------------------------------------------------------
def _collect_passes(reset: bool = False) -> dict:
    """The ``passes`` telemetry collector: per-pass aggregates (sites,
    summed bytes delta), per-tag site counts (same stable tag keys as
    the legacy fusion report: ``executor``, ``executor_infer``,
    ``fused_step``, ``predictor``), counted skips with reasons, and the
    raw pipeline records."""
    with _LOCK:
        recs = [r for r in _RECORDS if not r["_seen"]["passes"]]
        if reset:
            for r in recs:
                r["_seen"]["passes"] = True
    by_pass: Dict[str, dict] = {}
    by_tag: Dict[str, int] = {}
    skipped: Dict[tuple, int] = {}
    n_applied = n_rejected = n_skipped = 0
    for r in recs:
        for e in r["passes"]:
            agg = by_pass.setdefault(e["pass"], {
                "applied": 0, "rejected": 0, "skipped": 0, "sites": 0,
                "bytes_delta": 0.0, "measured": 0})
            if e["status"] == "applied":
                n_applied += 1
                agg["applied"] += 1
                agg["sites"] += len(e["sites"])
                by_tag[r["tag"]] = by_tag.get(r["tag"], 0) + \
                    len(e["sites"])
                if e.get("bytes_delta") is not None:
                    agg["bytes_delta"] += e["bytes_delta"]
                    agg["measured"] += 1
            elif e["status"] == "rejected":
                n_rejected += 1
                agg["rejected"] += 1
            elif e["status"] == "skipped":
                n_skipped += 1
                agg["skipped"] += 1
                k = (e["pass"], r["tag"], e.get("reason"))
                skipped[k] = skipped.get(k, 0) + 1
    public = [{k: v for k, v in r.items() if k != "_seen"}
              for r in recs]
    return {
        "num_applied": n_applied,
        "num_rejected": n_rejected,
        "num_skipped": n_skipped,
        "by_pass": by_pass,
        "by_tag": by_tag,
        "skipped": [{"pass": p, "tag": t, "reason": why, "count": c}
                    for (p, t, why), c in sorted(skipped.items(),
                                                 key=lambda kv: kv[0])],
        "pipelines": public,
    }


pass_report = _treg.collector_view("passes", _collect_passes)


def collect_fusion(reset: bool = False) -> dict:
    """The legacy ``fusion_report()`` payload, built from the SAME
    store as :func:`pass_report` (satellite of round 12: the fusion
    report is a compatible filtered view — same ``by_tag`` keys, same
    per-rewrite {tag, sites, bailouts} entries)."""
    with _LOCK:
        recs = [r for r in _RECORDS if not r["_seen"]["fusion"]]
        if reset:
            for r in recs:
                r["_seen"]["fusion"] = True
    rewrites = []
    for r in recs:
        for e in r["passes"]:
            if e["pass"] != "pallas_fusion" or e["status"] == "disabled":
                continue
            rewrites.append({"tag": r["tag"], "sites": list(e["sites"]),
                             "bailouts": list(e["bailouts"])})
    by_tag: Dict[str, int] = {}
    for r in rewrites:
        by_tag[r["tag"]] = by_tag.get(r["tag"], 0) + len(r["sites"])
    return {
        "num_rewritten_sites": sum(len(r["sites"]) for r in rewrites),
        "num_bailouts": sum(len(r["bailouts"]) for r in rewrites),
        "by_tag": by_tag,
        "rewrites": rewrites,
    }
