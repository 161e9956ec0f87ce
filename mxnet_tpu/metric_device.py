"""In-step (non-blocking) metric accumulation for the fused Module path.

The reference's training loop calls ``update_metric`` every batch
(reference: python/mxnet/module/base_module.py:376, module.py:736); its
metrics pull predictions to host numpy immediately. Under the fused XLA
step that host pull is a synchronization point: it collapses the
donation-chained async dispatch and costs a device round trip per batch;
a separate device kernel per batch would add a dispatch of its own.

So the metric counters are computed INSIDE the fused step program itself:
``Module.update_metric`` attaches pure counter rules to the
FusedSymbolStep (one retrace), each step advances one device scalar per
metric as part of the single XLA program, and the host only syncs when
the metric is actually read — ``EvalMetric.get()`` — i.e. at the
Speedometer interval and the epoch log line. Instance counts are derived
from the step count (batch shapes are static), so a reset at any point
realigns exactly.

Every supported rule reproduces the corresponding ``metric.py`` update
semantics (which mirror reference metric.py); anything unsupported —
custom metrics, exotic shapes — falls back to the synchronous numpy path
transparently.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import metric as metric_mod

__all__ = ["inline_update", "flush", "discard", "flush_and_detach"]


def _jx(v):
    data = getattr(v, "_data", v)
    return data if isinstance(data, jax.Array) else jnp.asarray(data)


class _DevRef:
    """A leaf metric's view of its in-step counter slot.

    Holds only a weakref to the FusedSymbolStep: a metric object that
    outlives its Module must not pin the step's device buffers. Tracks
    ``seen_t`` to enforce the per-call contract — in-step counters
    advance on EVERY step, so a caller that skips update_metric for some
    batches (gap) invalidates the window; the window is discarded and
    the metric drops to the synchronous path (reference per-call
    semantics preserved; fit() calls every batch and never gaps)."""

    __slots__ = ("fused_wr", "idx", "inst_per_step", "t0", "last_val",
                 "last_t", "seen_t", "shape_sig", "detach_epoch")

    def __init__(self, fused, idx, inst_per_step, shape_sig):
        import weakref
        self.fused_wr = weakref.ref(fused)
        self.idx = idx
        self.inst_per_step = inst_per_step
        self.shape_sig = shape_sig
        self.detach_epoch = fused._metric_detach_epoch
        # counters start accumulating from the NEXT step
        self.t0 = fused.num_update
        self.last_val = 0.0
        self.last_t = fused.num_update
        self.seen_t = fused.num_update

    @property
    def fused(self):
        return self.fused_wr()

    def valid(self, fused):
        f = self.fused
        return (f is not None and f is fused and
                self.detach_epoch == fused._metric_detach_epoch)

    def flush(self, metric):
        """Fold the increment since the last read into the metric
        (one sync on the step chain)."""
        f = self.fused
        if f is None or not self.valid(f) or f._metric_state is None \
                or self.idx >= len(f._metric_state):
            return
        cur_t = f.num_update
        if cur_t == self.last_t:
            return
        # the one place fit()'s loop waits for the device: a wait phase
        # of the step it is read in (telemetry/timeline.py)
        from .telemetry import timeline as _tlmod
        with _tlmod.phase("device_read"):
            val = np.asarray(f._metric_state[self.idx])
        cur = int(val) if val.dtype.kind in "iu" else float(val)
        metric.sum_metric += cur - self.last_val
        metric.num_inst += (cur_t - self.last_t) * self.inst_per_step
        self.last_val = cur
        self.last_t = cur_t

    def discard(self):
        """Zero the device counter and realign (metric.reset())."""
        f = self.fused
        if f is None:
            return
        if self.valid(f):
            f.reset_metric_state(self.idx)
        self.last_val = 0.0
        self.last_t = self.t0 = self.seen_t = f.num_update


def flush_and_detach(fused):
    """Executor reshape: fold every live metric's counters (their
    per-step instance counts were exact for the steps run so far), then
    drop the in-step rules so re-attachment rebuilds with new shapes.
    Called by Module.forward BEFORE the first differently-shaped step."""
    for m in fused.live_metrics():
        ref = getattr(m, "_dev_acc", None)
        if ref is not None and ref.valid(fused):
            ref.flush(m)
        m._dev_acc = None
    fused.detach_metrics()


def flush(metric):
    ref = getattr(metric, "_dev_acc", None)
    if ref is not None:
        ref.flush(metric)


def discard(metric):
    ref = getattr(metric, "_dev_acc", None)
    if ref is not None:
        ref.discard()


# -- rule builders ------------------------------------------------------------
# each: build(metric, labels, preds) with jnp shape templates ->
#   (init_scalar, fn(state, label_vals, pred_vals) -> state, inst_per_step)
# or None when the metric/shapes aren't supported. label_vals/pred_vals are
# the in-step value lists selected exactly like EvalMetric.update_dict.

def _pairs_ok(labels, preds):
    return len(labels) == len(preds) and labels


def _b_accuracy(metric, labels, preds):
    if not _pairs_ok(labels, preds):
        return None
    axis = metric.axis
    plan = []
    inst = 0
    for lv, pv in zip(labels, preds):
        need_argmax = pv.ndim > lv.ndim or (pv.ndim == lv.ndim and
                                            pv.shape != lv.shape)
        n = int(np.prod(lv.shape)) if lv.ndim else 1
        pexp = int(np.prod(pv.shape[:axis] + pv.shape[axis + 1:])) \
            if need_argmax else int(np.prod(pv.shape))
        if n != pexp:
            return None
        plan.append(need_argmax)
        inst += n

    def fn(state, label_vals, pred_vals):
        for need_argmax, lab, prd in zip(plan, label_vals, pred_vals):
            p = jnp.argmax(prd, axis=axis) if need_argmax else prd
            state = state + jnp.sum(
                p.astype(jnp.int32).ravel() ==
                lab.astype(jnp.int32).ravel()).astype(jnp.int32)
        return state

    return jnp.zeros((), jnp.int32), fn, inst


def _b_top_k(metric, labels, preds):
    if not _pairs_ok(labels, preds):
        return None
    k = metric.top_k
    inst = 0
    for lv, pv in zip(labels, preds):
        if pv.ndim != 2 or lv.ndim != 1 or pv.shape[0] != lv.shape[0]:
            return None
        inst += int(lv.shape[0])

    def fn(state, label_vals, pred_vals):
        for lab, prd in zip(label_vals, pred_vals):
            kk = min(k, prd.shape[1])
            _, idx = jax.lax.top_k(prd.astype(jnp.float32), kk)
            hit = jnp.any(idx == lab.astype(jnp.int32)[:, None], axis=1)
            state = state + jnp.sum(hit).astype(jnp.int32)
        return state

    return jnp.zeros((), jnp.int32), fn, inst


def _b_cross_entropy(metric, labels, preds):
    if not _pairs_ok(labels, preds):
        return None
    eps = metric.eps
    inst = 0
    for lv, pv in zip(labels, preds):
        if pv.ndim != 2 or int(np.prod(lv.shape)) != pv.shape[0]:
            return None
        inst += int(pv.shape[0])

    def fn(state, label_vals, pred_vals):
        for lab, prd in zip(label_vals, pred_vals):
            li = lab.ravel().astype(jnp.int32)
            prob = jnp.take_along_axis(
                prd.astype(jnp.float32), li[:, None], axis=1)[:, 0]
            state = state + jnp.sum(-jnp.log(prob + eps))
        return state

    return jnp.zeros((), jnp.float32), fn, inst


def _b_elementwise_err(kind):
    def build(metric, labels, preds):
        if not _pairs_ok(labels, preds):
            return None
        shapes = []
        for lv, pv in zip(labels, preds):
            ls = lv.shape if lv.ndim > 1 else (
                (lv.shape[0], 1) if lv.ndim else (1, 1))
            ps = pv.shape if pv.ndim > 1 else (
                (pv.shape[0], 1) if pv.ndim else (1, 1))
            if ls != ps:
                return None
            shapes.append(ls)

        def fn(state, label_vals, pred_vals):
            for ls, lab, prd in zip(shapes, label_vals, pred_vals):
                d = lab.astype(jnp.float32).reshape(ls) - \
                    prd.astype(jnp.float32).reshape(ls)
                if kind == "mae":
                    e = jnp.mean(jnp.abs(d))
                elif kind == "mse":
                    e = jnp.mean(jnp.square(d))
                else:  # rmse
                    e = jnp.sqrt(jnp.mean(jnp.square(d)))
                state = state + e
            return state

        return jnp.zeros((), jnp.float32), fn, len(shapes)
    return build


def _b_loss(metric, labels, preds):
    inst = sum(int(np.prod(pv.shape)) if pv.ndim else 1 for pv in preds)

    def fn(state, label_vals, pred_vals):
        for prd in pred_vals:
            state = state + jnp.sum(prd.astype(jnp.float32))
        return state

    return jnp.zeros((), jnp.float32), fn, inst


_RULES = {
    metric_mod.Accuracy: _b_accuracy,
    metric_mod.TopKAccuracy: _b_top_k,
    metric_mod.CrossEntropy: _b_cross_entropy,
    metric_mod.NegativeLogLikelihood: _b_cross_entropy,
    metric_mod.MAE: _b_elementwise_err("mae"),
    metric_mod.MSE: _b_elementwise_err("mse"),
    metric_mod.RMSE: _b_elementwise_err("rmse"),
    metric_mod.Loss: _b_loss,
}


def _walk(metric, label_dict, pred_dict, out):
    """Collect (leaf, label_dict, pred_dict) with composite filters
    applied exactly like CompositeEvalMetric.update_dict; None =
    unsupported leaf somewhere."""
    if type(metric) is metric_mod.CompositeEvalMetric:
        labels, preds = label_dict, pred_dict
        if metric.label_names is not None:
            labels = {k: v for k, v in labels.items()
                      if k in metric.label_names}
        if metric.output_names is not None:
            preds = {k: v for k, v in preds.items()
                     if k in metric.output_names}
        for m in metric.metrics:
            if _walk(m, labels, preds, out) is None:
                return None
        return out
    if type(metric) not in _RULES:
        return None
    out.append((metric, label_dict, pred_dict))
    return out


def _select(d, override):
    keys = override if override is not None else list(d)
    try:
        return [d[n] for n in keys], keys
    except KeyError:
        return None, None


def inline_update(fused, metric, label_dict, pred_dict) -> bool:
    """Route update_metric through in-step counters. Returns False when
    the metric isn't supported (caller uses the sync path). The batch
    whose step ALREADY ran when the rules get attached is counted
    synchronously once; all later steps count on device. A shape change
    (bucketing-style reshape) flushes and re-attaches with new
    templates; multiple metric objects append independent counters."""
    leaves = _walk(metric, label_dict, pred_dict, [])
    if leaves is None:
        return False
    # resolve every leaf's value lists + shape signature first
    plans = []
    for m, ld, pd in leaves:
        pvals, pnames = _select(pd, m.output_names)
        lvals, lnames = _select(ld, m.label_names)
        if pvals is None or lvals is None:
            return False
        lt = [jax.ShapeDtypeStruct(_jx(v).shape, _jx(v).dtype)
              for v in lvals]
        pt = [jax.ShapeDtypeStruct(_jx(v).shape, _jx(v).dtype)
              for v in pvals]
        shape_sig = (tuple(t.shape for t in lt),
                     tuple(t.shape for t in pt))
        plans.append((m, lnames, pnames, lt, pt, shape_sig))
    refs = [getattr(m, "_dev_acc", None)
            for m, _ln, _pn, _lt, _pt, _ss in plans]
    if all(r is not None and r.valid(fused) and
           r.shape_sig == p[5] for r, p in zip(refs, plans)):
        # counters advance inside the step — but only contiguous
        # per-step calls keep the window attributable.
        if all(fused.num_update == r.seen_t + 1 for r in refs):
            for r in refs:
                r.seen_t = fused.num_update
            return True
        # mixed per-call states: settle EACH leaf under its own
        # contract (a composite can mix them when one leaf was also
        # updated standalone this batch) — a blanket discard here
        # silently dropped contiguous siblings' submitted batches.
        for r, (m, ld, pd) in zip(refs, leaves):
            if fused.num_update == r.seen_t + 1:
                # contiguous first call for this batch: the in-step
                # counter holds it — stay attached
                r.seen_t = fused.num_update
            elif fused.num_update == r.seen_t:
                # double call for the SAME batch — no gap: fold the
                # window (discarding silently lost it), release the
                # slot, and count this batch a second time — the
                # reference's per-call double-count semantics
                r.flush(m)
                fused.release_metric_slot(r.idx)
                m._dev_acc = None
                m.update_dict(ld, pd)
            else:
                # true gap: the counter holds steps whose batches were
                # never submitted via update_metric — the window is not
                # attributable, so it is dropped (lossy by design) and
                # only the current batch counts, synchronously
                r.discard()
                fused.release_metric_slot(r.idx)
                m._dev_acc = None
                m.update_dict(ld, pd)
        return True
    if any(r is not None and r.valid(fused) and r.shape_sig != p[5]
           for r, p in zip(refs, plans)):
        # batch shapes changed since attach: fold what's counted (exact
        # for the steps run so far), drop the rules, re-attach below
        # with the new shape templates
        flush_and_detach(fused)
    # a partially-attached plan (e.g. a leaf later joins a composite):
    # settle the still-valid refs' windows before they're re-slotted,
    # under the SAME per-call contract as the all-valid branch above —
    # a contiguous window folds (and, being counted in-step, covers
    # this batch, so its leaf must skip the final sync update that
    # previously double-counted every partial re-attach); a double
    # call folds but still earns the second sync count; a true gap is
    # unattributable and is discarded.
    covered = set()
    for r, p in zip(refs, plans):
        if r is not None and r.valid(fused):
            if fused.num_update == r.seen_t + 1:
                # contiguous first call for this batch: the in-step
                # counter already holds it
                r.flush(p[0])
                covered.add(id(p[0]))
            elif fused.num_update == r.seen_t:
                # double call, no gap: fold, then the sync pass below
                # counts this batch a second time (per-call semantics)
                r.flush(p[0])
            else:
                r.discard()
            p[0]._dev_acc = None
    # build EVERY rule first (a late shape failure must not leave a
    # partially-attached plan — sync + in-step would double count),
    # then claim slots (reuse or append)
    built_rules = []
    for m, lnames, pnames, lt, pt, shape_sig in plans:
        built = _RULES[type(m)](m, lt, pt)
        if built is None:
            return False
        init, fn, inst = built
        sig = (type(m).__name__, tuple(lnames), tuple(pnames), shape_sig,
               getattr(m, "axis", None), getattr(m, "top_k", None),
               getattr(m, "eps", None))
        built_rules.append((m, sig, init, lnames, pnames, fn, inst,
                            shape_sig))
    for m, sig, init, lnames, pnames, fn, inst, shape_sig in built_rules:
        idx = fused.attach_metric(m, sig, init, lnames, pnames, fn)
        m._dev_acc = _DevRef(fused, idx, inst, shape_sig)
    # the already-run step for THIS batch isn't in the freshly-attached
    # counters — count it synchronously PER LEAF, skipping leaves whose
    # just-flushed window already covered it
    for (m, ld, pd), _plan in zip(leaves, plans):
        if id(m) not in covered:
            m.update_dict(ld, pd)
    return True
