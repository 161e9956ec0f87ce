"""Contrib operators: CTC loss, the SSD MultiBox family, box_nms, FFT,
Correlation, Crop, RPN Proposal/MultiProposal, count_sketch,
DeformableConvolution, and the PSROI pooling family.

Reference analogs: src/operator/contrib/{ctc_loss, multibox_prior,
multibox_target, multibox_detection, bounding_box, fft, ifft, proposal,
multi_proposal, count_sketch, deformable_convolution,
psroi_pooling, deformable_psroi_pooling}.cc and src/operator/
{correlation, crop}.cc. All are re-derived as vectorized jax/lax code
(fixed shapes, scan/while-free where possible) so XLA can fuse and tile
them for TPU; none of the reference's kernel code is used.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register_op

_NEG = -1e30  # large-negative stand-in for -inf: keeps grads finite


# ---------------------------------------------------------------------------
# CTC loss (reference: src/operator/contrib/ctc_loss.cc — warp-ctc kernels;
# here: log-space alpha recursion under lax.scan, grads via autodiff)
# ---------------------------------------------------------------------------
def _ctc_one(logp, label, t_len, l_len, blank):
    """Negative log likelihood for one sequence.

    logp: (T, C) log-probabilities. label: (L,) int32 token ids.
    t_len/l_len: actual lengths. blank: blank id.
    """
    T, C = logp.shape
    L = label.shape[0]
    S = 2 * L + 1
    # extended label sequence: blank, l1, blank, l2, ..., blank
    z = jnp.full((S,), blank, jnp.int32).at[1::2].set(label.astype(jnp.int32))
    pos = jnp.arange(S)
    valid = pos < 2 * l_len + 1
    # skip-transition allowed when z[s] != blank and z[s] != z[s-2]
    can_skip = jnp.concatenate(
        [jnp.zeros((2,), bool), (z[2:] != blank) & (z[2:] != z[:-2])])

    alpha0 = jnp.full((S,), _NEG).at[0].set(logp[0, z[0]])
    alpha0 = jnp.where((pos == 1) & (l_len > 0),
                       logp[0, z[jnp.minimum(1, S - 1)]], alpha0)
    alpha0 = jnp.where(valid, alpha0, _NEG)

    def step(alpha, tlp):
        t, lp = tlp
        a1 = alpha
        a2 = jnp.concatenate([jnp.full((1,), _NEG), alpha[:-1]])
        a3 = jnp.concatenate([jnp.full((2,), _NEG), alpha[:-2]])
        a3 = jnp.where(can_skip, a3, _NEG)
        m = jnp.maximum(jnp.maximum(a1, a2), a3)
        tot = m + jnp.log(jnp.exp(a1 - m) + jnp.exp(a2 - m)
                          + jnp.exp(a3 - m))
        new = jnp.where(valid, tot + lp[z], _NEG)
        # frozen once t >= t_len so the final alpha is the one at t_len-1
        new = jnp.where(t < t_len, new, alpha)
        return new, None

    ts = jnp.arange(1, T)
    alpha, _ = jax.lax.scan(step, alpha0, (ts, logp[1:]))
    s_last = 2 * l_len  # index of final blank
    a_end = alpha[jnp.minimum(s_last, S - 1)]
    a_pre = jnp.where(l_len > 0,
                      alpha[jnp.maximum(jnp.minimum(s_last - 1, S - 1), 0)],
                      _NEG)
    m = jnp.maximum(a_end, a_pre)
    ll = m + jnp.log(jnp.exp(a_end - m) + jnp.exp(a_pre - m))
    return -ll


@register_op("CTCLoss", aliases=["ctc_loss", "_contrib_CTCLoss",
                                 "_contrib_ctc_loss"])
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first", **kw):
    """CTC negative log likelihood per sample.

    data: (T, N, C) unnormalized activations (softmax applied internally,
    matching the reference op). label: (N, L) padded token ids. Returns (N,)
    losses. blank is class 0 ('first', padding value 0) or C-1 ('last',
    padding value -1).
    """
    T, N, C = data.shape
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    blank = 0 if blank_label == "first" else C - 1
    label = label.astype(jnp.int32)
    pad_val = 0 if blank_label == "first" else -1
    if use_data_lengths and data_lengths is not None:
        t_lens = data_lengths.astype(jnp.int32)
    else:
        t_lens = jnp.full((N,), T, jnp.int32)
    if use_label_lengths and label_lengths is not None:
        l_lens = label_lengths.astype(jnp.int32)
    else:
        l_lens = (label != pad_val).sum(axis=1).astype(jnp.int32)
    logp_n = jnp.transpose(logp, (1, 0, 2))  # (N, T, C)
    return jax.vmap(_ctc_one, in_axes=(0, 0, 0, 0, None))(
        logp_n, label, t_lens, l_lens, blank)


# ---------------------------------------------------------------------------
# SSD MultiBox family + box_nms
# (reference: src/operator/contrib/multibox_prior.cc, multibox_target.cc,
# multibox_detection.cc, bounding_box.cc. Re-derived as fixed-shape
# vectorized lax: the reference's sequential CPU loops become masked argmax
# scans / pairwise-IoU matrices that XLA can fuse; no dynamic shapes.)
# ---------------------------------------------------------------------------
def _tuplef(v, default):
    """Attr coercion: tuples arrive as python sequences or MXNet-style
    '(a,b)' strings (symbol JSON)."""
    if v is None:
        return tuple(default)
    if isinstance(v, str):
        v = v.strip("()[] ")
        return tuple(float(x) for x in v.split(",") if x.strip())
    if isinstance(v, (int, float)):
        return (float(v),)
    return tuple(float(x) for x in v)


def _box_iou(a, b):
    """Pairwise IoU of corner-format boxes: (A,4) x (B,4) -> (A,B)
    (reference: CalculateOverlap, multibox_target.cc)."""
    tl = jnp.maximum(a[:, None, :2], b[None, :, :2])
    br = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum(a[:, 2] - a[:, 0], 0) * jnp.maximum(a[:, 3] - a[:, 1], 0)
    area_b = jnp.maximum(b[:, 2] - b[:, 0], 0) * jnp.maximum(b[:, 3] - b[:, 1], 0)
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


@register_op("MultiBoxPrior", aliases=["_contrib_MultiBoxPrior"], no_grad=True)
def multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                   steps=(-1.0, -1.0), offsets=(0.5, 0.5), **kw):
    """Generate SSD anchor boxes from a feature map.

    data: (N, C, H, W); output (1, H*W*K, 4) corner boxes, K = num_sizes - 1
    + num_ratios, ordered [all sizes at ratio 1, then ratios[1:] at sizes[0]]
    per location (reference: multibox_prior.cc:40-72 MultiBoxPriorForward).
    """
    sizes = _tuplef(sizes, (1.0,))
    ratios = _tuplef(ratios, (1.0,))
    steps = _tuplef(steps, (-1.0, -1.0))
    offsets = _tuplef(offsets, (0.5, 0.5))
    H, W = int(data.shape[2]), int(data.shape[3])
    step_y = steps[0] if steps[0] > 0 else 1.0 / H
    step_x = steps[1] if steps[1] > 0 else 1.0 / W
    cy = (jnp.arange(H, dtype=jnp.float32) + offsets[0]) * step_y
    cx = (jnp.arange(W, dtype=jnp.float32) + offsets[1]) * step_x
    # half-widths/heights per anchor kind; w carries the H/W aspect
    # correction the reference applies (multibox_prior.cc:50,62)
    ws = [s * H / W / 2 for s in sizes] + \
         [sizes[0] * H / W * (r ** 0.5) / 2 for r in ratios[1:]]
    hs = [s / 2 for s in sizes] + \
         [sizes[0] / (r ** 0.5) / 2 for r in ratios[1:]]
    w = jnp.asarray(ws, jnp.float32)
    h = jnp.asarray(hs, jnp.float32)
    cxg = jnp.broadcast_to(cx[None, :, None], (H, W, w.shape[0]))
    cyg = jnp.broadcast_to(cy[:, None, None], (H, W, w.shape[0]))
    boxes = jnp.stack([cxg - w, cyg - h, cxg + w, cyg + h], axis=-1)
    boxes = boxes.reshape(1, H * W * w.shape[0], 4)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    return boxes.astype(data.dtype)


def _encode_loc(anchors, gt):
    """Box regression targets (reference: AssignLocTargets,
    multibox_target.cc:32-55). Variances divided out by the caller."""
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    ax = (anchors[:, 0] + anchors[:, 2]) * 0.5
    ay = (anchors[:, 1] + anchors[:, 3]) * 0.5
    gw = gt[:, 2] - gt[:, 0]
    gh = gt[:, 3] - gt[:, 1]
    gx = (gt[:, 0] + gt[:, 2]) * 0.5
    gy = (gt[:, 1] + gt[:, 3]) * 0.5
    eps = 1e-12
    return jnp.stack([
        (gx - ax) / jnp.maximum(aw, eps),
        (gy - ay) / jnp.maximum(ah, eps),
        jnp.log(jnp.maximum(gw, eps) / jnp.maximum(aw, eps)),
        jnp.log(jnp.maximum(gh, eps) / jnp.maximum(ah, eps)),
    ], axis=1)


def _multibox_target_one(anchors, label, cls_pred, overlap_threshold,
                         ignore_label, negative_mining_ratio,
                         negative_mining_thresh, minimum_negative_samples,
                         variances):
    """Single-sample anchor matching (reference: MultiBoxTargetForward,
    multibox_target.cc:72-277). The sequential greedy bipartite match is a
    fixed-length lax.scan (one round per ground-truth slot)."""
    A = anchors.shape[0]
    L = label.shape[0]
    valid = label[:, 0] > -0.5
    iou = _box_iou(anchors, label[:, 1:5])
    iou = jnp.where(valid[None, :], iou, -1.0)

    # stage 1: greedy global bipartite matching, at most L rounds
    def bipartite_round(state, _):
        a_used, g_used, m_gt, m_iou = state
        masked = jnp.where(a_used[:, None] | g_used[None, :], -1.0, iou)
        flat = jnp.argmax(masked)
        ai, gi = flat // L, flat % L
        ok = masked[ai, gi] > 1e-6
        a_used = a_used.at[ai].set(a_used[ai] | ok)
        g_used = g_used.at[gi].set(g_used[gi] | ok)
        m_gt = m_gt.at[ai].set(jnp.where(ok, gi.astype(jnp.int32), m_gt[ai]))
        m_iou = m_iou.at[ai].set(jnp.where(ok, masked[ai, gi], m_iou[ai]))
        return (a_used, g_used, m_gt, m_iou), None

    init = (jnp.zeros(A, bool), jnp.zeros(L, bool),
            jnp.full(A, -1, jnp.int32), jnp.full(A, -1.0))
    (matched, _, match_gt, match_iou), _ = jax.lax.scan(
        bipartite_round, init, None, length=L)

    # stage 2: per-anchor threshold matching for still-unmatched anchors
    best_gt = jnp.argmax(iou, axis=1).astype(jnp.int32)
    best_iou = jnp.max(iou, axis=1)
    match_gt = jnp.where(matched, match_gt, best_gt)
    match_iou = jnp.where(matched, match_iou, best_iou)
    thr_pos = (~matched) & (best_iou > overlap_threshold) \
        if overlap_threshold > 0 else jnp.zeros(A, bool)
    positive = matched | thr_pos
    num_pos = positive.sum()

    # negatives: hard-negative mining by background prob, or everything
    if negative_mining_ratio > 0:
        prob = jax.nn.softmax(cls_pred, axis=0)[0]  # background prob (A,)
        cand = (~positive) & (match_iou < negative_mining_thresh)
        num_neg = jnp.minimum(
            jnp.maximum((num_pos * negative_mining_ratio).astype(jnp.int32),
                        int(minimum_negative_samples)),
            A - num_pos)
        score = jnp.where(cand, -prob, -jnp.inf)  # hardest = lowest bg prob
        rank = jnp.argsort(jnp.argsort(-score))
        negative = cand & (rank < num_neg)
    else:
        negative = ~positive

    cls_of_gt = label[jnp.clip(match_gt, 0, L - 1), 0]
    cls_target = jnp.where(positive, cls_of_gt + 1.0,
                           jnp.where(negative, 0.0, float(ignore_label)))
    gt_boxes = label[jnp.clip(match_gt, 0, L - 1), 1:5]
    enc = _encode_loc(anchors, gt_boxes) / jnp.asarray(variances)
    loc_target = jnp.where(positive[:, None], enc, 0.0).reshape(A * 4)
    loc_mask = jnp.where(positive[:, None],
                         jnp.ones((A, 4)), 0.0).reshape(A * 4)
    return loc_target, loc_mask, cls_target


@register_op("MultiBoxTarget", aliases=["_contrib_MultiBoxTarget"],
             no_grad=True, num_outputs=3)
def multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    negative_mining_thresh=0.5, minimum_negative_samples=0,
                    variances=(0.1, 0.1, 0.2, 0.2), **kw):
    """Compute SSD training targets.

    anchor: (1, A, 4); label: (B, L, 5+) rows [cls, xmin, ymin, xmax, ymax],
    -1-padded; cls_pred: (B, C, A). Returns (loc_target (B, A*4),
    loc_mask (B, A*4), cls_target (B, A))
    (reference: multibox_target.cc, multibox_target-inl.h:60-81).
    """
    variances = _tuplef(variances, (0.1, 0.1, 0.2, 0.2))
    anchors = anchor.reshape(-1, 4)
    # no_grad ops bypass the registry's per-(op,attrs) jit cache, so cache
    # the jitted batch fn per attr-tuple here (re-tracing the bipartite scan
    # per call would dominate the step)
    fn = _mbt_jit(float(overlap_threshold), float(ignore_label),
                  float(negative_mining_ratio), float(negative_mining_thresh),
                  int(minimum_negative_samples), variances)
    loc_t, loc_m, cls_t = fn(anchors, label, cls_pred)
    return loc_t, loc_m, cls_t


@functools.lru_cache(maxsize=None)
def _mbt_jit(ot, il, nmr, nmt, mns, variances):
    def batch(anchors, label, cls_pred):
        one = lambda lb, cp: _multibox_target_one(
            anchors, lb, cp, ot, il, nmr, nmt, mns, variances)
        return jax.vmap(one)(label, cls_pred)
    return jax.jit(batch)


def _decode_boxes(anchors, loc_pred, variances, clip):
    """Decode regression output to corner boxes (reference:
    TransformLocations, multibox_detection.cc:46-71)."""
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    ax = (anchors[:, 0] + anchors[:, 2]) * 0.5
    ay = (anchors[:, 1] + anchors[:, 3]) * 0.5
    p = loc_pred.reshape(-1, 4)
    ox = p[:, 0] * variances[0] * aw + ax
    oy = p[:, 1] * variances[1] * ah + ay
    ow = jnp.exp(p[:, 2] * variances[2]) * aw * 0.5
    oh = jnp.exp(p[:, 3] * variances[3]) * ah * 0.5
    boxes = jnp.stack([ox - ow, oy - oh, ox + ow, oy + oh], axis=1)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    return boxes


def _greedy_nms_keep(boxes, ids, valid, nms_threshold, force_suppress):
    """Greedy NMS over score-sorted boxes: returns keep mask.

    The reference's O(N^2) sequential suppression (multibox_detection.cc:
    152-167) as a fori_loop over a precomputed pairwise IoU matrix."""
    N = boxes.shape[0]
    iou = _box_iou(boxes, boxes)
    same = jnp.ones((N, N), bool) if force_suppress \
        else ids[:, None] == ids[None, :]
    later = jnp.arange(N)[None, :] > jnp.arange(N)[:, None]
    sup_mat = (iou >= nms_threshold) & same & later

    def body(i, keep):
        return keep & ~(keep[i] & sup_mat[i])

    return jax.lax.fori_loop(0, N, body, valid)


def _multibox_detection_one(cls_prob, loc_pred, anchors, threshold, clip,
                            variances, nms_threshold, force_suppress,
                            nms_topk):
    A = cls_prob.shape[1]
    fg = cls_prob[1:, :]                       # drop background row
    cid = jnp.argmax(fg, axis=0).astype(jnp.float32)   # 0-based class id
    score = jnp.max(fg, axis=0)
    valid = score >= threshold
    boxes = _decode_boxes(anchors, loc_pred, variances, clip)
    order = jnp.argsort(-jnp.where(valid, score, -jnp.inf))
    cid, score, boxes, valid = cid[order], score[order], boxes[order], valid[order]
    if nms_topk > 0:
        valid = valid & (jnp.arange(A) < nms_topk)
    if 0 < nms_threshold <= 1:
        keep = _greedy_nms_keep(boxes, cid, valid, nms_threshold,
                                force_suppress)
    else:
        keep = valid
    row = jnp.concatenate([cid[:, None], score[:, None], boxes], axis=1)
    return jnp.where(keep[:, None], row, -1.0)


@register_op("MultiBoxDetection", aliases=["_contrib_MultiBoxDetection"],
             no_grad=True)
def multibox_detection(cls_prob, loc_pred, anchor, clip=True, threshold=0.01,
                       background_id=0, nms_threshold=0.5,
                       force_suppress=False, variances=(0.1, 0.1, 0.2, 0.2),
                       nms_topk=-1, **kw):
    """Decode predictions into detections with per-class NMS.

    cls_prob: (B, C, A) softmax class probabilities (class 0 = background);
    loc_pred: (B, A*4); anchor: (1, A, 4). Output (B, A, 6) rows
    [class_id, score, xmin, ymin, xmax, ymax], suppressed/invalid rows -1
    (reference: multibox_detection.cc:83-169, -inl.h:48-73).
    """
    variances = _tuplef(variances, (0.1, 0.1, 0.2, 0.2))
    if int(background_id) != 0:
        # the reference kernel also assumes class 0 is background (its
        # scan starts at j=1, multibox_detection.cc:108) — reject rather
        # than silently return wrong detections
        raise NotImplementedError("MultiBoxDetection: background_id must "
                                  "be 0 (class 0 is background)")
    anchors = anchor.reshape(-1, 4)
    fn = _mbd_jit(float(threshold), bool(clip), variances,
                  float(nms_threshold), bool(force_suppress), int(nms_topk))
    return fn(cls_prob, loc_pred, anchors)


@functools.lru_cache(maxsize=None)
def _mbd_jit(threshold, clip, variances, nms_threshold, force_suppress,
             nms_topk):
    def batch(cls_prob, loc_pred, anchors):
        one = lambda cp, lp: _multibox_detection_one(
            cp, lp, anchors, threshold, clip, variances, nms_threshold,
            force_suppress, nms_topk)
        return jax.vmap(one)(cls_prob, loc_pred)
    return jax.jit(batch)


@register_op("box_nms", aliases=["_contrib_box_nms", "box_non_maximum_suppression",
                                 "_contrib_box_non_maximum_suppression"],
             no_grad=True)
def box_nms(data, overlap_thresh=0.5, topk=-1, coord_start=2, score_index=1,
            id_index=-1, force_suppress=False, in_format="corner",
            out_format="corner", valid_thresh=0.0, **kw):
    """Generic non-maximum suppression over (..., N, K) box records
    (reference: bounding_box.cc box_nms, bounding_box-inl.h:50-86).

    Entries are sorted by descending score; suppressed/invalid entries are
    set to -1. Boxes with score <= valid_thresh are invalid.
    """
    shape = data.shape
    N, K = shape[-2], shape[-1]
    flat = data.reshape((-1, N, K))
    cs, si = int(coord_start), int(score_index)

    def one(d):
        score = d[:, si]
        valid = score > valid_thresh
        boxes = d[:, cs:cs + 4]
        if in_format == "center":
            cxy, wh = boxes[:, :2], boxes[:, 2:]
            boxes = jnp.concatenate([cxy - wh / 2, cxy + wh / 2], axis=1)
        ids = d[:, int(id_index)] if int(id_index) >= 0 \
            else jnp.zeros(N, d.dtype)
        order = jnp.argsort(-jnp.where(valid, score, -jnp.inf))
        d_s, boxes_s, ids_s = d[order], boxes[order], ids[order]
        valid_s, score_s = valid[order], score[order]
        if topk > 0:
            valid_s = valid_s & (jnp.arange(N) < int(topk))
        keep = _greedy_nms_keep(boxes_s, ids_s, valid_s,
                                float(overlap_thresh),
                                bool(force_suppress) or int(id_index) < 0)
        out = d_s
        if out_format == "center" and in_format == "corner":
            b = d_s[:, cs:cs + 4]
            out = out.at[:, cs:cs + 4].set(jnp.concatenate(
                [(b[:, :2] + b[:, 2:]) / 2, b[:, 2:] - b[:, :2]], axis=1))
        elif out_format == "corner" and in_format == "center":
            out = out.at[:, cs:cs + 4].set(boxes_s)
        return jnp.where(keep[:, None], out, -1.0)

    return jax.vmap(one)(flat).reshape(shape)


# ---------------------------------------------------------------------------
# FFT / IFFT (reference: src/operator/contrib/fft-inl.h, ifft-inl.h —
# cuFFT C2C; here jnp.fft, output layout interleaved [re, im] per element)
# ---------------------------------------------------------------------------
@register_op("fft", aliases=["_contrib_fft"])
def fft(data, compute_size=128, **kw):
    """Real input (..., d) -> (..., 2d) interleaved real/imag of the
    unnormalized FFT along the last axis (reference: fft-inl.h; layout
    verified against the reference's GPU operator test,
    incubator-mxnet/tests/python/gpu/test_operator_gpu.py:189)."""
    spec = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    out = jnp.stack([spec.real, spec.imag], axis=-1)
    return out.reshape(data.shape[:-1] + (2 * data.shape[-1],)) \
        .astype(data.dtype)


@register_op("ifft", aliases=["_contrib_ifft"])
def ifft(data, compute_size=128, **kw):
    """Interleaved (..., 2d) -> real (..., d), unnormalized (x d) like
    cuFFT inverse (reference: ifft-inl.h; the same test file, :108,
    compares out/d with np.fft.ifft)."""
    d = data.shape[-1] // 2
    pairs = data.reshape(data.shape[:-1] + (d, 2)).astype(jnp.float32)
    spec = jax.lax.complex(pairs[..., 0], pairs[..., 1])
    out = jnp.fft.ifft(spec, axis=-1).real * d
    return out.astype(data.dtype)


# ---------------------------------------------------------------------------
# Correlation (FlowNet cost volume; reference: src/operator/correlation.cc)
# ---------------------------------------------------------------------------
@register_op("Correlation")
def correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True, **kw):
    """Patch correlation between two NCHW feature maps
    (reference: correlation.cc:40-82 CorrelationForward). The reference's
    6-deep displacement loop becomes one fused jnp expression per
    displacement (G = (2*max_displacement/stride2+1)^2 static slices);
    gradients come from autodiff instead of the hand-written backward.
    """
    kernel_size = int(kernel_size)
    max_displacement = int(max_displacement)
    stride1, stride2, pad_size = int(stride1), int(stride2), int(pad_size)
    is_multiply = bool(is_multiply)
    n, c, h, w = data1.shape
    kernel_radius = (kernel_size - 1) // 2
    border = max_displacement + kernel_radius
    padded_h, padded_w = h + 2 * pad_size, w + 2 * pad_size
    top_h = int(np.ceil((padded_h - border * 2) / float(stride1)))
    top_w = int(np.ceil((padded_w - border * 2) / float(stride1)))
    grid_radius = max_displacement // stride2
    grid_width = 2 * grid_radius + 1
    sumelems = kernel_size * kernel_size * c

    p1 = jnp.pad(data1, ((0, 0), (0, 0), (pad_size, pad_size),
                         (pad_size, pad_size)))
    p2 = jnp.pad(data2, ((0, 0), (0, 0), (pad_size, pad_size),
                         (pad_size, pad_size)))

    # top-left corners of the kernel window in the padded maps:
    # x1 = j*stride1 + max_displacement - kernel_radius ... but the
    # reference indexes tmp[y1+h][x1+w] with y1 = i*stride1 + max_disp
    # over a (kernel) window, i.e. window origin y1 (kernel_radius folded
    # into border for the output size only)
    ys = jnp.arange(top_h) * stride1 + max_displacement
    xs = jnp.arange(top_w) * stride1 + max_displacement

    outs = []
    for tc in range(grid_width * grid_width):
        s2o = (tc % grid_width - grid_radius) * stride2
        s2p = (tc // grid_width - grid_radius) * stride2
        acc = 0.0
        for kh in range(kernel_size):
            for kw_ in range(kernel_size):
                a = p1[:, :, ys[:, None] + kh, xs[None, :] + kw_]
                b = p2[:, :, ys[:, None] + s2p + kh,
                       xs[None, :] + s2o + kw_]
                acc = acc + (a * b if is_multiply else jnp.abs(a - b))
        outs.append(acc.sum(axis=1) / sumelems)      # (n, top_h, top_w)
    return jnp.stack(outs, axis=1)                   # (n, G^2, top_h, top_w)


# ---------------------------------------------------------------------------
# Crop (legacy; reference: src/operator/crop.cc MXNET_REGISTER_OP_PROPERTY)
# ---------------------------------------------------------------------------
@register_op("Crop", num_outputs=1)
def crop_op(*inputs, offset=(0, 0), h_w=(0, 0), center_crop=False,
            num_args=None, **kw):
    """Crop an NCHW tensor to h_w or to the size of a second input
    (reference: crop-inl.h)."""
    data = inputs[0]
    if len(inputs) > 1:
        out_h, out_w = inputs[1].shape[2], inputs[1].shape[3]
    else:
        out_h, out_w = (int(x) for x in h_w)
    if center_crop:
        o_h = (data.shape[2] - out_h) // 2
        o_w = (data.shape[3] - out_w) // 2
    else:
        o_h, o_w = (int(x) for x in offset)
    return data[:, :, o_h:o_h + out_h, o_w:o_w + out_w]


# ---------------------------------------------------------------------------
# RPN Proposal (reference: src/operator/contrib/proposal.cc,
# multi_proposal.cc)
# ---------------------------------------------------------------------------
def _generate_base_anchors(feature_stride, scales, ratios):
    """(reference: proposal-inl.h:184-223 GenerateAnchors — including the
    floor/round quirks, which the test-suite numerics depend on)."""
    base = [0.0, 0.0, feature_stride - 1.0, feature_stride - 1.0]
    w = base[2] - base[0] + 1.0
    h = base[3] - base[1] + 1.0
    x_ctr = base[0] + 0.5 * (w - 1.0)
    y_ctr = base[1] + 0.5 * (h - 1.0)
    size = w * h
    anchors = []
    for ratio in ratios:
        size_ratio = np.floor(size / ratio)
        new_w = np.floor(np.sqrt(size_ratio) + 0.5)
        new_h = np.floor(new_w * ratio + 0.5)
        for scale in scales:
            sw, sh = new_w * scale, new_h * scale
            anchors.append([x_ctr - 0.5 * (sw - 1), y_ctr - 0.5 * (sh - 1),
                            x_ctr + 0.5 * (sw - 1), y_ctr + 0.5 * (sh - 1)])
    return np.asarray(anchors, np.float32)


def _proposal_one(scores_fg, bbox_deltas, im_info, base_anchors,
                  feature_stride, rpn_pre_nms_top_n, rpn_post_nms_top_n,
                  threshold, rpn_min_size):
    """Single-image RPN proposal generation (reference: proposal.cc:300+
    Forward): shift anchors, decode deltas, clip, filter small, pre-NMS
    top-k, greedy NMS, post-NMS top-k."""
    A = base_anchors.shape[0]
    H, W = scores_fg.shape[1], scores_fg.shape[2]
    shift_x = jnp.arange(W, dtype=jnp.float32) * feature_stride
    shift_y = jnp.arange(H, dtype=jnp.float32) * feature_stride
    # anchor layout index = h*(W*A) + w*A + a
    sx = jnp.broadcast_to(shift_x[None, :, None], (H, W, A))
    sy = jnp.broadcast_to(shift_y[:, None, None], (H, W, A))
    shifts = jnp.stack([sx, sy, sx, sy], axis=-1)
    anchors = (base_anchors[None, None, :, :] + shifts).reshape(-1, 4)
    # deltas (4A, H, W) -> (H, W, A, 4) -> (N, 4); scores (A,H,W)->(N,)
    d = bbox_deltas.reshape(A, 4, H, W).transpose(2, 3, 0, 1).reshape(-1, 4)
    s = scores_fg.transpose(1, 2, 0).reshape(-1)

    widths = anchors[:, 2] - anchors[:, 0] + 1.0
    heights = anchors[:, 3] - anchors[:, 1] + 1.0
    ctr_x = anchors[:, 0] + 0.5 * (widths - 1.0)
    ctr_y = anchors[:, 1] + 0.5 * (heights - 1.0)
    pred_ctr_x = d[:, 0] * widths + ctr_x
    pred_ctr_y = d[:, 1] * heights + ctr_y
    pred_w = jnp.exp(d[:, 2]) * widths
    pred_h = jnp.exp(d[:, 3]) * heights
    im_h, im_w = im_info[0], im_info[1]
    x1 = jnp.clip(pred_ctr_x - 0.5 * (pred_w - 1), 0, im_w - 1)
    y1 = jnp.clip(pred_ctr_y - 0.5 * (pred_h - 1), 0, im_h - 1)
    x2 = jnp.clip(pred_ctr_x + 0.5 * (pred_w - 1), 0, im_w - 1)
    y2 = jnp.clip(pred_ctr_y + 0.5 * (pred_h - 1), 0, im_h - 1)
    boxes = jnp.stack([x1, y1, x2, y2], axis=1)
    # filter too-small boxes (reference FilterBox: score -> -1)
    iw = x2 - x1 + 1.0
    ih = y2 - y1 + 1.0
    min_size = rpn_min_size * im_info[2]  # scaled by im_scale
    s = jnp.where((iw < min_size) | (ih < min_size), -1.0, s)

    order = jnp.argsort(-s)
    if rpn_pre_nms_top_n > 0:
        order = order[:rpn_pre_nms_top_n]
    boxes_s, s_s = boxes[order], s[order]
    valid = s_s > -1.0
    keep = _greedy_nms_keep(boxes_s, jnp.zeros(boxes_s.shape[0]), valid,
                            threshold, True)
    # compact kept boxes to the front, pad with the first kept one
    rank = jnp.argsort(~keep, stable=True)       # kept first, stable order
    boxes_k = boxes_s[rank]
    score_k = s_s[rank]
    n_keep = keep.sum()
    idx = jnp.minimum(jnp.arange(rpn_post_nms_top_n), n_keep - 1)
    rois = boxes_k[idx]
    roi_scores = score_k[idx]
    return rois, roi_scores


@register_op("Proposal", aliases=["_contrib_Proposal"], no_grad=True,
             num_outputs=1)
def proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2), feature_stride=16,
             output_score=False, iou_loss=False, **kw):
    """RPN region proposals (reference: src/operator/contrib/proposal.cc).

    cls_prob: (B, 2A, H, W) softmax fg/bg; bbox_pred: (B, 4A, H, W);
    im_info: (B, 3) [height, width, scale]. Output rois
    (B*rpn_post_nms_top_n, 5) rows [batch_idx, x1, y1, x2, y2].
    """
    if iou_loss:
        raise NotImplementedError("Proposal: iou_loss=True")
    scales = _tuplef(scales, (4, 8, 16, 32))
    ratios = _tuplef(ratios, (0.5, 1, 2))
    base = jnp.asarray(_generate_base_anchors(float(feature_stride),
                                              scales, ratios))
    B = cls_prob.shape[0]
    A = base.shape[0]
    rois_all, scores_all = [], []
    for b in range(B):
        fg = cls_prob[b, A:, :, :]  # foreground scores (A, H, W)
        rois, rs = _proposal_one(
            fg, bbox_pred[b], im_info[b], base, float(feature_stride),
            int(rpn_pre_nms_top_n), int(rpn_post_nms_top_n),
            float(threshold), float(rpn_min_size))
        batch_col = jnp.full((rois.shape[0], 1), float(b))
        rois_all.append(jnp.concatenate([batch_col, rois], axis=1))
        scores_all.append(rs[:, None])
    out = jnp.concatenate(rois_all, axis=0)
    if output_score:
        return out, jnp.concatenate(scores_all, axis=0)
    return out


@register_op("MultiProposal", aliases=["_contrib_MultiProposal"],
             no_grad=True)
def multi_proposal(cls_prob, bbox_pred, im_info, **kw):
    """Batch variant (reference: src/operator/contrib/multi_proposal.cc —
    same math as Proposal over every image)."""
    kw.pop("output_score", None)
    return proposal(cls_prob, bbox_pred, im_info, output_score=False, **kw)


# ---------------------------------------------------------------------------
# count_sketch (reference: src/operator/contrib/count_sketch-inl.h:47 —
# compact bilinear pooling building block)
# ---------------------------------------------------------------------------
@register_op("count_sketch", aliases=["_contrib_count_sketch"])
def count_sketch(data, h, s, out_dim=None, processing_batch_size=32, **kw):
    """Count sketch projection: out[n, h[i]] += s[i] * data[n, i].

    data: (n, in_dim); h: (1, in_dim) int hash bucket per input dim;
    s: (1, in_dim) signs in {-1, +1}. Output (n, out_dim). The scatter-add
    maps to one segment_sum; gradients come from autodiff (the reference
    hand-writes the mirrored gather kernel)."""
    if out_dim is None:
        raise ValueError("count_sketch requires out_dim")
    out_dim = int(out_dim)
    n, in_dim = data.shape
    hh = h.reshape(-1).astype(jnp.int32)
    ss = s.reshape(-1).astype(data.dtype)
    signed = data * ss[None, :]
    out = jax.ops.segment_sum(signed.T, hh, num_segments=out_dim)  # (out, n)
    return out.T


# ---------------------------------------------------------------------------
# Deformable convolution (DCN v1; reference:
# src/operator/contrib/deformable_convolution-inl.h,
# nn/deformable_im2col.cuh:216-260 — offset layout [dg][2*(i*Kw+j)] with the
# h-offset first, sample = (h_in + i*dil + off_h, w_in + j*dil + off_w),
# zero outside the image)
# ---------------------------------------------------------------------------
def _bilinear_sample_chw(img, ys, xs):
    """Bilinear sample a (C, H, W) image at float positions ys/xs (...,).
    Out-of-image points and out-of-range corners contribute zero, matching
    the reference kernel's bounds checks."""
    C, H, W = img.shape
    y0 = jnp.floor(ys)
    x0 = jnp.floor(xs)
    dy = ys - y0
    dx = xs - x0
    out = 0.0
    for cy, wy in ((y0, 1 - dy), (y0 + 1, dy)):
        for cx, wx in ((x0, 1 - dx), (x0 + 1, dx)):
            valid = (cy >= 0) & (cy < H) & (cx >= 0) & (cx < W)
            yi = jnp.clip(cy, 0, H - 1).astype(jnp.int32)
            xi = jnp.clip(cx, 0, W - 1).astype(jnp.int32)
            v = img[:, yi, xi]                        # (C, ...)
            out = out + jnp.where(valid, wy * wx, 0.0) * v
    # no whole-point mask: the reference guard is h_im > -1 (partial
    # bilinear contributions at the border), which the per-corner checks
    # above reproduce exactly
    return out                                        # (C, ...)


def _deform_conv_one(data, offset, weight, kernel, stride, dilate, pad,
                     num_group, num_deformable_group):
    """Single-sample deformable conv: data (C,H,W), offset (2*dg*Kh*Kw,
    oh,ow), weight (F, C/g, Kh, Kw)."""
    C, H, W = data.shape
    kh, kw = kernel
    sh, sw = stride
    dh, dw = dilate
    ph, pw = pad
    oh = (H + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    ow = (W + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    dg = num_deformable_group
    off = offset.reshape(dg, kh * kw, 2, oh, ow)
    h_in = jnp.arange(oh) * sh - ph
    w_in = jnp.arange(ow) * sw - pw

    cpg = C // dg                                    # channels per dg
    cols = []
    for tap in range(kh * kw):
        i, j = tap // kw, tap % kw
        tap_cols = []
        for g in range(dg):
            ys = h_in[:, None] + i * dh + off[g, tap, 0]
            xs = w_in[None, :] + j * dw + off[g, tap, 1]
            sampled = _bilinear_sample_chw(
                data[g * cpg:(g + 1) * cpg], ys, xs)   # (cpg, oh, ow)
            tap_cols.append(sampled)
        cols.append(jnp.concatenate(tap_cols, axis=0))  # (C, oh, ow)
    col = jnp.stack(cols, axis=1)                       # (C, Kh*Kw, oh, ow)

    F = weight.shape[0]
    cg = C // num_group
    fg = F // num_group
    outs = []
    for g in range(num_group):
        w_g = weight[g * fg:(g + 1) * fg].reshape(fg, cg * kh * kw)
        c_g = col[g * cg:(g + 1) * cg].reshape(cg * kh * kw, oh * ow)
        outs.append((w_g @ c_g).reshape(fg, oh, ow))
    return jnp.concatenate(outs, axis=0)                # (F, oh, ow)


@register_op("DeformableConvolution",
             aliases=["_contrib_DeformableConvolution"])
def deformable_convolution(data, offset, weight, bias=None, kernel=None,
                           stride=(1, 1), dilate=(1, 1), pad=(0, 0),
                           num_filter=0, num_group=1,
                           num_deformable_group=1, no_bias=False, **kw):
    """Deformable convolution: sampling locations shifted by learned
    offsets. Gradients (data, offset, weight) all come from autodiff of
    the bilinear sampling — the reference hand-writes three kernels
    (deformable_col2im, _col2im_coord, im2col)."""
    kernel = tuple(int(k) for k in _tuplef(kernel, (3, 3)))
    stride = tuple(int(s) for s in _tuplef(stride, (1, 1)))
    dilate = tuple(int(d) for d in _tuplef(dilate, (1, 1)))
    pad = tuple(int(p) for p in _tuplef(pad, (0, 0)))
    fn = lambda d, o: _deform_conv_one(d, o, weight, kernel, stride,
                                       dilate, pad, int(num_group),
                                       int(num_deformable_group))
    out = jax.vmap(fn)(data, offset)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


# ---------------------------------------------------------------------------
# Position-sensitive ROI pooling (R-FCN family; reference:
# src/operator/contrib/psroi_pooling.cu:51-120,
# deformable_psroi_pooling.cu:71-161)
# ---------------------------------------------------------------------------
def _psroi_one(data, roi, spatial_scale, output_dim, group_size, pooled):
    """One ROI over one batch of feature maps: data (B, C, H, W),
    roi [batch_ind, x1, y1, x2, y2]. Integer-grid average pooling of the
    position-sensitive channel (psroi_pooling.cu:51)."""
    B, C, H, W = data.shape
    G = group_size
    img = data[roi[0].astype(jnp.int32)]
    ps = img.reshape(output_dim, G, G, H, W)
    # floor(x + 0.5) = C round() for the non-negative ROI coords
    # (jnp.round is half-to-even and would shift half-integer ROIs)
    start_w = jnp.floor(roi[1] + 0.5) * spatial_scale
    start_h = jnp.floor(roi[2] + 0.5) * spatial_scale
    end_w = (jnp.floor(roi[3] + 0.5) + 1.0) * spatial_scale
    end_h = (jnp.floor(roi[4] + 0.5) + 1.0) * spatial_scale
    roi_w = jnp.maximum(end_w - start_w, 0.1)
    roi_h = jnp.maximum(end_h - start_h, 0.1)
    bin_h = roi_h / pooled
    bin_w = roi_w / pooled
    hs = jnp.arange(H, dtype=jnp.float32)
    ws = jnp.arange(W, dtype=jnp.float32)
    out = []
    for ph in range(pooled):
        row = []
        for pw in range(pooled):
            hstart = jnp.clip(jnp.floor(ph * bin_h + start_h), 0, H)
            hend = jnp.clip(jnp.ceil((ph + 1) * bin_h + start_h), 0, H)
            wstart = jnp.clip(jnp.floor(pw * bin_w + start_w), 0, W)
            wend = jnp.clip(jnp.ceil((pw + 1) * bin_w + start_w), 0, W)
            mask = ((hs[:, None] >= hstart) & (hs[:, None] < hend)
                    & (ws[None, :] >= wstart) & (ws[None, :] < wend))
            gh = min(max(int(ph * G // pooled), 0), G - 1)
            gw = min(max(int(pw * G // pooled), 0), G - 1)
            sel = ps[:, gh, gw]                       # (output_dim, H, W)
            total = jnp.sum(sel * mask, axis=(1, 2))
            area = jnp.maximum(mask.sum(), 1)
            empty = (hend <= hstart) | (wend <= wstart)
            row.append(jnp.where(empty, 0.0, total / area))
        out.append(jnp.stack(row, axis=-1))
    return jnp.stack(out, axis=-2)                    # (output_dim, p, p)


@register_op("PSROIPooling", aliases=["_contrib_PSROIPooling"])
def psroi_pooling(data, rois, spatial_scale=1.0, output_dim=None,
                  pooled_size=None, group_size=0, **kw):
    """Position-sensitive ROI pooling (reference: psroi_pooling.cu:51).
    data: (B, output_dim*G*G, H, W); rois: (R, 5). Output
    (R, output_dim, pooled, pooled)."""
    group_size = int(group_size) or int(pooled_size)
    fn = lambda r: _psroi_one(data, r, float(spatial_scale),
                              int(output_dim), group_size,
                              int(pooled_size))
    return jax.vmap(fn)(rois)


def _dpsroi_one(data, roi, trans, spatial_scale, output_dim, group_size,
                pooled, part_size, sample_per_part, trans_std, num_classes):
    """Deformable PSROI pooling for one ROI, fully vectorized over
    (output_dim, pooled, pooled, samples) — the reference unrolls this as
    a CUDA grid (deformable_psroi_pooling.cu:71-161)."""
    B, C, H, W = data.shape
    G = group_size
    P = pooled
    S = sample_per_part
    img = data[roi[0].astype(jnp.int32)]
    ps = img.reshape(output_dim, G, G, H, W)
    start_w = jnp.floor(roi[1] + 0.5) * spatial_scale - 0.5
    start_h = jnp.floor(roi[2] + 0.5) * spatial_scale - 0.5
    end_w = (jnp.floor(roi[3] + 0.5) + 1.0) * spatial_scale - 0.5
    end_h = (jnp.floor(roi[4] + 0.5) + 1.0) * spatial_scale - 0.5
    roi_w = jnp.maximum(end_w - start_w, 0.1)
    roi_h = jnp.maximum(end_h - start_h, 0.1)
    bin_h = roi_h / P
    bin_w = roi_w / P
    sub_h = bin_h / S
    sub_w = bin_w / S

    ph = jnp.arange(P)
    pw = jnp.arange(P)
    # per-bin trans offsets; class of channel ctop = ctop // cls_per
    cls_per = output_dim // num_classes
    if trans is None:
        tx = jnp.zeros((output_dim, P, P))
        ty = jnp.zeros((output_dim, P, P))
    else:
        part_h = (ph * part_size // P)                        # (P,)
        part_w = (pw * part_size // P)                        # (P,)
        cls = jnp.arange(output_dim) // cls_per               # (D,)
        tx = trans[cls[:, None, None] * 2,
                   part_h[None, :, None], part_w[None, None, :]] * trans_std
        ty = trans[cls[:, None, None] * 2 + 1,
                   part_h[None, :, None], part_w[None, None, :]] * trans_std

    # sample positions: (D, P, P, S, S)
    ih = jnp.arange(S)
    iw = jnp.arange(S)
    hpos = (ph[None, :, None, None, None] * bin_h + start_h
            + ty[:, :, :, None, None] * roi_h
            + ih[None, None, None, :, None] * sub_h)
    wpos = (pw[None, None, :, None, None] * bin_w + start_w
            + tx[:, :, :, None, None] * roi_w
            + iw[None, None, None, None, :] * sub_w)
    hpos = jnp.broadcast_to(hpos, (output_dim, P, P, S, S))
    wpos = jnp.broadcast_to(wpos, (output_dim, P, P, S, S))

    ok = ((wpos >= -0.5) & (wpos <= W - 0.5)
          & (hpos >= -0.5) & (hpos <= H - 0.5))
    hc = jnp.clip(hpos, 0.0, H - 1.0)
    wc = jnp.clip(wpos, 0.0, W - 1.0)
    h0 = jnp.floor(hc)
    w0 = jnp.floor(wc)
    dh = hc - h0
    dw = wc - w0
    h0i = h0.astype(jnp.int32)
    w0i = w0.astype(jnp.int32)
    h1i = jnp.minimum(h0i + 1, H - 1)
    w1i = jnp.minimum(w0i + 1, W - 1)

    # position-sensitive channel per bin: sel (D, P, P, H, W)
    gh = jnp.clip(ph * G // P, 0, G - 1)
    gw = jnp.clip(pw * G // P, 0, G - 1)
    sel = ps[:, gh[:, None], gw[None, :]]                     # (D,P,P,H,W)

    d_ix = jnp.arange(output_dim)[:, None, None, None, None]
    p_ix = jnp.arange(P)[None, :, None, None, None]
    q_ix = jnp.arange(P)[None, None, :, None, None]
    v = (sel[d_ix, p_ix, q_ix, h0i, w0i] * (1 - dh) * (1 - dw)
         + sel[d_ix, p_ix, q_ix, h0i, w1i] * (1 - dh) * dw
         + sel[d_ix, p_ix, q_ix, h1i, w0i] * dh * (1 - dw)
         + sel[d_ix, p_ix, q_ix, h1i, w1i] * dh * dw)
    acc = jnp.sum(jnp.where(ok, v, 0.0), axis=(3, 4))
    cnt = jnp.sum(ok, axis=(3, 4))
    return jnp.where(cnt > 0, acc / jnp.maximum(cnt, 1), 0.0)


@register_op("DeformablePSROIPooling",
             aliases=["_contrib_DeformablePSROIPooling"])
def deformable_psroi_pooling(data, rois, trans=None, spatial_scale=1.0,
                             output_dim=None, group_size=None,
                             pooled_size=None, part_size=0,
                             sample_per_part=4, trans_std=0.0,
                             no_trans=False, **kw):
    """Deformable position-sensitive ROI pooling (R-FCN / DCN v1;
    reference: deformable_psroi_pooling.cu:71). trans: (R,
    num_classes*2, part, part) normalized bin offsets."""
    part_size = int(part_size) or int(pooled_size)
    if no_trans:
        trans = None
    num_classes = 1
    if trans is not None:
        num_classes = trans.shape[1] // 2
    fn = lambda r, t: _dpsroi_one(
        data, r, t, float(spatial_scale), int(output_dim),
        int(group_size), int(pooled_size), part_size,
        int(sample_per_part), float(trans_std), num_classes)
    if trans is None:
        return jax.vmap(lambda r: fn(r, None))(rois)
    return jax.vmap(fn)(rois, trans)
