"""Per-op fixtures for the registry gradient sweep (tools/grad_sweep.py,
frozen into tests/test_op_gradients.py).

Each entry: inputs (numpy arrays), attrs, optional mode:
  'grad' (default) — jax.grad vs directional finite differences
  'fwd'            — forward-only (stochastic / custom-backward / int ops)
  'skip'           — not runnable as a pure array op (reason required)
and optional grad_args (inputs differentiated), tol, outputs (how many of
the op's outputs take a gradient, from the first; all without).
Shapes follow the op's reference contract (conv NCHW, RNN TNC, ...).
"""
import numpy as np


def _r(seed=0):
    return np.random.RandomState(seed)


def _pos(shape, seed=0, lo=0.4, hi=1.3):
    return _r(seed).uniform(lo, hi, shape).astype(np.float32)


def _signed(shape, seed=0):
    r = _r(seed)
    return (_pos(shape, seed) *
            np.where(r.rand(*shape) < 0.5, -1, 1)).astype(np.float32)


def _img(shape=(2, 3, 8, 8), seed=0):
    return _signed(shape, seed)


def _boxes(n=4, seed=0):
    r = _r(seed)
    x1 = r.uniform(0, 0.4, (1, n, 1))
    y1 = r.uniform(0, 0.4, (1, n, 1))
    x2 = x1 + r.uniform(0.2, 0.5, (1, n, 1))
    y2 = y1 + r.uniform(0.2, 0.5, (1, n, 1))
    return np.concatenate([x1, y1, x2, y2], -1).astype(np.float32)


_DOM01 = dict(lo=0.05, hi=0.92)      # (0,1) open-interval domains

CASES = {
    # -- layers ---------------------------------------------------------------
    "Convolution": dict(
        inputs=[_img(), _signed((5, 3, 3, 3), 1)],
        attrs=dict(num_filter=5, kernel=(3, 3), stride=(1, 1),
                   pad=(1, 1), no_bias=True)),
    "Deconvolution": dict(
        inputs=[_img(), _signed((3, 5, 3, 3), 1)],
        attrs=dict(num_filter=5, kernel=(3, 3), stride=(2, 2),
                   no_bias=True)),
    "conv_s2d_stem": dict(
        inputs=[_img((2, 3, 16, 16)), _signed((8, 3, 7, 7), 1)]),
    "Pooling": dict(inputs=[_img()],
                    attrs=dict(kernel=(2, 2), stride=(2, 2),
                               pool_type="avg")),
    "BatchNorm": dict(
        inputs=[_img((2, 4, 5, 5)), _pos((4,), 1), _signed((4,), 2),
                _signed((4,), 3), _pos((4,), 4)],
        attrs=dict(fix_gamma=False), grad_args=[0, 1, 2]),
    "_FusedBNReLUConv": dict(
        # BN(+1x1-conv) fused op (ops/pallas_fused.py): 8-divisible
        # channels so the Pallas path (analytic custom VJP) is the one
        # checked. Finite differences need the smooth bare-BN variant
        # (act_type=None) — the relu kink makes directional FD
        # unreliable; the relu path's gradient is pinned against
        # autodiff by tests/test_fusion_pass.py instead.
        inputs=[_img((2, 8, 4, 4)), _pos((8,), 1), _signed((8,), 2),
                _signed((8,), 3), _pos((8,), 4),
                _signed((16, 8, 1, 1), 5)],
        attrs=dict(fix_gamma=False, num_filter=16, no_bias=True,
                   training=True, act_type=None),
        grad_args=[0, 1, 2, 5], tol=(5e-2, 5e-3)),
    "_FusedBNReLUConvK": dict(
        # general-geometry BN(+conv) fused op (round 12,
        # ops/pallas_fused.py): a 3x3/stride-2 site the Pallas op can't
        # take, through the same analytic custom VJP. Bare-BN variant
        # for the same FD-smoothness reason as _FusedBNReLUConv; the
        # relu path is pinned against autodiff in tests/test_passes.py.
        inputs=[_img((2, 8, 5, 5)), _pos((8,), 1), _signed((8,), 2),
                _signed((8,), 3), _pos((8,), 4),
                _signed((6, 8, 3, 3), 5)],
        attrs=dict(fix_gamma=False, num_filter=6, no_bias=True,
                   training=True, act_type=None, kernel=(3, 3),
                   stride=(2, 2), pad=(1, 1)),
        grad_args=[0, 1, 2, 5], tol=(5e-2, 5e-3)),
    "LayerNorm": dict(
        inputs=[_signed((3, 6), 0), _pos((6,), 1), _signed((6,), 2)]),
    "CausalSelfAttention": dict(
        # packed QKV (B, S, 3*heads*head_dim) from the fused projection
        # (round 16, serving/decode); the blockwise max/denominator
        # recurrence is smooth in data, so plain FD applies.
        inputs=[_signed((2, 4, 3 * 2 * 3), 0)],
        attrs=dict(num_heads=2)),
    "Mamba2Mixer": dict(
        # ops/seq.py: 2 heads of 4, state 4, one group, chunk 4 over 6
        # steps (a padded tail); in_proj rows [z 8 | x B C 16 | dt 2],
        # small so that the decays' exponentials stay in FD's reach
        inputs=[_signed((2, 6, 8), 0), 0.3 * _signed((26, 8), 1),
                _signed((16, 4), 2), _signed((16,), 3), _signed((2,), 4),
                _pos((2,), 5), _signed((2,), 6), _pos((8,), 7),
                _signed((8, 8), 8)],
        attrs=dict(num_heads=2, head_dim=4, state_size=4, num_groups=1,
                   chunk_size=4),
        grad_args=[0, 1, 2, 3, 4, 5, 6, 7, 8], tol=(6e-2, 6e-3)),
    "GatedDeltaNet": dict(
        # ops/seq.py: 1 key head of 4 serving 2 value heads of 3, chunk 4
        # over 6 steps (a padded tail); qkvz rows [q 4 | k 4 | v 6 | z 6],
        # ba rows [b 2 | a 2], a 4-tap convolution over the 14 q, k, v
        # channels; small weights so that the decays' exponentials and the
        # chunk's triangular solve stay in FD's reach, and an eps under the
        # gated norm's root that its small rows do not vanish beside (at
        # 1e-6 the norm is too curved for a step of 1e-2)
        inputs=[_signed((2, 6, 8), 0), 0.3 * _signed((20, 8), 1),
                0.3 * _signed((4, 8), 2), 0.5 * _signed((14, 4), 3),
                _signed((2,), 4), 0.3 * _signed((2,), 5), _pos((3,), 6),
                _signed((8, 6), 7)],
        attrs=dict(num_k_heads=1, num_v_heads=2, key_dim=4, value_dim=3,
                   chunk_size=4, eps=1e-2),
        tol=(6e-2, 6e-3)),
    "LatentMoE": dict(
        # ops/seq.py: 6 experts, 2 of 3 held, top-2, a buffer with room;
        # the choice is piecewise constant, so FD sees the smooth part
        # (tests/test_seq_ops.py pins the gradients against the plain
        # reference); the bias takes no gradient
        inputs=[_signed((1, 5, 8), 0), _signed((6, 8), 1),
                _signed((6,), 2), _signed((4, 8), 3), _signed((8, 4), 4),
                _signed((2, 4, 6), 5), _signed((2, 6, 4), 6),
                _signed((3, 8), 7), _signed((8, 3), 8)],
        attrs=dict(expert_ids=(1, 4), top_k=2, buffer_rows=10,
                   scaling=2.5),
        grad_args=[0, 3, 4, 5, 6, 7, 8], tol=(8e-2, 8e-3)),
    "GatedMoE": dict(
        # ops/seq.py: LatentMoE's routing around gated experts on the full
        # hidden vector: 6 experts, 2 held, top-2, w1/w3 (2, 8, 6), w2 (2,
        # 6, 8), a gated shared MLP 3 wide; the choice is piecewise
        # constant (tests/test_gated_moe.py pins the gradients against the
        # plain reference); the bias takes no gradient
        inputs=[_signed((1, 5, 8), 0), _signed((6, 8), 1),
                _signed((6,), 2), 0.5 * _signed((2, 8, 6), 3),
                0.5 * _signed((2, 8, 6), 4), 0.5 * _signed((2, 6, 8), 5),
                0.5 * _signed((6, 8), 6), 0.5 * _signed((8, 3), 7)],
        attrs=dict(expert_ids=(1, 4), top_k=2, buffer_rows=10,
                   scaling=2.5),
        grad_args=[0, 3, 4, 5, 6, 7], tol=(8e-2, 8e-3)),
    "RoutedMoE": dict(
        # ops/seq.py: GatedMoE of a layer with no shared expert: the same
        # routing and experts, the routed sum alone, the chosen scores
        # over their sum + 1e-6 (tests/test_short_conv.py pins the
        # gradients against the plain reference)
        inputs=[_signed((1, 5, 8), 0), _signed((6, 8), 1),
                _signed((6,), 2), 0.5 * _signed((2, 8, 6), 3),
                0.5 * _signed((2, 8, 6), 4), 0.5 * _signed((2, 6, 8), 5)],
        attrs=dict(expert_ids=(1, 4), top_k=2, buffer_rows=10,
                   norm_topk_eps=1e-6),
        grad_args=[0, 3, 4, 5], tol=(8e-2, 8e-3)),
    "GatedShortConv": dict(
        # ops/seq.py: in_weight rows [B 8 | C 8 | z 8], three causal taps
        # a channel over 6 steps, no activation: a cubic in the input
        inputs=[_signed((2, 6, 8), 0), 0.5 * _signed((24, 8), 1),
                0.5 * _signed((8, 3), 2), 0.5 * _signed((8, 8), 3)],
        tol=(6e-2, 6e-3)),
    "LatentAttention": dict(
        # ops/seq.py: 2 heads, q/k 4 + 2 wide (the 2-wide rotary key
        # shared by both heads), values 3 wide, a latent of 6, blocks of
        # 2 over 5 steps; rows grouped by part: Wq [q_nope 8 | q_pe 4],
        # Wkva [c 6 | k_pe 2], Wkvb [k_nope 8 | v 6]
        inputs=[_signed((2, 5, 8), 0), 0.5 * _signed((12, 8), 1),
                0.5 * _signed((8, 8), 2), _pos((6,), 3),
                0.5 * _signed((14, 6), 4), 0.5 * _signed((8, 6), 5)],
        attrs=dict(num_heads=2, nope_dim=4, rope_dim=2, v_dim=3,
                   latent_dim=6, rope_theta=50.0, block=2),
        tol=(6e-2, 6e-3)),
    "CausalGQAttention": dict(
        # ops/seq.py: packed [q | k | v], 2 query heads on 1 key/value
        # head of 3, blocks of 2 over 5 steps
        inputs=[_signed((2, 5, (2 + 2) * 3), 0)],
        attrs=dict(num_heads=2, num_kv_heads=1, head_dim=3, block=2)),
    "RoPE": dict(
        # ops/seq.py: (B, L, H, D) with an even D; a small theta so that
        # every pair turns visibly within 5 positions
        inputs=[_signed((2, 5, 2, 4), 0)], attrs=dict(theta=50.0)),
    "HyperConnectionMaps": dict(
        # ops/seq.py: n = 2 streams of 3 (n C = 6), phi (8, 6) rows [pre 2 |
        # post 2 | res 4], one alpha a map and a bias of order 1, so that
        # no map is near the identity; the fourth output (what the
        # iterations leave) takes no gradient
        inputs=[_signed((2, 5, 6), 0), _signed((8, 6), 1),
                _pos((3,), 2), _signed((8,), 3)],
        attrs=dict(streams=2, iters=20, eps=1e-6), outputs=3),
    "HyperConnectionRead": dict(
        # ops/seq.py: the maps and the mix in one, the same fixture; the
        # fifth output (what the iterations leave) takes no gradient
        inputs=[_signed((2, 5, 6), 0), _signed((8, 6), 1),
                _pos((3,), 2), _signed((8,), 3)],
        attrs=dict(streams=2, iters=20, eps=1e-6), outputs=4),
    "HyperConnectionPre": dict(
        inputs=[_signed((2, 5, 6), 0), _pos((2, 2, 5), 1)]),
    "HyperConnectionPost": dict(
        inputs=[_signed((2, 5, 6), 0), _signed((2, 5, 3), 1),
                _pos((2, 2, 2, 5), 2), _pos((2, 2, 5), 3)]),
    "HyperConnectionSpread": dict(
        inputs=[_signed((2, 5, 3), 0)], attrs=dict(streams=2)),
    "HyperConnectionMerge": dict(
        inputs=[_signed((2, 5, 6), 0)], attrs=dict(streams=2)),
    "GatedMLP": dict(
        # ops/seq.py: [gate | up] rows of one (2 f, hidden) weight, f = 4
        inputs=[_signed((2, 5, 6), 0), 0.5 * _signed((8, 6), 1),
                0.5 * _signed((6, 4), 2)]),
    "ExitGate": dict(
        # ops/seq.py: 3 passes of (2, 4) rows of 6; the second output
        # (the gauges' statistics) takes no gradient
        inputs=[_signed((3, 2, 4, 6), 0), _signed((1, 6), 1),
                _signed((1,), 2)], outputs=1),
    "InstanceNorm": dict(
        inputs=[_img((2, 3, 4, 4)), _pos((3,), 1), _signed((3,), 2)]),
    "L2Normalization": dict(inputs=[_signed((3, 5), 0)]),
    "LRN": dict(inputs=[_img((2, 6, 4, 4))],
                attrs=dict(nsize=3), tol=(8e-2, 1e-2)),
    "FullyConnected": dict(
        inputs=[_signed((3, 4), 0), _signed((5, 4), 1),
                _signed((5,), 2)],
        attrs=dict(num_hidden=5)),
    "Embedding": dict(
        inputs=[np.array([[0, 2], [1, 3]], np.int32),
                _signed((4, 5), 1)],
        attrs=dict(input_dim=4, output_dim=5), grad_args=[1]),
    "_contrib_SparseEmbedding": dict(
        inputs=[np.array([[0, 2], [1, 3]], np.int32),
                _signed((4, 5), 1)],
        attrs=dict(input_dim=4, output_dim=5), grad_args=[1]),
    "_contrib_sparse_segment_sum": dict(
        # row-gradient reducer behind SparseEmbedding's backward
        # (sparse/rowsparse.py); ids take no gradient, data does —
        # segment 2 left empty to pin the zero-row path
        inputs=[_signed((6, 4), 0),
                np.array([0, 1, 0, 3, 1, 0], np.int32)],
        attrs=dict(num_segments=4), grad_args=[0]),
    "RNN": dict(
        inputs=[_signed((4, 2, 3), 0),            # (T,N,C)
                _signed((4 * 5 * (3 + 5 + 2),), 1),  # lstm flat params
                np.zeros((1, 2, 5), np.float32),
                np.zeros((1, 2, 5), np.float32)],
        attrs=dict(state_size=5, num_layers=1, mode="lstm"),
        tol=(6e-2, 6e-3)),
    "Dropout": dict(inputs=[_signed((3, 4), 0)],
                    attrs=dict(p=0.4, training=False)),
    "Activation": dict(inputs=[_signed((3, 4), 0)],
                       attrs=dict(act_type="tanh")),
    "LeakyReLU": dict(inputs=[_signed((3, 4), 0)],
                      attrs=dict(act_type="leaky")),
    "SoftmaxActivation": dict(inputs=[_signed((3, 4), 0)]),
    "Pad": dict(inputs=[_img((2, 3, 4, 4))],
                attrs=dict(mode="constant",
                           pad_width=(0, 0, 0, 0, 1, 1, 1, 1))),
    "UpSampling": dict(inputs=[_img((2, 3, 4, 4))],
                       attrs=dict(scale=2, sample_type="nearest")),
    "SliceChannel": dict(inputs=[_signed((4, 6), 0)],
                         attrs=dict(num_outputs=3, axis=1)),
    "Crop": dict(inputs=[_img((2, 3, 8, 8)), _img((2, 3, 4, 4), 1)],
                 attrs=dict(num_args=2), grad_args=[0]),
    "SwapAxis": dict(inputs=[_signed((3, 4), 0)],
                     attrs=dict(dim1=0, dim2=1)),
    "Flatten": dict(inputs=[_img((2, 3, 4, 4))]),
    "Reshape": dict(inputs=[_signed((3, 4), 0)],
                    attrs=dict(shape=(4, 3))),
    "Cast": dict(inputs=[_signed((3, 4), 0)],
                 attrs=dict(dtype="float32")),
    "Concat": dict(inputs=[_signed((3, 2), 0), _signed((3, 4), 1)],
                   attrs=dict(dim=1, num_args=2)),
    # -- output heads (identity/softmax forwards; training grads live in
    #    the executor's implicit losses — tests/test_output_heads.py) ---------
    "SoftmaxOutput": dict(inputs=[_signed((3, 4), 0),
                                  np.array([0, 2, 1], np.float32)],
                          grad_args=[0], mode="fwd"),
    "SVMOutput": dict(inputs=[_signed((3, 4), 0),
                              np.array([0, 2, 1], np.float32)],
                      mode="fwd"),
    "LinearRegressionOutput": dict(
        inputs=[_signed((3, 4), 0), _signed((3, 4), 1)], mode="fwd"),
    "MAERegressionOutput": dict(
        inputs=[_signed((3, 4), 0), _signed((3, 4), 1)], mode="fwd"),
    "LogisticRegressionOutput": dict(
        inputs=[_signed((3, 4), 0), _signed((3, 4), 1)], mode="fwd"),
    "IdentityAttachKLSparseReg": dict(
        inputs=[_pos((3, 4), 0, **_DOM01)], mode="fwd"),
    "BlockGrad": dict(inputs=[_signed((3, 4), 0)], mode="fwd"),
    "MakeLoss": dict(inputs=[_pos((3, 4), 0)]),
    # -- attention/vision extras ----------------------------------------------
    "BilinearSampler": dict(
        inputs=[_img((2, 3, 6, 6)),
                _r(1).uniform(-0.8, 0.8, (2, 2, 4, 4)).astype(
                    np.float32)],
        grad_args=[0]),
    "GridGenerator": dict(
        inputs=[_r(0).uniform(-0.5, 0.5, (2, 6)).astype(np.float32)],
        attrs=dict(transform_type="affine", target_shape=(4, 4))),
    "SpatialTransformer": dict(
        inputs=[_img((2, 3, 6, 6)),
                _r(1).uniform(-0.5, 0.5, (2, 6)).astype(np.float32)],
        attrs=dict(transform_type="affine", sampler_type="bilinear",
                   target_shape=(4, 4)),
        grad_args=[0]),
    "ROIPooling": dict(
        inputs=[_img((1, 3, 8, 8)),
                np.array([[0, 0, 0, 6, 6]], np.float32)],
        attrs=dict(pooled_size=(2, 2), spatial_scale=1.0),
        grad_args=[0]),
    "Correlation": dict(
        inputs=[_img((1, 2, 6, 6)), _img((1, 2, 6, 6), 1)],
        attrs=dict(kernel_size=1, max_displacement=1, stride1=1,
                   stride2=1, pad_size=1), tol=(8e-2, 1e-2)),
    "depth_to_space": dict(inputs=[_img((2, 8, 3, 3))],
                           attrs=dict(block_size=2)),
    "space_to_depth": dict(inputs=[_img((2, 2, 4, 4))],
                           attrs=dict(block_size=2)),
    # -- detection (assignment/NMS ops: forward-only by design) ---------------
    "MultiBoxPrior": dict(
        inputs=[_img((1, 3, 4, 4))],
        attrs=dict(sizes=(0.5,), ratios=(1.0,)), mode="fwd"),
    "MultiBoxTarget": dict(
        inputs=[_boxes(3), np.array([[[0, 0.1, 0.1, 0.4, 0.4]]],
                                    np.float32),
                _pos((1, 2, 3), 2)],
        mode="fwd"),
    "MultiBoxDetection": dict(
        inputs=[_pos((1, 2, 3), 0, **_DOM01),
                _signed((1, 12), 1),
                _boxes(3)],
        mode="fwd"),
    "Proposal": dict(
        inputs=[_pos((1, 2, 4, 4), 0, **_DOM01),
                _signed((1, 4, 4, 4), 1) * 0.1,
                np.array([[16.0, 16.0, 1.0]], np.float32)],
        attrs=dict(scales=(8,), ratios=(1.0,), feature_stride=4,
                   rpn_pre_nms_top_n=8, rpn_post_nms_top_n=4,
                   rpn_min_size=1),
        mode="fwd"),
    "MultiProposal": dict(
        inputs=[_pos((1, 2, 4, 4), 0, **_DOM01),
                _signed((1, 4, 4, 4), 1) * 0.1,
                np.array([[16.0, 16.0, 1.0]], np.float32)],
        attrs=dict(scales=(8,), ratios=(1.0,), feature_stride=4,
                   rpn_pre_nms_top_n=8, rpn_post_nms_top_n=4,
                   rpn_min_size=1),
        mode="fwd"),
    "box_nms": dict(
        inputs=[np.concatenate([_pos((1, 4, 1), 0, **_DOM01),
                                _boxes(4)[..., :4]], -1)],
        attrs=dict(overlap_thresh=0.5), mode="fwd"),
    "_contrib_box_iou": dict(
        inputs=[_boxes(3)[0], _boxes(4, 1)[0]], mode="fwd"),
    "DeformableConvolution": dict(
        inputs=[_img((1, 2, 6, 6)),
                _r(1).uniform(-0.3, 0.3, (1, 18, 6, 6)).astype(
                    np.float32),
                _signed((4, 2, 3, 3), 2)],
        attrs=dict(num_filter=4, kernel=(3, 3), pad=(1, 1),
                   no_bias=True), tol=(8e-2, 1e-2), grad_args=[0, 2]),
    "PSROIPooling": dict(
        inputs=[_img((1, 8, 6, 6)),
                np.array([[0, 0, 0, 4, 4]], np.float32)],
        attrs=dict(spatial_scale=1.0, output_dim=2, pooled_size=2),
        grad_args=[0]),
    "DeformablePSROIPooling": dict(
        inputs=[_img((1, 8, 6, 6)),
                np.array([[0, 0, 0, 4, 4]], np.float32)],
        attrs=dict(spatial_scale=1.0, output_dim=2, pooled_size=2,
                   group_size=2, no_trans=True),
        grad_args=[0]),
    # -- sequence/loss --------------------------------------------------------
    "CTCLoss": dict(
        inputs=[_signed((5, 2, 4), 0),
                np.array([[1, 2], [2, 3]], np.float32)],
        tol=(6e-2, 6e-3), grad_args=[0]),
    "Custom": dict(mode="skip", inputs=[],
                   reason="requires a registered python CustomOp type; "
                          "covered by tests/test_custom_op.py"),
    # -- linalg/indexing ------------------------------------------------------
    "dot": dict(inputs=[_signed((3, 4), 0), _signed((4, 2), 1)]),
    "batch_dot": dict(inputs=[_signed((2, 3, 4), 0),
                              _signed((2, 4, 2), 1)]),
    "batch_take": dict(inputs=[_signed((3, 4), 0),
                               np.array([0, 2, 1], np.int32)],
                       grad_args=[0]),
    "broadcast_to": dict(inputs=[_signed((1, 4), 0)],
                         attrs=dict(shape=(3, 4))),
    "_scatter_set_nd": dict(
        inputs=[_signed((2, 3), 0), np.array([[0, 1], [0, 2]], np.int32),
                _signed((2,), 1)],
        attrs=dict(shape=(2, 3)), mode="fwd"),
    "count_sketch": dict(
        inputs=[_signed((2, 6), 0), _pos((6,), 1) * 3,
                np.sign(_signed((6,), 2))],
        attrs=dict(out_dim=4), grad_args=[0]),
    "_image_to_tensor": dict(inputs=[_pos((8, 8, 3), 0)]),
    # -- scalar-attr arithmetic ----------------------------------------------
    "_div_scalar": dict(inputs=[_signed((3, 4), 0)],
                        attrs=dict(scalar=2.0)),
    "_mod_scalar": dict(inputs=[_pos((3, 4), 0)],
                        attrs=dict(scalar=2.0)),
    "_rpower_scalar": dict(inputs=[_pos((3, 4), 0)],
                           attrs=dict(scalar=2.0)),
    "_rdiv_scalar": dict(inputs=[_pos((3, 4), 0)],
                         attrs=dict(scalar=2.0)),
    "_power_scalar": dict(inputs=[_pos((3, 4), 0)],
                          attrs=dict(scalar=2.0)),
    "_rmod_scalar": dict(inputs=[_pos((3, 4), 0)],
                         attrs=dict(scalar=2.0)),
    "_hypot_scalar": dict(inputs=[_signed((3, 4), 0)],
                          attrs=dict(scalar=2.0)),
    "_maximum_scalar": dict(inputs=[_signed((3, 4), 0)],
                            attrs=dict(scalar=0.1)),
    "_minimum_scalar": dict(inputs=[_signed((3, 4), 0)],
                            attrs=dict(scalar=0.1)),
    # -- domain-restricted unaries -------------------------------------------
    "arccos": dict(inputs=[_signed((3, 4), 0) * 0.6]),
    "arcsin": dict(inputs=[_signed((3, 4), 0) * 0.6]),
    "arctanh": dict(inputs=[_signed((3, 4), 0) * 0.6]),
    "arccosh": dict(inputs=[_pos((3, 4), 0, lo=1.2, hi=2.5)]),
    "erfinv": dict(inputs=[_signed((3, 4), 0) * 0.6]),
    "broadcast_power": dict(inputs=[_pos((3, 4), 0),
                                    _pos((1, 4), 1)]),
    "_power": dict(inputs=[_pos((3, 4), 0), _pos((3, 4), 1)]),
    # -- positive-domain unaries ---------------------------------------------
    "log": dict(inputs=[_pos((3, 4), 0)]),
    "log2": dict(inputs=[_pos((3, 4), 0)]),
    "log10": dict(inputs=[_pos((3, 4), 0)]),
    "sqrt": dict(inputs=[_pos((3, 4), 0)]),
    "rsqrt": dict(inputs=[_pos((3, 4), 0)]),
    # -- linalg (square / SPD fixtures) ---------------------------------------
    "linalg_gemm": dict(inputs=[_signed((3, 4), 0), _signed((4, 2), 1),
                                _signed((3, 2), 2)]),
    "linalg_gemm2": dict(inputs=[_signed((3, 4), 0),
                                 _signed((4, 2), 1)]),
    "linalg_potrf": dict(
        inputs=[(lambda a: (a @ a.T + 3 * np.eye(3, dtype=np.float32)))
                (_signed((3, 3), 0))]),
    "linalg_potri": dict(
        inputs=[np.linalg.cholesky(
            (lambda a: a @ a.T + 3 * np.eye(3, dtype=np.float32))
            (_signed((3, 3), 0))).astype(np.float32)],
        tol=(8e-2, 1e-2)),
    "linalg_trmm": dict(
        inputs=[np.tril(_signed((3, 3), 0)).astype(np.float32),
                _signed((3, 4), 1)]),
    "linalg_trsm": dict(
        inputs=[(np.tril(_signed((3, 3), 0)) +
                 3 * np.eye(3)).astype(np.float32),
                _signed((3, 4), 1)], tol=(8e-2, 1e-2)),
    "linalg_sumlogdiag": dict(
        inputs=[(lambda a: a @ a.T + 3 * np.eye(3, dtype=np.float32))
                (_signed((3, 3), 0))]),
    "linalg_syevd": dict(
        inputs=[(lambda a: ((a + a.T) / 2).astype(np.float32))
                (_signed((3, 3), 0))], mode="fwd"),
    "ifft": dict(inputs=[_signed((2, 8), 0)],
                 attrs=dict(compute_size=128), mode="fwd"),
    "fft": dict(inputs=[_signed((2, 4), 0)],
                attrs=dict(compute_size=128), mode="fwd"),
    # -- indexing with integer operands ---------------------------------------
    "one_hot": dict(inputs=[np.array([0, 2, 1], np.int32)],
                    attrs=dict(depth=4), mode="fwd"),
    "pick": dict(inputs=[_signed((3, 4), 0),
                         np.array([0, 2, 1], np.float32)],
                 grad_args=[0]),
    "scatter_nd": dict(
        inputs=[_signed((2,), 0),
                np.array([[0, 1], [0, 2]], np.int32)],
        attrs=dict(shape=(2, 3)), grad_args=[0]),
    "_scatter_set_nd": dict(
        inputs=[_signed((2, 3), 0), _signed((2,), 1),
                np.array([[0, 1], [0, 2]], np.int32)],
        attrs=dict(shape=(2, 3)), mode="fwd"),
    "softmax_cross_entropy": dict(
        inputs=[_signed((3, 4), 0), np.array([0, 2, 1], np.float32)],
        grad_args=[0], mode="fwd"),
    # -- optimizer update kernels (multi-output state math; the fused
    #    training path uses parallel/functional_opt — forward-only here) ------
    "adam_update": dict(
        inputs=[_signed((3, 4), 0), _signed((3, 4), 1) * 0.1,
                _signed((3, 4), 2) * 0.01, _pos((3, 4), 3) * 0.01],
        attrs=dict(lr=0.1), mode="fwd"),
    "rmsprop_update": dict(
        inputs=[_signed((3, 4), 0), _signed((3, 4), 1) * 0.1,
                _pos((3, 4), 2) * 0.01],
        attrs=dict(lr=0.1), mode="fwd"),
    "rmspropalex_update": dict(
        inputs=[_signed((3, 4), 0), _signed((3, 4), 1) * 0.1,
                _pos((3, 4), 2) * 0.01, _signed((3, 4), 3) * 0.01,
                _signed((3, 4), 4) * 0.01],
        attrs=dict(lr=0.1), mode="fwd"),
    "ftml_update": dict(
        inputs=[_signed((3, 4), 0), _signed((3, 4), 1) * 0.1,
                _pos((3, 4), 2) * 0.01, _pos((3, 4), 3) * 0.01,
                _signed((3, 4), 4) * 0.01],
        attrs=dict(lr=0.1, t=1), mode="fwd"),
    "ftrl_update": dict(
        inputs=[_signed((3, 4), 0), _signed((3, 4), 1) * 0.1,
                _signed((3, 4), 2) * 0.01, _pos((3, 4), 3) * 0.01],
        attrs=dict(lr=0.1), mode="fwd"),
    # -- sampling-coordinate gradients: bilinear kernels are piecewise
    #    linear in the coordinates (kinks at integer grid points), so
    #    central differences straddle kinks; data gradients are checked,
    #    coordinate args get a smaller eps and looser tolerance ---------------
    "broadcast_mod": dict(inputs=[_pos((3, 4), 0) * 3,
                                  _pos((1, 4), 1)], grad_args=[0]),
    "_mod": dict(inputs=[_pos((3, 4), 0) * 3, _pos((3, 4), 1)],
                 grad_args=[0]),
    # -- host/cv/io ops -------------------------------------------------------
    "_cvimdecode": dict(mode="skip", inputs=[],
                        reason="host-side JPEG decode on raw bytes; "
                               "covered by tests/test_data_io.py"),
    "_cvimread": dict(mode="skip", inputs=[],
                      reason="host-side file read; covered by io tests"),
    "_cvimresize": dict(mode="skip", inputs=[],
                        reason="host-side cv resize on uint8 images; "
                               "covered by image pipeline tests"),
    "_cvcopyMakeBorder": dict(
        mode="skip", inputs=[],
        reason="host-side cv border op on uint8 images; covered by "
               "image pipeline tests"),
    # -- quantization (int8 dataplane; no gradients by design) ----------------
    "_contrib_quantized_conv": dict(
        mode="skip", inputs=[],
        reason="int8 dataplane op (no gradient by design); numerics "
               "covered by tests/test_contrib.py quantization cases"),
    "_contrib_quantized_fully_connected": dict(
        mode="skip", inputs=[],
        reason="int8 dataplane op; covered by quantization tests"),
    "_contrib_quantized_pooling": dict(
        mode="skip", inputs=[],
        reason="int8 dataplane op; covered by quantization tests"),
    # -- samplers (stochastic: forward-only with valid params) ----------------
    "_sample_gamma": dict(
        inputs=[_pos((3,), 0), _pos((3,), 1)], mode="fwd"),
    "_sample_unique_zipfian": dict(
        mode="skip", inputs=[],
        reason="host-side rejection sampler with data-dependent output "
               "count; covered by tests/test_op_surface.py"),
}
