"""Neural-network operators.

Reference surface: src/operator/nn/ (convolution, fully_connected, pooling,
batch_norm, layer_norm, softmax, dropout, activation, deconvolution, lrn) and
src/operator/{rnn,leaky_relu,instance_norm,softmax_output}.

TPU notes: data layout follows the reference's NCHW at the API, but conv and
pooling are expressed through ``lax.conv_general_dilated`` / ``lax.reduce_window``
with explicit dimension_numbers so XLA picks MXU-friendly internal layouts.
bf16 inputs hit the MXU directly. These replace the reference's cuDNN kernels
(src/operator/nn/cudnn/) — XLA *is* the kernel library.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register_op
from ..dtype import resolve_dtype


def _tup(v, n=None):
    if v is None:
        return None
    if isinstance(v, (int, float)):
        v = (int(v),) * (n or 1)
    return tuple(int(x) for x in v)


# ---------------------------------------------------------------------------
# FullyConnected (reference: src/operator/nn/fully_connected.cc:228-309)
# ---------------------------------------------------------------------------
@register_op("FullyConnected")
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True, **kw):
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    out = jnp.matmul(x, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution (reference: src/operator/nn/convolution.cc; cuDNN path
# src/operator/nn/cudnn/cudnn_convolution-inl.h — here: XLA HLO convolution)
# ---------------------------------------------------------------------------
def _conv_dnums(ndim):
    if ndim == 3:
        return ("NCH", "OIH", "NCH")
    if ndim == 4:
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


@register_op("Convolution")
def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                cudnn_tune=None, cudnn_off=False, workspace=None, layout=None, **kw):
    nd = data.ndim
    sdims = nd - 2
    stride = _tup(stride, sdims) or (1,) * sdims
    dilate = _tup(dilate, sdims) or (1,) * sdims
    pad = _tup(pad, sdims) or (0,) * sdims
    dn = jax.lax.conv_dimension_numbers(data.shape, weight.shape, _conv_dnums(nd))
    # bf16 inputs: the TPU MXU accumulates in f32 natively; an explicit
    # preferred_element_type breaks this JAX version's conv transpose rule
    out = jax.lax.conv_general_dilated(
        data, weight.astype(data.dtype), window_strides=stride,
        padding=[(p, p) for p in pad], lhs_dilation=None, rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=int(num_group))
    out = out.astype(data.dtype)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * sdims)
    return out


@register_op("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, target_shape=None, num_filter=None,
                  num_group=1, no_bias=True, workspace=None, cudnn_tune=None,
                  cudnn_off=False, layout=None, **kw):
    """Transposed convolution (reference: src/operator/nn/deconvolution.cc)."""
    nd = data.ndim
    sdims = nd - 2
    stride = _tup(stride, sdims) or (1,) * sdims
    dilate = _tup(dilate, sdims) or (1,) * sdims
    pad = _tup(pad, sdims) or (0,) * sdims
    adj = _tup(adj, sdims) or (0,) * sdims
    kernel = _tup(kernel, sdims) or weight.shape[2:]
    # gradient-of-conv formulation: lhs_dilation=stride, flipped spatial pad
    pads = []
    for k, p, a, d in zip(kernel, pad, adj, dilate):
        eff_k = (k - 1) * d + 1
        pads.append((eff_k - 1 - p, eff_k - 1 - p + a))
    # weight layout is (Cin, Cout/g, *k) in MXNet deconv; conv wants (O, I, *k)
    w = jnp.swapaxes(weight, 0, 1)
    w = jnp.flip(w, axis=tuple(range(2, nd)))
    if num_group > 1:
        # regroup: (g, Cout/g, Cin/g, *k) → (Cout, Cin/g, *k)
        cin = data.shape[1]
        wg = weight.reshape((num_group, cin // num_group) + weight.shape[1:])
        wg = jnp.swapaxes(wg, 1, 2)
        w = wg.reshape((-1, cin // num_group) + weight.shape[2:])
        w = jnp.flip(w, axis=tuple(range(2, nd)))
    dn = jax.lax.conv_dimension_numbers(data.shape, w.shape, _conv_dnums(nd))
    out = jax.lax.conv_general_dilated(
        data, w, window_strides=(1,) * sdims, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=int(num_group))
    out = out.astype(data.dtype)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * sdims)
    return out


# ---------------------------------------------------------------------------
# Pooling (reference: src/operator/nn/pooling.cc)
# ---------------------------------------------------------------------------
@register_op("conv_s2d_stem", aliases=["_contrib_conv_s2d_stem"])
def conv_s2d_stem(data, weight, **kw):
    """Mathematically exact space-to-depth rewrite of the 7x7/s2/pad3
    ImageNet stem conv: block-2 space-to-depth on the input, the SAME
    (O,C,7,7) weights front-padded to 8x8 and folded to (O,C*4,4,4), then
    a stride-1 conv with block-space pads (2,1). Identical output to
    Convolution(kernel=7, stride=2, pad=3) for even H,W — checkpoint
    compatible both directions (derivation: output pixel i reads
    x[2i-3..2i+3]; splitting x into even/odd phases gives 4 block taps per
    phase with the tap table w8[2a'+p] for the front-padded kernel).

    Why: the MXU contracts over C*kh*kw; with C=3 the standard stem
    wastes most of the 128-deep contraction lanes, and the folded form
    quadruples the input-channel depth (the MLPerf ResNet TPU technique).
    """
    # the rewrite below is derived specifically for kernel 7x7, stride 2,
    # pad 3, no dilation/groups, and needs even H,W — reject anything
    # else loudly instead of silently computing the wrong convolution
    def _is(name, want):
        v = kw.get(name)
        return v is None or tuple(v) == want
    if not (_is("kernel", (7, 7)) and _is("stride", (2, 2))
            and _is("pad", (3, 3)) and _is("dilate", (1, 1))
            and int(kw.get("num_group", 1)) == 1):
        raise ValueError(
            "conv_s2d_stem implements exactly Convolution(kernel=(7,7), "
            f"stride=(2,2), pad=(3,3), no dilation/groups); got attrs "
            f"{ {k: v for k, v in kw.items() if k in ('kernel', 'stride', 'pad', 'dilate', 'num_group')} }. "
            "Use the plain Convolution op for other geometries.")
    B, C, H, W = data.shape
    if H % 2 or W % 2:
        raise ValueError(
            f"conv_s2d_stem needs even spatial dims (space-to-depth "
            f"block 2); got input {H}x{W}")
    O = weight.shape[0]
    xs = data.reshape(B, C, H // 2, 2, W // 2, 2).transpose(
        0, 1, 3, 5, 2, 4).reshape(B, C * 4, H // 2, W // 2)
    w8 = jnp.pad(weight.astype(data.dtype),
                 ((0, 0), (0, 0), (1, 0), (1, 0)))
    wf = w8.reshape(O, C, 4, 2, 4, 2).transpose(
        0, 1, 3, 5, 2, 4).reshape(O, C * 4, 4, 4)
    return jax.lax.conv_general_dilated(
        xs, wf, (1, 1), ((2, 1), (2, 1)),
        dimension_numbers=("NCHW", "OIHW", "NCHW")).astype(data.dtype)


@register_op("Pooling")
def pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", cudnn_off=False,
            count_include_pad=True, **kw):
    nd = data.ndim
    sdims = nd - 2
    if global_pool:
        ax = tuple(range(2, nd))
        if pool_type == "max":
            return jnp.max(data, axis=ax, keepdims=True)
        if pool_type == "sum":
            return jnp.sum(data, axis=ax, keepdims=True)
        return jnp.mean(data, axis=ax, keepdims=True)
    kernel = _tup(kernel, sdims)
    stride = _tup(stride, sdims) or (1,) * sdims
    pad = _tup(pad, sdims) or (0,) * sdims
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad on the high side so ceil((x+2p-k)/s)+1 windows fit
        pads = [(0, 0), (0, 0)]
        for i in range(sdims):
            x = data.shape[2 + i]
            out_sz = int(np.ceil((x + 2 * pad[i] - kernel[i]) / stride[i])) + 1
            need = (out_sz - 1) * stride[i] + kernel[i] - x - pad[i]
            pads.append((pad[i], max(need, pad[i])))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]

    # NOTE: init values must be python scalars — a traced/array init prevents
    # JAX from selecting the differentiable reduce_window_{max,sum} primitives
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else int(jnp.iinfo(data.dtype).min)
        return jax.lax.reduce_window(data, init,
                                     jax.lax.max, window, strides, pads)
    zero = 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0
    summed = jax.lax.reduce_window(data, zero,
                                   jax.lax.add, window, strides, pads)
    if pool_type == "sum":
        return summed
    if count_include_pad:
        denom = np.prod(kernel)
        return summed / jnp.asarray(denom, data.dtype)
    ones = jnp.ones(data.shape, data.dtype)
    counts = jax.lax.reduce_window(ones, jnp.asarray(0, data.dtype),
                                   jax.lax.add, window, strides, pads)
    return summed / counts


# ---------------------------------------------------------------------------
# Activations (reference: src/operator/nn/activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------
@register_op("Activation")
def activation(data, act_type="relu", **kw):
    fns = {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
           "softrelu": jax.nn.softplus, "softsign": jax.nn.soft_sign}
    return fns[act_type](data)


@register_op("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, **kw):
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data > 0, data, alpha * jnp.expm1(data))
    if act_type == "rrelu":
        # inference behavior: use mean slope (reference: leaky_relu-inl.h)
        s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, s * data)
    raise ValueError(f"unknown act_type {act_type}")


# ---------------------------------------------------------------------------
# softmax family (reference: src/operator/nn/softmax.cc, softmax_output.cc,
# loss_binary_op.cc)
# ---------------------------------------------------------------------------
@register_op("softmax")
def softmax(data, axis=-1, temperature=None, length=None, **kw):
    x = data / temperature if temperature else data
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax")
def log_softmax(data, axis=-1, temperature=None, **kw):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register_op("SoftmaxActivation")
def softmax_activation(data, mode="instance", **kw):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register_op("softmax_cross_entropy")
def softmax_cross_entropy(data, label, **kw):
    logp = jax.nn.log_softmax(data, axis=-1)
    nll = -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return jnp.sum(nll)


@register_op("SoftmaxOutput", aliases=["Softmax"])
def softmax_output(data, label=None, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0, **kw):
    """Forward = softmax; the custom backward (∂=p-y) is realized by pairing
    with the cross-entropy loss at the framework level (reference:
    src/operator/softmax_output.cc). Module's fit wires this through
    ``_softmax_output_loss`` below."""
    if multi_output:
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data, axis=-1)


def softmax_output_loss(data, label, grad_scale=1.0, ignore_label=-1.0,
                        use_ignore=False, multi_output=False,
                        normalization="null", smooth_alpha=0.0, **kw):
    """Cross-entropy whose gradient wrt data equals SoftmaxOutput's backward."""
    axis = 1 if multi_output else -1
    logp = jax.nn.log_softmax(data, axis=axis)
    lab = label.astype(jnp.int32)
    nll = -jnp.take_along_axis(logp, jnp.expand_dims(lab, axis), axis=axis)
    nll = jnp.squeeze(nll, axis)
    if use_ignore:
        mask = (lab != int(ignore_label)).astype(data.dtype)
        nll = nll * mask
        if normalization == "valid":
            return grad_scale * jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    # reference backward semantics (softmax_output.cc): "null" leaves each
    # sample's (p - y) unscaled → implicit loss is the SUM of per-sample CE
    # (the optimizer's rescale_grad=1/batch does the averaging); "batch"
    # divides by batch size.
    if normalization == "batch":
        return grad_scale * jnp.mean(nll)
    return grad_scale * jnp.sum(nll)


# ---------------------------------------------------------------------------
# Normalization (reference: src/operator/nn/batch_norm.cc, layer_norm.cc,
# src/operator/instance_norm.cc, lrn.cc)
# ---------------------------------------------------------------------------
@register_op("BatchNorm", num_outputs=3)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False, training=False, **kw):
    """Returns (out, batch_mean, batch_var). Running-stat update is done by the
    caller (gluon layer / executor) — functional style; the reference mutates
    aux states in-place (batch_norm.cc)."""
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    if training and not use_global_stats:
        mean = jnp.mean(data, axis=red)
        var = jnp.var(data, axis=red)
    else:
        mean, var = moving_mean, moving_var
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = jax.lax.rsqrt(var + eps)
    out = (data - mean.reshape(bshape)) * (g * inv).reshape(bshape) + beta.reshape(bshape)
    return out.astype(data.dtype), mean, var


@register_op("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **kw):
    ax = axis % data.ndim
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    out = (data - mean) * inv * gamma.reshape(bshape) + beta.reshape(bshape)
    if output_mean_var:
        return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)
    return out


# ---------------------------------------------------------------------------
# CausalSelfAttention — no reference analog (MXNet ~1.1 predates attention);
# the single-device graduation of parallel/ring.py's blockwise math: one
# resident block, no ring hop, same stable max/denominator recurrence and
# the same -1e30 additive-mask convention (masked logits underflow to an
# exact 0.0 contribution, so padding/stale rows can never perturb outputs).
# ---------------------------------------------------------------------------
@register_op("CausalSelfAttention")
def causal_self_attention(data, num_heads=1, scale=None, **kw):
    """Causal multi-head self-attention over packed QKV.

    data: (B, S, 3*num_heads*head_dim) — the fused QKV projection
    (FullyConnected with flatten=False). Returns (B, S, num_heads*head_dim);
    position i attends to positions <= i.
    """
    from ..parallel.ring import local_attention_block, _NEG
    b, s, three_hd = data.shape
    h = int(num_heads)
    d = three_hd // (3 * h)
    qkv = data.reshape(b, s, 3, h, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    pos = jnp.arange(s)
    bias = jnp.where(pos[:, None] >= pos[None, :], 0.0, _NEG)[None, None]
    o, _, l = local_attention_block(q, k, v, bias=bias, scale=scale)
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.reshape(b, s, h * d).astype(data.dtype)


@register_op("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3, **kw):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * jax.lax.rsqrt(var + eps) * gamma.reshape(bshape) + \
        beta.reshape(bshape)


@register_op("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **kw):
    """Local response norm across channels (reference: src/operator/nn/lrn.cc)."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (data.ndim - 2))
    # NOTE: init must be a python scalar — an array init stops JAX from
    # selecting the differentiable reduce_window_sum primitive, and the
    # generic reduce_window has no reverse-mode rule (found by the
    # registry gradient sweep, tests/test_op_gradients.py)
    window = jax.lax.reduce_window(
        padded, 0.0, jax.lax.add,
        (1, nsize) + (1,) * (data.ndim - 2), (1,) * data.ndim,
        [(0, 0)] * data.ndim)
    return data / jnp.power(knorm + alpha / nsize * window, beta)


# ---------------------------------------------------------------------------
# Dropout (reference: src/operator/nn/dropout.cc) — needs an RNG key; eager
# mode uses the global random state, traced mode must pass `key`.
# ---------------------------------------------------------------------------
@register_op("Dropout")
def dropout(data, p=0.5, mode="training", axes=None, key=None, training=None, **kw):
    from ..random import next_key
    is_training = training if training is not None else True
    if not is_training and mode != "always":
        return data
    if p <= 0.0:
        return data
    if key is None:
        key = next_key()
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(data.dtype) / keep
    return data * mask


# ---------------------------------------------------------------------------
# RNN — fused multi-layer RNN/LSTM/GRU via lax.scan
# (reference: src/operator/rnn-inl.h + cudnn_rnn-inl.h; the cuDNN fused kernel
# maps, a layer and direction, to one product over the whole sequence, a
# scan whose body is the one product that needs the step before it, and in
# the backward pass one whole-sequence product before its scan and three
# after it)
# ---------------------------------------------------------------------------
def _rnn_gate_count(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def rnn_unpack_params(params, mode, num_layers, input_size, state_size,
                      bidirectional=False):
    """Split the reference's flat cuDNN-layout parameter vector into per-layer
    (Wx, Wh, bx, bh) (reference layout: rnn-inl.h GetRnnParamSize)."""
    ngates = _rnn_gate_count(mode)
    dirs = 2 if bidirectional else 1
    layers = []
    off = 0
    for layer in range(num_layers):
        for d in range(dirs):
            isz = input_size if layer == 0 else state_size * dirs
            wx_n = ngates * state_size * isz
            wh_n = ngates * state_size * state_size
            wx = params[off:off + wx_n].reshape(ngates * state_size, isz); off += wx_n
            wh = params[off:off + wh_n].reshape(ngates * state_size, state_size); off += wh_n
            layers.append([wx, wh, None, None])
    for layer in range(num_layers):
        for d in range(dirs):
            b_n = ngates * state_size
            layers[layer * dirs + d][2] = params[off:off + b_n]; off += b_n
            layers[layer * dirs + d][3] = params[off:off + b_n]; off += b_n
    return layers


@register_op("_rnn_zero_state")
def rnn_zero_state(data, state_size=0, num=0, batch_axis=0, **kw):
    """Zero initial RNN state derived from a data symbol's batch dim —
    lets cell.unroll(begin_state=None) work at graph-build time without
    a concrete batch size (the reference creates shape-(0,...) zeros and
    lets InferShape fill them in; here shapes flow through eval_shape).
    data (T,N,C) + num>0 -> zeros (num, N, state_size); otherwise
    zeros (data.shape[batch_axis], state_size) — the caller passes the
    layout's batch axis (NTC->0, TNC->1)."""
    n = data.shape[1] if num else data.shape[int(batch_axis)]
    shape = (num, n, state_size) if num else (n, state_size)
    return jnp.zeros(shape, data.dtype)


def rnn_param_size(mode, num_layers, input_size, state_size, bidirectional=False):
    ngates = _rnn_gate_count(mode)
    dirs = 2 if bidirectional else 1
    total = 0
    for layer in range(num_layers):
        isz = input_size if layer == 0 else state_size * dirs
        total += dirs * ngates * state_size * (isz + state_size + 2)
    return total


# The elementwise part of one time step, ``cell(carry, *pre) -> carry`` with
# the hidden state last in the carry: all that the four modes differ in.
# ``pre`` is what the cell reads of the input's and the state's
# pre-activations ``gx_t`` / ``gh_t`` (each with its bias, (N, G*H)): their
# sum, or for the GRU, whose reset gate scales a part of ``gh_t``, the two.
def _lstm_cell(carry, gates):
    c, _ = carry
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return c_new, jax.nn.sigmoid(o) * jnp.tanh(c_new)


def _gru_cell(carry, gx, gh):
    (hprev,) = carry
    rx, zx, nx = jnp.split(gx, 3, axis=-1)
    rh, zh, nh = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    return ((1 - z) * n + z * hprev,)


def _rnn_tanh_cell(carry, gates):
    return (jnp.tanh(gates),)


def _rnn_relu_cell(carry, gates):
    return (jax.nn.relu(gates),)


_RNN_CELLS = {"lstm": _lstm_cell, "gru": _gru_cell,
              "rnn_tanh": _rnn_tanh_cell, "rnn_relu": _rnn_relu_cell}


def _cell_reads(cell, gx, gh):
    """``pre`` for ``cell``, of one step or of all steps at once."""
    return (gx, gh) if cell is _gru_cell else (gx + gh,)


def _recurrence_step(cell, carry, *pre):
    """One step's new carry, each state in the dtype it came in."""
    return tuple(new.astype(old.dtype)
                 for new, old in zip(cell(carry, *pre), carry))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _recurrence(cell, reverse, gx, wh, bh, init):
    """What of a layer is sequential: from the input's pre-activations
    ``gx`` (T, N, G*H) and the entering carry, ``(final carry, ys)``. One
    for the four modes and both directions. The scan's body holds the one
    product that needs the step before it, ``h @ wh.T`` forward and
    ``d gh_t @ wh`` backward; what the backward pass needs of every step
    at once is a product over the whole sequence outside the scan: the
    states' pre-activations again before it (the forward scan stacks each
    step's entering carry and nothing as wide as the gates), ``wh``'s and
    ``bh``'s gradients after it, not sums the loop carries. A
    ``custom_vjp``: it has no forward-mode derivative."""
    return _recurrence_fwd(cell, reverse, gx, wh, bh, init)[0]


def _recurrence_fwd(cell, reverse, gx, wh, bh, init):
    def body(carry, gx_t):
        gh_t = carry[-1] @ wh.T + bh
        new = _recurrence_step(cell, carry, *_cell_reads(cell, gx_t, gh_t))
        return new, (new[-1], carry)

    with jax.named_scope("mx_rnn_scan"):
        carry, (ys, entering) = jax.lax.scan(body, init, gx, reverse=reverse)
    return (carry, ys), (gx, wh, bh, entering)


def _recurrence_bwd(cell, reverse, residuals, cotangents):
    gx, wh, bh, entering = residuals
    d_final, d_ys = cotangents

    def body(d_carry, step):
        carry, pre_t, d_y = step
        _, pull = jax.vjp(partial(_recurrence_step, cell), carry, *pre_t)
        d_prev, *d_pre_t = pull(d_carry[:-1] + (d_carry[-1] + d_y,))
        d_h = (d_prev[-1] + d_pre_t[-1] @ wh).astype(d_prev[-1].dtype)
        return d_prev[:-1] + (d_h,), tuple(d_pre_t)

    with jax.named_scope("mx_rnn_scan"):
        gh = jnp.einsum("tnh,gh->tng", entering[-1], wh) + bh
        d_init, d_pre = jax.lax.scan(
            body, d_final, (entering, _cell_reads(cell, gx, gh), d_ys),
            reverse=not reverse)
        # of gx and gh: the sum's cotangent is that of both
        d_gx, d_gh = d_pre[0].astype(gx.dtype), d_pre[-1]
        d_wh = jnp.einsum(
            "tng,tnh->gh", d_gh, entering[-1],
            preferred_element_type=jnp.promote_types(gh.dtype, jnp.float32))
        d_bh = jnp.sum(d_gh, axis=(0, 1))
    return d_gx, d_wh.astype(wh.dtype), d_bh.astype(bh.dtype), d_init


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def _run_layer(xs, mode, wx, wh, bx, bh, h0, c0=None, reverse=False):
    """One layer in one direction over ``xs`` (T, N, C): ``(carry, ys)``.
    The input's pre-activations of all T steps are one product before the
    scan (so are ``wx``'s, ``bx``'s and ``xs``'s gradients, by autodiff of
    it); the scan is ``_recurrence``."""
    with jax.named_scope("mx_rnn_input"):
        gx = jnp.einsum("tnc,gc->tng", xs, wx) + bx
    init = (c0, h0) if mode == "lstm" else (h0,)
    return _recurrence(_RNN_CELLS[mode], reverse, gx, wh, bh, init)


@register_op("RNN", num_outputs=-1, names_its_parts=True)
def rnn(data, parameters, state, state_cell=None, state_size=None,
        num_layers=1, mode="lstm", bidirectional=False, p=0.0,
        state_outputs=False, lstm_state_clip_min=None, lstm_state_clip_max=None,
        lstm_state_clip_nan=False, training=None, key=None, **kw):
    """Fused RNN (reference: src/operator/rnn-inl.h, data layout (T, N, C);
    state (L*dirs, N, H)), the TPU-native replacement of the cuDNN fused RNN
    kernel. A layer and direction runs as ``_run_layer``: forward, the
    input's product for all T steps at once, then a ``lax.scan`` whose body
    holds one product, ``h @ Wh.T``; backward, the states' pre-activations
    of all steps again as one product, a scan whose body holds one product,
    ``d gh_t @ Wh``, and after it three products over the whole sequence
    (the gradients of ``Wh``, ``Wx`` and the input). The scan is a
    ``jax.custom_vjp``, so the operator has reverse-mode derivatives and no
    forward-mode one (``jax.jvp`` / ``jax.jacfwd`` raise). ``p`` applies
    dropout between stacked layers in training mode (rnn-inl.h inter-layer
    dropout)."""
    T, N, C = data.shape
    dirs = 2 if bidirectional else 1
    layers = rnn_unpack_params(parameters, mode, num_layers, C, state_size,
                               bidirectional)
    apply_dropout = p and p > 0.0 and (training is None or training)
    if apply_dropout and key is None:
        from ..random import next_key
        key = next_key()
    xs = data
    h_out, c_out = [], []
    for layer in range(num_layers):
        outs = []
        for d in range(dirs):
            li = layer * dirs + d
            wx, wh, bx, bh = layers[li]
            h0 = state[li]
            c0 = state_cell[li] if mode == "lstm" else None
            carry, ys = _run_layer(xs, mode, wx, wh, bx, bh, h0, c0,
                                   reverse=(d == 1))
            outs.append(ys)
            if mode == "lstm":
                c_out.append(carry[0]); h_out.append(carry[1])
            else:
                h_out.append(carry[0])
        xs = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if apply_dropout and layer < num_layers - 1:
            sub = jax.random.fold_in(key, layer)
            keep = 1.0 - p
            mask = jax.random.bernoulli(sub, keep, xs.shape)
            xs = xs * mask.astype(xs.dtype) / keep
    out = xs
    if state_outputs:
        hs = jnp.stack(h_out)
        if mode == "lstm":
            return out, hs, jnp.stack(c_out)
        return out, hs
    return out


# ---------------------------------------------------------------------------
# misc vision ops
# ---------------------------------------------------------------------------
@register_op("UpSampling")
def upsampling(*args, scale=1, sample_type="nearest", num_args=1, num_filter=0,
               multi_input_mode="concat", workspace=None, **kw):
    data = args[0]
    if sample_type == "nearest":
        if num_args > 1 and multi_input_mode == "concat":
            outs = [jnp.repeat(jnp.repeat(a, scale, axis=2), scale, axis=3)
                    for a in args]
            return jnp.concatenate(outs, axis=1)
        return jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    # bilinear = deconvolution with bilinear kernel (args[1])
    weight = args[1]
    pad = scale // 2
    return deconvolution(data, weight, None, kernel=(scale * 2 - scale % 2,) * 2,
                         stride=(scale,) * 2, pad=(pad,) * 2,
                         num_filter=data.shape[1], num_group=data.shape[1],
                         no_bias=True)


@register_op("ROIPooling")
def roi_pooling(data, rois, pooled_size=(1, 1), spatial_scale=1.0, **kw):
    """Reference: src/operator/roi_pooling.cc. Vectorized over rois."""
    ph, pw = _tup(pooled_size, 2)
    N, C, H, W = data.shape

    def one_roi(roi):
        batch_idx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1)
        rw = jnp.maximum(x2 - x1 + 1, 1)
        img = data[batch_idx]  # (C,H,W)
        ys = jnp.arange(H)
        xs = jnp.arange(W)

        def pool_cell(iy, ix):
            hstart = y1 + (iy * rh) // ph
            hend = y1 + ((iy + 1) * rh + ph - 1) // ph
            wstart = x1 + (ix * rw) // pw
            wend = x1 + ((ix + 1) * rw + pw - 1) // pw
            mask = ((ys[:, None] >= hstart) & (ys[:, None] < hend) &
                    (xs[None, :] >= wstart) & (xs[None, :] < wend))
            masked = jnp.where(mask[None], img, -jnp.inf)
            val = jnp.max(masked, axis=(1, 2))
            return jnp.where(jnp.isfinite(val), val, 0.0)

        grid = jax.vmap(lambda iy: jax.vmap(lambda ix: pool_cell(iy, ix))(
            jnp.arange(pw)))(jnp.arange(ph))  # (ph, pw, C)
        return jnp.transpose(grid, (2, 0, 1))

    return jax.vmap(one_roi)(rois)


@register_op("GridGenerator", no_grad=True)
def grid_generator(data, transform_type="affine", target_shape=(0, 0), **kw):
    h, w = target_shape
    ys = jnp.linspace(-1, 1, h)
    xs = jnp.linspace(-1, 1, w)
    gx, gy = jnp.meshgrid(xs, ys)
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()])  # (3, h*w)
    if transform_type == "affine":
        theta = data.reshape(-1, 2, 3)
        out = jnp.einsum("nij,jk->nik", theta, base)
        return out.reshape(-1, 2, h, w)
    return data + jnp.stack([gx, gy])[None]


@register_op("BilinearSampler")
def bilinear_sampler(data, grid, **kw):
    """Reference: src/operator/bilinear_sampler.cc. grid in [-1,1], (N,2,H,W)."""
    N, C, H, W = data.shape
    _, _, outH, outW = grid.shape
    gx = (grid[:, 0] + 1) * (W - 1) / 2
    gy = (grid[:, 1] + 1) * (H - 1) / 2

    x0 = jnp.floor(gx); y0 = jnp.floor(gy)
    x1, y1 = x0 + 1, y0 + 1
    wx1 = gx - x0; wy1 = gy - y0
    wx0 = 1 - wx1; wy0 = 1 - wy1

    def sample(img, xi, yi):
        xi_c = jnp.clip(xi.astype(jnp.int32), 0, W - 1)
        yi_c = jnp.clip(yi.astype(jnp.int32), 0, H - 1)
        valid = ((xi >= 0) & (xi <= W - 1) & (yi >= 0) & (yi <= H - 1))
        vals = img[:, yi_c, xi_c]  # (C, outH, outW)
        return vals * valid[None]

    def per_image(img, x0i, y0i, x1i, y1i, w00, w01, w10, w11):
        return (sample(img, x0i, y0i) * w00[None] + sample(img, x1i, y0i) * w01[None]
                + sample(img, x0i, y1i) * w10[None] + sample(img, x1i, y1i) * w11[None])

    return jax.vmap(per_image)(data, x0, y0, x1, y1,
                               wy0 * wx0, wy0 * wx1, wy1 * wx0, wy1 * wx1)


@register_op("SpatialTransformer")
def spatial_transformer(data, loc, target_shape=(0, 0), transform_type="affine",
                        sampler_type="bilinear", cudnn_off=False, **kw):
    grid = grid_generator(loc, transform_type, target_shape)
    return bilinear_sampler(data, grid)


# legacy v0.x interface names (reference: MXNET_REGISTER_OP_PROPERTY
# batch_norm_v1 src/operator/batch_norm_v1.cc, convolution_v1, pooling_v1 —
# same math behind the older Operator interface; here plain aliases)
from .registry import alias as _alias
_alias("BatchNorm", "BatchNorm_v1")
_alias("Convolution", "Convolution_v1")
_alias("Pooling", "Pooling_v1")
