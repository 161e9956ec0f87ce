"""ResNet-50 (He et al.), pre-activation bottleneck units as the repo's
``examples/image_classification/symbols/resnet.py`` builds them.

Two halves that share nothing but parameter *names* (MXNet's:
``stage1_unit1_conv1_weight``, ``bn0_gamma``, ``fc1_bias``, ...):

* the system under test, built through the public API (``build``);
* the plain reference (``reference_*``): ``jax.numpy`` / ``jax.lax`` in
  float32 at ``Precision.HIGHEST``, written from the paper's equations.
  It imports nothing of ``mxnet_tpu``. ``precision="fp8"`` is the
  control: the same mathematics with every tensor that the program
  holds in bfloat16 (weights as the convolutions read them, and what
  every convolution, BatchNorm-ReLU and residual sum puts out) rounded
  to the four significant bits of an 8-bit float (e4m3), the products
  summed in float32.

Departures from the published network are listed in ``resnet50.json``.
"""
from __future__ import annotations

import functools
import json
import os
import sys
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

import flops as _flops
from refutil import first_steps, held, seed_key

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BN_EPS = 2e-5


# ---------------------------------------------------------------------------
# structure, from the configuration's sizes
# ---------------------------------------------------------------------------
def _units(sizes):
    """``(name, in_ch, out_ch, stride, projects)`` of every unit."""
    out = []
    in_ch = sizes["filter_list"][0]
    for s, n in enumerate(sizes["units"]):
        out_ch = sizes["filter_list"][s + 1]
        for u in range(n):
            stride = 2 if (u == 0 and s > 0) else 1
            out.append((f"stage{s + 1}_unit{u + 1}", in_ch, out_ch, stride,
                        u == 0))
            in_ch = out_ch
    return out


def param_shapes(sizes):
    """Trainable parameters by name, in forward order."""
    f0 = sizes["filter_list"][0]
    shapes = {"bn_data_beta": (3,), "conv0_weight": (f0, 3, 7, 7),
              "bn0_gamma": (f0,), "bn0_beta": (f0,)}
    for name, in_ch, out_ch, _, projects in _units(sizes):
        mid = out_ch // 4
        shapes[f"{name}_bn1_gamma"] = (in_ch,)
        shapes[f"{name}_bn1_beta"] = (in_ch,)
        shapes[f"{name}_conv1_weight"] = (mid, in_ch, 1, 1)
        shapes[f"{name}_bn2_gamma"] = (mid,)
        shapes[f"{name}_bn2_beta"] = (mid,)
        shapes[f"{name}_conv2_weight"] = (mid, mid, 3, 3)
        shapes[f"{name}_bn3_gamma"] = (mid,)
        shapes[f"{name}_bn3_beta"] = (mid,)
        shapes[f"{name}_conv3_weight"] = (out_ch, mid, 1, 1)
        if projects:
            shapes[f"{name}_sc_weight"] = (out_ch, in_ch, 1, 1)
    last = sizes["filter_list"][-1]
    shapes["bn1_gamma"] = (last,)
    shapes["bn1_beta"] = (last,)
    shapes["fc1_weight"] = (sizes["classes"], last)
    shapes["fc1_bias"] = (sizes["classes"],)
    return shapes


def _bn_names(sizes):
    names = ["bn_data", "bn0"]
    for name, *_ in _units(sizes):
        names += [f"{name}_bn1", f"{name}_bn2", f"{name}_bn3"]
    return names + ["bn1"]


def _bn_channels(sizes, bn):
    if bn == "bn_data":
        return 3
    return param_shapes(sizes)[bn + "_beta"][0]


# ---------------------------------------------------------------------------
# seeded weights and batches
# ---------------------------------------------------------------------------
def make_weights(sizes, seed):
    """``(params, stats)`` from the seed, float32, made on the device in
    one jitted call. Convolutions and the dense layer are He-normal
    (the last convolution of a unit at a quarter of that, so that the
    sum over sixteen residual branches keeps its scale when BatchNorm
    normalizes by the seeded statistics); BatchNorm scales are drawn
    near 1 and shifts near 0, moving means near 0 and moving variances
    near 1, so that no two channels are alike."""
    shapes = param_shapes(sizes)
    bns = [(b, _bn_channels(sizes, b)) for b in _bn_names(sizes)]

    @jax.jit
    def make(key):
        params, stats = {}, {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("_weight"):
                fan_in = int(np.prod(shape[1:]))
                std = (2.0 / fan_in) ** 0.5
                if name.endswith("conv3_weight"):
                    std *= 0.25
                params[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith("_gamma"):
                params[name] = jax.random.uniform(k, shape, jnp.float32,
                                                  0.8, 1.2)
            else:       # BatchNorm shifts and the dense bias
                params[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
        for i, (bn, ch) in enumerate(bns):
            k = jax.random.fold_in(key, 100000 + i)
            k1, k2 = jax.random.split(k)
            stats[bn + "_moving_mean"] = 0.1 * jax.random.normal(
                k1, (ch,), jnp.float32)
            stats[bn + "_moving_var"] = jax.random.uniform(
                k2, (ch,), jnp.float32, 0.8, 1.2)
        return params, stats

    return make(seed_key(seed))


def make_rows(sizes, seed, n):
    """``n`` images (float32, NCHW, in [0, 1]) and labels, made on the
    device in one jitted call. Every image has a level, a contrast and a
    coarse pattern of its own under fine noise, as photographs do: rows
    of nothing but white noise look alike to every layer past the first,
    and BatchNorm, which subtracts the mean over the batch, would then
    be left with rounding alone."""
    img, coarse = sizes["image"], sizes["image"] // 16

    @jax.jit
    def make(key):
        k = jax.random.split(key, 5)
        level = jax.random.uniform(k[0], (n, 3, 1, 1), jnp.float32, 0.2, 0.8)
        contrast = jax.random.uniform(k[1], (n, 1, 1, 1), jnp.float32,
                                      0.05, 0.4)
        pattern = jax.random.normal(k[2], (n, 3, coarse, coarse))
        pattern = jnp.repeat(jnp.repeat(pattern, 16, axis=2), 16, axis=3)
        fine = 0.03 * jax.random.normal(k[3], (n, 3, img, img))
        x = jnp.clip(level + contrast * pattern + fine, 0.0, 1.0)
        y = jax.random.randint(k[4], (n,), 0, sizes["classes"])
        return x, y.astype(jnp.float32)

    return make(jax.random.fold_in(seed_key(seed), 11))


def make_batches(sizes, seed, n):
    """``n`` batches of ``(images, labels)``, on the device."""
    x, y = make_rows(sizes, seed, n * sizes["batch"])
    b = sizes["batch"]
    return [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b]) for i in range(n)]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _conv(x, w, stride, pad, precision):
    y = lax.conv_general_dilated(
        x, held(w, precision), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)
    return held(y, precision)


def _dense(x, w, b, precision):
    return jnp.dot(x, held(w, precision).T,
                   precision=lax.Precision.HIGHEST) + b


def _bn(x, gamma, beta, stats, bn, train):
    if train:
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.mean(jnp.square(x - mean[None, :, None, None]),
                       axis=(0, 2, 3))
    else:
        mean, var = stats[bn + "_moving_mean"], stats[bn + "_moving_var"]
    scale = lax.rsqrt(var + BN_EPS)
    if gamma is not None:
        scale = scale * gamma
    return (x - mean[None, :, None, None]) * scale[None, :, None, None] \
        + beta[None, :, None, None]


def _unit(x, p, stats, name, stride, projects, train, precision):
    def bn_relu(v, bn):
        return held(jax.nn.relu(_bn(v, p[f"{name}_{bn}_gamma"],
                                     p[f"{name}_{bn}_beta"], stats,
                                     f"{name}_{bn}", train)), precision)
    a1 = bn_relu(x, "bn1")
    y = _conv(a1, p[f"{name}_conv1_weight"], 1, 0, precision)
    y = _conv(bn_relu(y, "bn2"), p[f"{name}_conv2_weight"], stride, 1,
              precision)
    y = _conv(bn_relu(y, "bn3"), p[f"{name}_conv3_weight"], 1, 0, precision)
    shortcut = _conv(a1, p[f"{name}_sc_weight"], stride, 0, precision) \
        if projects else x
    return held(y + shortcut, precision)


def reference_logits(sizes, params, stats, x, train, precision="float32",
                     remat=False):
    """The network's logits for images ``x`` (NCHW, float32)."""
    x = held(_bn(x, None, params["bn_data_beta"], stats, "bn_data", train),
              precision)
    x = _conv(x, params["conv0_weight"], 2, 3, precision)
    x = held(jax.nn.relu(_bn(x, params["bn0_gamma"], params["bn0_beta"],
                              stats, "bn0", train)), precision)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for name, _, _, stride, projects in _units(sizes):
        unit = partial(_unit, name=name, stride=stride, projects=projects,
                       train=train, precision=precision)
        if remat:
            unit = jax.checkpoint(unit)
        x = unit(x, params, stats)
    x = held(jax.nn.relu(_bn(x, params["bn1_gamma"], params["bn1_beta"],
                              stats, "bn1", train)), precision)
    x = jnp.mean(x, axis=(2, 3))
    return _dense(x, params["fc1_weight"], params["fc1_bias"], precision)


@functools.lru_cache(maxsize=None)
def _forward_program(sizes_json, precision):
    sizes = json.loads(sizes_json)

    @jax.jit
    def fwd(params, stats, x):
        return jax.nn.softmax(reference_logits(
            sizes, params, stats, x, False, precision), axis=-1)

    return fwd


def reference_forward(sizes, weights, rows, precision="float32"):
    """Class probabilities of ``rows`` in inference mode, in blocks of at
    most 32 rows."""
    params, stats = weights
    fwd = _forward_program(json.dumps(sizes, sort_keys=True), precision)
    out = []
    for i in range(0, len(rows), 32):
        block = rows[i:i + 32]
        pad = 32 - len(block)
        if pad:
            block = np.concatenate([block, np.zeros((pad,) + block.shape[1:],
                                                    block.dtype)])
        out.append(np.asarray(fwd(params, stats,
                                  jnp.asarray(block)))[:32 - pad])
    return np.concatenate(out)


def _decays(name):
    """MXNet's rule: weight decay on weights and BatchNorm scales, none
    on shifts and biases."""
    return name.endswith("_weight") or name.endswith("_gamma")


@functools.lru_cache(maxsize=None)
def _train_program(sizes_json, opt_json, precision):
    """The jitted step, built once a process for one set of sizes: a
    second seed reuses the compiled program."""
    sizes, opt = json.loads(sizes_json), json.loads(opt_json)
    lr, mom, wd = opt["learning_rate"], opt["momentum"], opt["wd"]

    def loss_fn(p, stats, x, y):
        logits = reference_logits(sizes, p, stats, x, True, precision,
                                  remat=True)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, y.astype(jnp.int32)[:, None], axis=-1))

    @jax.jit
    def step(p, m, stats, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, stats, x, y)
        new_p, new_m = {}, {}
        for k in p:
            gk = g[k] + (wd * p[k] if _decays(k) else 0.0)
            new_m[k] = mom * m[k] - lr * gk
            new_p[k] = p[k] + new_m[k]
        return new_p, new_m, loss

    return step


def reference_train(sizes, opt, weights, batches, precision="float32"):
    """SGD with momentum from ``weights`` over ``batches``: what
    ``refutil.first_steps`` returns."""
    params, stats = weights
    step = _train_program(json.dumps(sizes, sort_keys=True),
                          json.dumps(opt, sort_keys=True), precision)
    return first_steps(lambda p, m, x, y: step(p, m, stats, x, y), params,
                       batches, opt["learning_rate"])


# ---------------------------------------------------------------------------
# the system under test, through the public API
# ---------------------------------------------------------------------------
def _symbol(sizes):
    sys.path.insert(0, os.path.join(_REPO, "examples",
                                    "image_classification"))
    from symbols import resnet as resnet_sym
    img = sizes["image"]
    return resnet_sym.resnet(
        units=list(sizes["units"]), num_stages=len(sizes["units"]),
        filter_list=list(sizes["filter_list"]),
        num_classes=sizes["classes"], image_shape=(3, img, img),
        bottle_neck=True, stem=sizes["stem"])


def _nd_weights(weights):
    import mxnet_tpu as mx
    params, stats = weights
    arg = {k: mx.nd.NDArray(v) for k, v in params.items()}
    # the symbol's first BatchNorm has fix_gamma=True: its scale is a
    # parameter the graph never reads
    arg["bn_data_gamma"] = mx.nd.ones((3,))
    aux = {k: mx.nd.NDArray(v) for k, v in stats.items()}
    return arg, aux


def build(cfg, sizes, role, weights):
    """The system under test. ``role`` ``fit``: a bound, initialized
    fused ``Module`` for ``Module.fit``; ``serve``: a ``Predictor`` made
    by ``Module.as_predictor``."""
    import mxnet_tpu as mx
    img = (3, sizes["image"], sizes["image"])
    arg, aux = _nd_weights(weights)
    if role == "fit":
        mod = mx.mod.Module(context=mx.current_context(),
                            symbol=_symbol(sizes), fused=True,
                            compute_dtype=cfg["compute_dtype"])
        mod.bind(data_shapes=[("data", (sizes["batch"],) + img)],
                 label_shapes=[("softmax_label", (sizes["batch"],))])
        mod.set_params(arg, aux)
        return mod
    if role == "serve":
        mod = mx.mod.Module(context=mx.current_context(),
                            symbol=_symbol(sizes))
        mod.bind(data_shapes=[("data", (max(sizes["buckets"]),) + img)],
                 for_training=False)
        mod.set_params(arg, aux)
        return mod.as_predictor(buckets=tuple(sizes["buckets"]),
                                compute_dtype=cfg["compute_dtype"])
    raise ValueError(f"resnet50 has no role {role!r}")


def read_params(system):
    """The module's parameters as float32 numpy arrays, by name."""
    arg, _ = system.get_params()
    return {k: v.asnumpy().astype(np.float32) for k, v in arg.items()}


def flops_per_item(sizes, mode):
    macs, _ = _flops.resnet_v2_forward_macs(
        sizes["units"], sizes["filter_list"], sizes["classes"],
        sizes["image"])
    return _flops.train_flops(macs) if mode == "train" \
        else _flops.forward_flops(macs)


def items_per_step(sizes):
    return sizes["batch"]
