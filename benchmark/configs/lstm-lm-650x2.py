"""The "medium" LSTM language model of Zaremba et al. 2014: embedding,
stacked LSTM layers, an untied output projection, softmax cross entropy
over the vocabulary, trained by truncated back-propagation through
``bptt`` steps from a zero state.

Two halves that share nothing but parameter names (``embed_weight``,
``l0_i2h_weight`` ... as gluon's ``rnn.LSTM`` names them,
``decoder_weight``, ``decoder_bias``) and the packing of the four gates
in one matrix, in the order input, forget, cell, output:

* the system under test (``build``): a gluon ``HybridBlock`` driven by
  ``parallel.TrainStep``, the path the repo's LM example takes;
* the plain reference (``reference_train``): ``jax.numpy`` in float32
  at ``Precision.HIGHEST``, a ``lax.scan`` over time written from the
  LSTM's equations. It imports nothing of ``mxnet_tpu``.
  ``precision="fp8"`` is the control: both operands of every matrix
  product rounded to the four significant bits of an 8-bit float (e4m3),
  the products summed in float32.

Departures from the paper are listed in ``lstm-lm-650x2.json``.
"""
from __future__ import annotations

import functools
import json

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

import flops as _flops
from refutil import first_steps, held, seed_key


def param_shapes(sizes):
    v, e, h = sizes["vocab"], sizes["embed"], sizes["hidden"]
    shapes = {"embed_weight": (v, e)}
    in_size = e
    for i in range(sizes["layers"]):
        shapes[f"l{i}_i2h_weight"] = (4 * h, in_size)
        shapes[f"l{i}_h2h_weight"] = (4 * h, h)
        shapes[f"l{i}_i2h_bias"] = (4 * h,)
        shapes[f"l{i}_h2h_bias"] = (4 * h,)
        in_size = h
    shapes["decoder_weight"] = (v, h)
    shapes["decoder_bias"] = (v,)
    return shapes


def make_weights(sizes, seed):
    """Every parameter uniform in (-0.05, 0.05), float32, made on the
    device in one jitted call."""
    shapes = param_shapes(sizes)

    @jax.jit
    def make(key):
        return {name: jax.random.uniform(jax.random.fold_in(key, i), shape,
                                         jnp.float32, -0.05, 0.05)
                for i, (name, shape) in enumerate(shapes.items())}

    return make(seed_key(seed))


def make_batches(sizes, seed, n):
    """``n`` batches of ``(tokens (batch, bptt), next tokens
    (batch * bptt,))``, int32, uniform over the vocabulary."""
    rng = np.random.default_rng([int(seed), 12])
    b, t, v = sizes["batch"], sizes["bptt"], sizes["vocab"]
    return [(rng.integers(0, v, (b, t)).astype(np.int32),
             rng.integers(0, v, (b * t,)).astype(np.int32))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _matmul(x, w, precision):
    """``x @ w.T``."""
    return jnp.dot(held(x, precision), held(w, precision).T,
                   precision=lax.Precision.HIGHEST)


def _lstm_layer(xs, wx, wh, bx, bh, precision):
    """``xs``: (time, batch, in). Zero initial state."""
    hidden = wh.shape[1]
    zeros = jnp.zeros((xs.shape[1], hidden), jnp.float32)
    # the input products of all steps at once; the recurrence in the scan
    gx = _matmul(xs.reshape(-1, xs.shape[-1]), wx, precision) \
        .reshape(xs.shape[0], xs.shape[1], -1) + bx + bh

    def step(carry, g_t):
        c, h = carry
        gates = g_t + _matmul(h, wh, precision)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (c, h), h

    _, hs = lax.scan(step, (zeros, zeros), gx)
    return hs


def reference_loss(sizes, p, tokens, targets, precision="float32"):
    x = jnp.take(p["embed_weight"], tokens, axis=0)      # (B, T, E)
    xs = jnp.swapaxes(x, 0, 1)                           # (T, B, E)
    for i in range(sizes["layers"]):
        xs = _lstm_layer(xs, p[f"l{i}_i2h_weight"], p[f"l{i}_h2h_weight"],
                         p[f"l{i}_i2h_bias"], p[f"l{i}_h2h_bias"], precision)
    h = jnp.swapaxes(xs, 0, 1).reshape(-1, xs.shape[-1])  # (B*T, H)
    logits = _matmul(h, p["decoder_weight"], precision) + p["decoder_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


@functools.lru_cache(maxsize=None)
def _train_program(sizes_json, opt_json, precision):
    """The jitted step, built once a process for one set of sizes: a
    second seed reuses the compiled program."""
    sizes, opt = json.loads(sizes_json), json.loads(opt_json)
    lr, mom, wd = opt["learning_rate"], opt["momentum"], opt["wd"]

    @jax.jit
    def step(p, m, x, y):
        loss, g = jax.value_and_grad(
            lambda q: reference_loss(sizes, q, x, y, precision))(p)
        new_m = {k: mom * m[k] - lr * (g[k] + wd * p[k]) for k in p}
        return {k: p[k] + new_m[k] for k in p}, new_m, loss

    return step


def reference_train(sizes, opt, weights, batches, precision="float32"):
    """SGD with momentum from ``weights`` over ``batches``: what
    ``refutil.first_steps`` returns."""
    step = _train_program(json.dumps(sizes, sort_keys=True),
                          json.dumps(opt, sort_keys=True), precision)
    return first_steps(step, weights, batches, opt["learning_rate"])


# ---------------------------------------------------------------------------
# the system under test, through the public API
# ---------------------------------------------------------------------------
def _net(sizes):
    from mxnet_tpu.gluon import HybridBlock, nn, rnn

    class LMModel(HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.embed = nn.Embedding(sizes["vocab"], sizes["embed"])
                self.lstm = rnn.LSTM(sizes["hidden"],
                                     num_layers=sizes["layers"],
                                     layout="NTC", dropout=sizes["dropout"],
                                     input_size=sizes["embed"])
                self.decoder = nn.Dense(sizes["vocab"], flatten=False,
                                        in_units=sizes["hidden"])

        def hybrid_forward(self, F, x):
            out = self.decoder(self.lstm(self.embed(x)))
            return out.reshape((-1, sizes["vocab"]))

    return LMModel()


def _leaf_of(param_name):
    """gluon's ``lmmodel0_embedding0_weight`` / ``..._lstm0_l0_i2h_weight``
    / ``..._dense0_bias`` -> the reference's leaf name."""
    for block, leaf in (("embedding", "embed_"), ("dense", "decoder_")):
        if f"_{block}" in param_name:
            return leaf + param_name.rsplit("_", 1)[1]
    return param_name.split("_lstm", 1)[1].split("_", 1)[1]


class _StepSystem:
    """``TrainStep`` with its net: what the step driver calls and what
    ``read_params`` reads."""

    def __init__(self, net, step):
        self.net, self.step = net, step

    def __call__(self, x, y):
        return self.step(x, y)


def build(cfg, sizes, role, weights):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import TrainStep
    if role != "step":
        raise ValueError(f"lstm-lm-650x2 has no role {role!r}")
    net = _net(sizes)
    net.initialize(mx.init.Zero())
    for name, p in net.collect_params().items():
        p.set_data(mx.nd.array(weights[_leaf_of(name)]))
    opt = dict(cfg["optimizer"])
    step = TrainStep(net, loss="softmax_ce", optimizer=opt.pop("name"),
                     optimizer_params=opt,
                     compute_dtype=cfg["compute_dtype"])
    return _StepSystem(net, step)


def read_params(system):
    system.step.sync_params()
    return {_leaf_of(name): p.data().asnumpy().astype(np.float32)
            for name, p in system.net.collect_params().items()}


def flops_per_item(sizes, mode):
    macs, _ = _flops.lstm_lm_forward_macs(
        sizes["vocab"], sizes["embed"], sizes["hidden"], sizes["layers"])
    return _flops.train_flops(macs) if mode == "train" \
        else _flops.forward_flops(macs)


def items_per_step(sizes):
    return sizes["batch"] * sizes["bptt"]
