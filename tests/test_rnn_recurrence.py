"""The ``RNN`` operator against a plain reference, and what its scans hold.

The reference below is the formulation the operator had before its scan
was cut down to what is sequential: one ``lax.scan`` a layer and direction
whose body computes both products, differentiated by plain autodiff. It
imports nothing of ``ops/nn.py``.
"""
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.nn import rnn, rnn_param_size

import numerics

GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}
N, C, H = 3, 5, 4


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _ref_unpack(params, mode, layers, dirs):
    """The flat cuDNN-layout vector: every (Wx, Wh) by layer and direction,
    then every (bx, bh) in the same order."""
    g = GATES[mode] * H
    out, off = [], 0
    for layer in range(layers):
        for _ in range(dirs):
            isz = C if layer == 0 else H * dirs
            wx = params[off:off + g * isz].reshape(g, isz); off += g * isz
            wh = params[off:off + g * H].reshape(g, H); off += g * H
            out.append([wx, wh])
    for w in out:
        w.append(params[off:off + g]); off += g
        w.append(params[off:off + g]); off += g
    assert off == params.shape[0]
    return out


def _ref_step(mode, carry, x_t, wx, wh, bx, bh):
    h = carry[-1]
    if mode == "lstm":
        i, f, g, o = jnp.split(x_t @ wx.T + h @ wh.T + bx + bh, 4, axis=-1)
        c = jax.nn.sigmoid(f) * carry[0] + jax.nn.sigmoid(i) * jnp.tanh(g)
        return c, jax.nn.sigmoid(o) * jnp.tanh(c)
    if mode == "gru":
        rx, zx, nx = jnp.split(x_t @ wx.T + bx, 3, axis=-1)
        rh, zh, nh = jnp.split(h @ wh.T + bh, 3, axis=-1)
        r, z = jax.nn.sigmoid(rx + rh), jax.nn.sigmoid(zx + zh)
        return ((1 - z) * jnp.tanh(nx + r * nh) + z * h,)
    act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu
    return (act(x_t @ wx.T + h @ wh.T + bx + bh),)


def _ref_rnn(data, params, state, state_cell, mode, layers, dirs):
    weights = _ref_unpack(params, mode, layers, dirs)
    xs, h_out, c_out = data, [], []
    for layer in range(layers):
        outs = []
        for d in range(dirs):
            li = layer * dirs + d
            init = (state_cell[li], state[li]) if mode == "lstm" \
                else (state[li],)

            def body(carry, x_t, w=weights[li]):
                new = _ref_step(mode, carry, x_t, *w)
                return new, new[-1]

            carry, ys = jax.lax.scan(body, init, xs, reverse=(d == 1))
            outs.append(ys)
            h_out.append(carry[-1])
            if mode == "lstm":
                c_out.append(carry[0])
        xs = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
    res = (xs, jnp.stack(h_out))
    return res + (jnp.stack(c_out),) if mode == "lstm" else res


def _inputs(mode, layers, dirs, T, seed=0):
    rng = np.random.RandomState(seed)
    n = rnn_param_size(mode, layers, C, H, dirs == 2)
    args = [rng.randn(T, N, C), 0.4 * rng.randn(n),
            rng.randn(layers * dirs, N, H)]
    if mode == "lstm":
        args.append(rng.randn(layers * dirs, N, H))
    return [jnp.asarray(a, jnp.float32) for a in args]


def _op(mode, layers, dirs):
    def run(data, params, state, state_cell=None):
        return rnn(data, params, state, state_cell, state_size=H,
                   num_layers=layers, mode=mode, bidirectional=dirs == 2,
                   state_outputs=True)
    return run


def _ref(mode, layers, dirs):
    def run(data, params, state, state_cell=None):
        return _ref_rnn(data, params, state, state_cell, mode, layers, dirs)
    return run


@pytest.mark.parametrize(
    "mode,dirs,layers,T",
    list(itertools.product(["lstm", "gru", "rnn_tanh", "rnn_relu"],
                           [1, 2], [1, 2], [1, 7])))
def test_outputs_states_and_gradients_match_the_plain_scan(mode, dirs,
                                                           layers, T):
    args = _inputs(mode, layers, dirs, T)
    shapes = jax.eval_shape(_ref(mode, layers, dirs), *args)
    rng = np.random.RandomState(1)
    weights = [jnp.asarray(rng.randn(*s.shape), jnp.float32) for s in shapes]

    # out, h (and c); d data, d parameters, d state (and d state_cell)
    got, _ = numerics.agree(
        _op(mode, layers, dirs), _ref(mode, layers, dirs), args, weights,
        range(len(args)), value=numerics.Tol(rtol=2e-5, atol=2e-5),
        same_dtype=True)
    assert len(got) == (3 if mode == "lstm" else 2)


@pytest.mark.parametrize("mode", ["lstm", "gru"])
def test_bf16_keeps_each_state_in_the_dtype_it_came_in(mode):
    """Data, parameters and ``state`` in bfloat16: outputs and ``h`` are
    bfloat16 forward and backward, and the LSTM's ``c``, given in float32,
    stays float32 through the scan."""
    args = [a.astype(jnp.bfloat16) for a in _inputs(mode, 2, 2, 7)]
    if mode == "lstm":
        args[3] = args[3].astype(jnp.float32)

    def loss(*a):
        outs = _op(mode, 2, 2)(*a)
        return sum(o.astype(jnp.float32).sum() for o in outs), outs

    # the gradient of the sum alone: no cotangent for the outputs beside it
    (_, outs), grads = numerics.traced(
        loss, args, (1.0, (0.0,) * (3 if mode == "lstm" else 2)),
        range(len(args)))
    assert [o.dtype for o in outs[:2]] == [jnp.bfloat16] * 2
    if mode == "lstm":
        assert outs[2].dtype == jnp.float32
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    ref = _ref(mode, 2, 2)(*[a.astype(jnp.float32) for a in args])
    for a, b in zip(outs, ref):
        np.testing.assert_allclose(a.astype(jnp.float32), b, atol=0.06)
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
               for g in grads)


# ---------------------------------------------------------------------------
# what the scans hold
# ---------------------------------------------------------------------------
def _scans(jaxpr, found=None):
    """Every ``scan`` equation of ``jaxpr`` and of what it calls."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scans(sub, found)
    return found


def _count(jaxpr, primitive):
    n = sum(eqn.primitive.name == primitive for eqn in jaxpr.eqns)
    return n + sum(_count(sub, primitive) for eqn in jaxpr.eqns
                   for sub in jax.core.jaxprs_in_params(eqn.params))


def test_every_scan_body_holds_one_product_and_carries_no_weight_sum():
    """A 2-layer LSTM, forward and backward: four scans, each with the one
    product that needs the step before it; the weight gradients are
    whole-sequence products outside, not sums a loop carries."""
    T = 7
    args = _inputs("lstm", 2, 1, T)

    def loss(*a):
        return sum(o.sum() for o in _op("lstm", 2, 1)(*a))

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(
        *args).jaxpr
    scans = _scans(jaxpr)
    assert len(scans) == 4
    weight_shapes = {(4 * H, C), (4 * H, H)}
    for eqn in scans:
        body = eqn.params["jaxpr"].jaxpr
        assert _count(body, "dot_general") == 1, body
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        carried = [v.aval.shape
                   for v in eqn.invars[n_consts:n_consts + n_carry]]
        assert not weight_shapes & set(carried), carried
    # outside the scans, whole-sequence products: forward a layer its
    # input's; backward a layer the states' pre-activations again, then
    # d Wh, d Wx and d xs (the first layer's too: data's gradient is asked)
    assert _count(jaxpr, "dot_general") - 4 == 2 * (1 + 4)


def test_forward_mode_is_refused_not_wrong():
    args = _inputs("gru", 1, 1, 3)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda d: _op("gru", 1, 1)(d, *args[1:])[0],
                (args[0],), (jnp.ones_like(args[0]),))
