"""Several residual streams under manifold-constrained hyper-connections
(arXiv:2512.24880): the ops of ``ops.seq`` (``mhc_maps``, ``mhc_pre``,
``mhc_post``, ``mhc_spread``, ``mhc_merge``) against the per-token form
written out here, values and gradients; what the Sinkhorn iterations
leave; ``PatternLM``'s layer with one stream against today's ``x +
f(x)``; a four-stream ``PatternLM`` against a plain model; the counters.
``alpha`` and the bias are of order 1 throughout, so that every map is
far from the identity. Nothing here is a time."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import PatternLM
from mxnet_tpu.gluon.model_zoo.pattern_lm import _Layer
from mxnet_tpu.ops import remat, seq

import numerics
from numerics import Tol

N, C = 4, 12
EPS = 1e-6


def _params(n=N, c=C, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    phi = 0.15 * jax.random.normal(ks[0], (n * (n + 2), n * c), jnp.float32)
    alpha = jnp.asarray([0.7, 1.3, 0.9], jnp.float32)
    bias = jax.random.normal(ks[2], (n * (n + 2),), jnp.float32)
    return phi, alpha, bias


def _token_maps(x, phi, alpha, bias, n, iters=20, eps=EPS, clamp=(-30, 30)):
    """One token's three maps from its streams ``x`` (n, C), as the paper
    writes them: the Sinkhorn a plain loop, columns before rows."""
    v = x.reshape(-1)
    v = v / jnp.sqrt(jnp.mean(v * v) + eps)
    t = phi @ v
    pre = jax.nn.sigmoid(alpha[0] * t[:n] + bias[:n])
    post = 2 * jax.nn.sigmoid(alpha[1] * t[n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * t[2 * n:] + bias[2 * n:],
                         *clamp)).reshape(n, n)
    for _ in range(iters):
        m = m / (m.sum(0, keepdims=True) + eps)
        m = m / (m.sum(1, keepdims=True) + eps)
    return pre, post, m


def _token_layer(x, phi, alpha, bias, f, n, **kw):
    pre, post, res = _token_maps(x, phi, alpha, bias, n, **kw)
    y = f(pre @ x)
    return res @ x + post[:, None] * y[None, :]


def _f(w):
    return lambda u: jnp.tanh(u @ w)


def _op_layer(data, phi, alpha, bias, f, n, **kw):
    pre, post, res, dev = seq.mhc_maps(data, phi, alpha, bias, streams=n,
                                       **kw)
    return seq.mhc_post(data, f(seq.mhc_pre(data, pre)), res, post), dev


def _streams(data, n):
    """(B, L, n C) -> (B, L, n, C)."""
    return data.reshape(data.shape[:2] + (n, -1))


@pytest.mark.parametrize("n,iters", [(4, 20), (2, 20), (4, 3), (1, 20)])
def test_ops_are_the_per_token_form_with_their_gradients(n, iters):
    phi, alpha, bias = _params(n)
    w = 0.4 * jax.random.normal(jax.random.PRNGKey(5), (C, C), jnp.float32)
    data = jax.random.normal(jax.random.PRNGKey(6), (2, 7, n * C),
                             jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(7), data.shape, jnp.float32)

    def got(data, phi, alpha, bias, w):
        return _op_layer(data, phi, alpha, bias, _f(w), n, iters=iters)[0]

    def want(data, phi, alpha, bias, w):
        one = lambda x: _token_layer(x, phi, alpha, bias, _f(w), n,  # noqa
                                     iters=iters)
        return jax.vmap(jax.vmap(one))(_streams(data, n)).reshape(data.shape)

    with jax.default_matmul_precision("highest"):
        numerics.agree(got, want, (data, phi, alpha, bias, w), cot, range(5),
                       value=Tol(atol=2e-5), grads=Tol(scaled=5e-5))


def test_maps_are_far_from_the_identity_and_h_res_is_doubly_stochastic():
    phi, alpha, bias = _params()
    data = jax.random.normal(jax.random.PRNGKey(8), (2, 9, N * C),
                             jnp.float32)
    def maps(data, phi, alpha, bias):
        *_, few = seq.mhc_maps(data, phi, alpha, bias, streams=N, iters=1)
        *_, res_c, _ = seq.mhc_maps(data, phi, 40.0 * alpha, bias, streams=N,
                                    clamp=(-2.0, 2.0))
        want = jax.vmap(jax.vmap(lambda x: _token_maps(
            x, phi, 40.0 * alpha, bias, N, clamp=(-2.0, 2.0))[2]))(
                _streams(data, N))
        return (seq.mhc_maps(data, phi, alpha, bias, streams=N), few, res_c,
                want)

    ((pre, post, res, dev), few, res_c, want), _ = numerics.traced(
        maps, (data, phi, alpha, bias))
    assert pre.shape == (N, 2, 9) and post.shape == (N, 2, 9)
    assert res.shape == (N, N, 2, 9) and dev.shape == (1,)
    assert float(res.min()) > 0
    assert float(jnp.abs(res - jnp.eye(N)[:, :, None, None]).max()) > 0.3
    rows, cols = res.sum(1), res.sum(0)
    left = max(float(jnp.abs(rows - 1).max()), float(jnp.abs(cols - 1).max()))
    assert left < 1e-3
    np.testing.assert_allclose(float(dev[0]), left, rtol=1e-6)
    # fewer iterations leave more: what the gauge is for
    assert float(few[0]) > 10 * left and float(few[0]) > 1e-2
    # the clamp is applied before the exponential
    assert np.isfinite(np.asarray(res_c)).all()
    np.testing.assert_allclose(jnp.moveaxis(res_c, (0, 1), (2, 3)), want,
                               atol=1e-5)


def test_no_gradient_reaches_what_the_iterations_leave():
    phi, alpha, bias = _params()
    data = jax.random.normal(jax.random.PRNGKey(9), (1, 5, N * C))
    _, g = numerics.traced(lambda b: seq.mhc_maps(
        data, phi, alpha, b, streams=N)[3][0], (bias,), 1.0, 0)
    assert not np.asarray(g).any()


def test_spread_and_merge():
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 3, C), jnp.float32)
    wide = seq.mhc_spread(x, streams=N)
    assert wide.shape == (2, 3, N * C)
    for j in range(N):
        np.testing.assert_array_equal(wide[..., j * C:(j + 1) * C], x)
    np.testing.assert_allclose(seq.mhc_merge(wide, streams=N), N * x,
                               rtol=1e-6)
    # the streams' sum is taken in float32: 256 + 1 + 1 is 258 there, and
    # 256 where every addition is rounded to bfloat16
    big = jnp.concatenate([jnp.full((1, 1, C), 256.0), jnp.ones((1, 1, C)),
                           jnp.ones((1, 1, C))], -1).astype(jnp.bfloat16)
    merged = seq.mhc_merge(big, streams=3)
    assert merged.dtype == jnp.bfloat16 and float(merged[0, 0, 0]) == 258.0


def test_a_unit_keeps_the_product_the_mean_square_and_the_sublayer_s_output():
    phi, alpha, bias = _params()
    w = jnp.eye(C, dtype=jnp.bfloat16)
    data = jnp.ones((2, 8, N * C), jnp.bfloat16)

    def unit(data):
        return _op_layer(data, phi.astype(jnp.bfloat16), alpha, bias,
                         lambda u: remat.kept(u @ w) * 2, N)[0]

    got = remat.kept_bytes(jax.make_jaxpr(
        jax.checkpoint(unit, policy=remat.POLICY))(data).jaxpr)
    tokens = 16
    maps = tokens * (N * (N + 2) + 1) * 4
    assert got == maps + tokens * C * 2 + tokens * C * 2


def _mlp_layer(streams, units=16, **hc):
    layer = _Layer(units, lambda: nn.GatedMLP(units, 24), 1e-6,
                   streams=streams, hyper_connections=hc or None,
                   prefix="l0_")
    layer.initialize(mx.init.Normal(0.3))
    return layer


def test_one_stream_at_its_start_values_is_today_s_residual():
    """With n = 1, ``b_pre`` = 30 and ``b_post`` = 0 (the start values)
    and nothing read off the stream (``alpha`` = 0) a layer gives ``x +
    f(x)``: ``H_pre`` = sigmoid(30), ``H_post`` = 1 and ``H_res`` = 1 to
    what ``eps`` leaves."""
    plain, one = _mlp_layer(None), _mlp_layer(1, eps=1e-9)
    for name, p in one.collect_params().items():
        if "_hc_" not in name:
            p.set_data(plain.collect_params()[name].data())
    one.hc_alpha.set_data(mx.nd.zeros((3,)))
    np.testing.assert_array_equal(one.hc_bias.data().asnumpy(),
                                  [30.0, 0.0, 8.0])
    x = mx.nd.array(np.random.RandomState(0).randn(2, 5, 16)
                    .astype(np.float32))
    np.testing.assert_allclose(one(x).asnumpy(), plain(x).asnumpy(),
                               rtol=1e-6, atol=1e-6)
    assert float(one.hc_dev.data().asnumpy()[0]) < 1e-6


def test_a_layer_s_start_values_keep_the_streams_apart():
    layer = _mlp_layer(4)
    bias = layer.hc_bias.data().asnumpy()
    np.testing.assert_allclose(bias[:4], -np.log(3.0), rtol=1e-6)
    assert not bias[4:8].any()
    np.testing.assert_array_equal(bias[8:].reshape(4, 4), 8.0 * np.eye(4))
    np.testing.assert_allclose(layer.hc_alpha.data().asnumpy(), 0.01)
    assert layer.hc_weight.shape == (24, 64)
    assert layer.hc_dev.grad_req == "null"


# -- a four-stream PatternLM against a plain model ---------------------------
def _rms(x, w, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _plain_model(params, tokens, pattern, n):
    """Embedding copied into n streams, a hyper-connection around every
    sublayer (gated MLPs), the streams summed, final norm, head."""
    p = {k.split("_", 1)[1]: jnp.asarray(v) for k, v in params.items()}
    x = jnp.take(p["embedding0_weight"], tokens, axis=0)      # (B, L, C)
    xs = jnp.repeat(x[..., None, :], n, axis=-2)
    for i in range(len(pattern)):
        norm_w = p[f"l{i}_rmsnorm0_gamma"]
        gu, down = p[f"l{i}_gatedmlp0_gate_up_weight"], \
            p[f"l{i}_gatedmlp0_down_weight"]
        f_units = down.shape[1]

        def f(u):
            h = _rms(u, norm_w) @ gu.T
            return (jax.nn.silu(h[:f_units]) * h[f_units:]) @ down.T

        one = lambda t: _token_layer(  # noqa
            t, p[f"l{i}_hc_weight"], p[f"l{i}_hc_alpha"],
            p[f"l{i}_hc_bias"], f, n)
        xs = jax.vmap(jax.vmap(one))(xs)
    h = _rms(xs.sum(-2), p["rmsnorm0_gamma"])
    return (h @ p["dense0_weight"].T).reshape(-1, p["dense0_weight"].shape[0])


def _stream_lm(seed=0):
    net = PatternLM("GGG", 31, 16, mlp=dict(units=24), epsilon=1e-6,
                    residual_streams=4,
                    hyper_connections=dict(iters=20, eps=1e-6,
                                           clamp=(-30.0, 30.0)))
    net.initialize(mx.init.Normal(0.3))
    rng = np.random.RandomState(seed)
    for name, p in net.collect_params().items():
        if name.endswith(("hc_alpha", "hc_bias")):
            p.set_data(mx.nd.array(rng.randn(*p.shape).astype(np.float32)))
        if name.endswith("hc_weight"):      # H~ of order 1, not 10
            p.set_data(0.2 * p.data())
    return net


def test_pattern_lm_with_four_streams_is_the_plain_model():
    net = _stream_lm()
    tokens = np.random.RandomState(1).randint(0, 31, (2, 6)).astype(np.int32)
    params = {k: v.data().asnumpy() for k, v in net.collect_params().items()}
    with jax.default_matmul_precision("highest"):
        got = net(mx.nd.array(tokens)).asnumpy()
        want, _ = numerics.traced(
            lambda params, tokens: _plain_model(params, tokens, "GGG", 4),
            (params, jnp.asarray(tokens)))
    assert got.shape == (12, 31)
    np.testing.assert_allclose(got, want, atol=3e-5)
    # and it is not the model with one stream: the maps do something
    assert np.abs(got).max() > 1e-2


def test_streams_train_through_train_step_and_publish_their_counters():
    from mxnet_tpu.parallel import TrainStep
    net = _stream_lm()
    step = TrainStep(net, loss="softmax_ce", optimizer="adam",
                     optimizer_params=dict(learning_rate=3e-3),
                     remat="layer")
    rng = np.random.RandomState(2)
    x = mx.nd.array(rng.randint(0, 31, (2, 6)).astype(np.int32))
    y = mx.nd.array(rng.randint(0, 31, (12,)).astype(np.int32))
    before = {k: v.data().asnumpy().copy()
              for k, v in net.collect_params().items()}
    losses = [float(step(x, y).asnumpy()) for _ in range(8)]
    assert losses[-1] < losses[0]
    for name, p in net.collect_params().items():
        moved = np.abs(p.data().asnumpy() - before[name]).max() > 0
        assert moved == (p.grad_req != "null" or name.endswith("hc_dev")), \
            name
    mx.telemetry.remove("mhc::res_sum_dev::")
    gauges = nn.publish_mhc_counters(net)
    assert sorted(gauges) == [f"mhc::res_sum_dev::{net.prefix}l{i}"
                              for i in range(3)]
    assert all(0 < v < 1e-2 for v in gauges.values())
    assert mx.telemetry.gauge(
        f"mhc::res_sum_dev::{net.prefix}l1").get() == gauges[
            f"mhc::res_sum_dev::{net.prefix}l1"]
    scopes = set(step.scope_table().values())
    assert {"mx_mhc_maps", "mx_mhc_pre", "mx_mhc_post", "mx_mhc_in",
            "mx_mhc_out"} <= scopes
    assert not [s for s in scopes if "mx_mhc" in s and "/" in s]


def test_sites_are_counted_where_the_maps_are_lowered():
    phi, alpha, bias = _params()
    data = jnp.ones((1, 4, N * C))
    gauge = mx.telemetry.gauge(seq.MHC_GAUGE)
    gauge.set(0)
    jax.jit(lambda d: seq.mhc_maps(d, phi, alpha, bias, streams=N)[0]) \
        .lower(data)
    assert gauge.get() == 1
    gauge.set(0)
    jax.jit(lambda d: seq.mhc_merge(d, streams=N)).lower(data)
    assert gauge.get() == 0


def test_streams_and_loops_do_not_go_together():
    with pytest.raises(ValueError, match="residual_streams"):
        PatternLM("GG", 31, 16, mlp=dict(units=24), loops=2,
                  residual_streams=4)


def test_without_streams_the_model_has_the_parameters_it_had():
    net = PatternLM("GG", 31, 16, mlp=dict(units=24))
    assert not [k for k in net.collect_params() if "_hc_" in k]
    assert list(net.stack._children.values())[0].__class__ is _Layer
