"""The hyper-connections' kernels (``ops/mhc_kernel.py``) against the plain
form of ``ops.seq`` (``mhc_maps``, ``mhc_pre``, ``mhc_post``) and JAX's own
derivative of it, interpreted on the CPU: each kernel alone, and a whole
sublayer through both ``custom_vjp`` with every gradient; the rule of
shapes they are taken by; and which form ``mhc_read`` and ``mhc_post``
take: the kernels where the rule takes the shapes and the program is
lowered for a TPU, the plain form everywhere else, with the gauge
``mhc::kernel_sites`` counting the sites. Nothing here is a time."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu.ops import mhc_kernel, seq

EPS = 1e-6
TOKENS = 256        # two blocks of 128


def _operands(n, c, dtype, tokens=TOKENS, seed=0):
    """Streams of order 1, ``phi`` small, ``alpha`` and the bias of order
    1, so that no map is near the identity."""
    rng = np.random.default_rng(seed)
    rows = n * (n + 2)
    return dict(
        x=jnp.asarray(rng.normal(size=(tokens, n * c)), dtype),
        y=jnp.asarray(rng.normal(size=(tokens, c)), dtype),
        phi=jnp.asarray(0.1 * rng.normal(size=(rows, n * c)), dtype),
        alpha=jnp.asarray([0.7, 1.3, 0.9], jnp.float32),
        bias=jnp.asarray(rng.normal(size=(rows,)), jnp.float32),
        res=jnp.asarray(rng.uniform(size=(n * n, tokens)), jnp.float32),
        post=jnp.asarray(rng.uniform(size=(n, tokens)), jnp.float32),
        g=jnp.asarray(rng.normal(size=(tokens, n * c)), dtype),
        du=jnp.asarray(rng.normal(size=(tokens, c)), dtype),
        d_raw=jnp.asarray(rng.normal(size=(rows, tokens)), jnp.float32),
        d_ms=jnp.asarray(rng.normal(size=(tokens,)), jnp.float32))


def _close(got, want, tol, name):
    assert got.dtype == want.dtype and got.shape == want.shape, name
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max(), err_msg=name)


def _tol(dtype):
    # float32: sums in another order; bfloat16: the rounding of one output
    return 2e-5 if jnp.dtype(dtype) == jnp.float32 else 2.0 ** -7


SHAPES = pytest.mark.parametrize("n,c", [(2, 128), (2, 256), (4, 128),
                                         (4, 256)])
DTYPES = pytest.mark.parametrize("dtype", ["float32", "bfloat16"])


@SHAPES
@DTYPES
def test_read_is_the_product_the_mean_square_and_the_mix(dtype, n, c):
    o = _operands(n, c, jnp.dtype(dtype), seed=n + c)
    a, b = o["alpha"][0], o["bias"][:n]
    got = mhc_kernel.read(o["x"], o["phi"], a, b, n=n, eps=EPS,
                          interpret=True)
    want = seq._read_plain(o["x"], o["phi"], a, b, n, EPS)
    for name, x, y in zip(("raw", "mean_sq", "u"), got, want):
        _close(x, y, _tol(dtype), name)


@SHAPES
@DTYPES
def test_post_is_the_plain_sums(dtype, n, c):
    o = _operands(n, c, jnp.dtype(dtype), seed=n + c + 1)
    got = mhc_kernel.post(o["x"], o["y"], o["res"], o["post"],
                          interpret=True)
    _close(got, seq._post_rows(o["x"], o["y"], o["res"], o["post"]),
           _tol(dtype), "streams")


@SHAPES
@DTYPES
def test_post_backward_is_jax_s_derivative_of_the_plain_sums(dtype, n, c):
    o = _operands(n, c, jnp.dtype(dtype), seed=n + c + 2)
    args = (o["x"], o["y"], o["res"], o["post"])
    dxp, dy, d_res, d_post = mhc_kernel.post_backward(o["g"], *args,
                                                      interpret=True)
    want = jax.vjp(seq._post_rows, *args)[1](o["g"])
    # one token's product of two streams, 128 or 256 terms in float32
    tol = _tol(dtype) if dtype == "bfloat16" else 1e-4
    for name, x, y in zip(("dxp", "dy", "d_res", "d_post"),
                          (dxp, dy, d_res, d_post), want):
        _close(x, y, tol, name)


@SHAPES
@DTYPES
def test_read_backward_sums_one_cotangent_of_the_streams(dtype, n, c):
    """``dX`` with the write side's share added in, ``d phi`` summed over
    both token blocks, and ``alpha_pre``'s and ``bias_pre``'s gradients
    from the logits' cotangent."""
    o = _operands(n, c, jnp.dtype(dtype), seed=n + c + 3)
    a, b = o["alpha"][0], o["bias"][:n]
    raw, ms, _ = seq._read_plain(o["x"], o["phi"], a, b, n, EPS)
    dx, d_phi, d_logits = mhc_kernel.read_backward(
        o["x"], o["g"], o["du"], raw, ms, o["d_raw"], o["d_ms"], o["phi"],
        a, b, n=n, eps=EPS, interpret=True)
    wx, w_phi, wa, wb = jax.vjp(
        lambda *p: seq._read_plain(*p, n, EPS), o["x"], o["phi"], a, b)[1](
            (o["d_raw"], o["d_ms"], o["du"]))
    # the plain form rounds its share before the write side's is added
    tol = 1e-4 if dtype == "float32" else 2.0 ** -6
    _close(dx, (wx.astype(jnp.float32) + o["g"].astype(jnp.float32)).astype(
        wx.dtype), tol, "dx")
    _close(d_phi.astype(w_phi.dtype), w_phi, tol, "d_phi")
    scaled = raw[:n] * lax.rsqrt(ms + EPS)[None]
    _close(jnp.sum(d_logits * scaled), wa, 1e-4, "d_alpha_pre")
    _close(jnp.sum(d_logits, axis=1), wb, 1e-4, "d_bias_pre")


# ---------------------------------------------------------------------------
# a whole sublayer through both custom_vjp
# ---------------------------------------------------------------------------
class _LoweredForATpu:
    """Stands where ``ops.seq`` names ``jax.lax``: every
    ``platform_dependent`` takes its TPU branch, as a lowering for a TPU
    would."""

    def __getattr__(self, name):
        return getattr(lax, name)

    @staticmethod
    def platform_dependent(*args, tpu, default):
        return tpu(*args)


@pytest.fixture
def kernels_here(monkeypatch):
    """The TPU's branches on this CPU, their kernels interpreted."""
    monkeypatch.setattr(seq, "lax", _LoweredForATpu())
    for name in ("read", "post", "post_backward", "read_backward"):
        monkeypatch.setattr(mhc_kernel, name, functools.partial(
            getattr(mhc_kernel, name), interpret=True))


def _sublayer(o, n, c, w):
    """``loss(data, phi, alpha, bias, w)``: a hyper-connected sublayer
    ``f(u) = tanh(u w)`` by the ops a layer calls, (1, T, n C) streams."""
    rng = np.random.default_rng(5)
    cot = jnp.asarray(rng.normal(size=(1,) + o["x"].shape), jnp.float32)

    def loss(data, phi, alpha, bias, w):
        x, u, post, res, dev = seq.mhc_read(data, phi, alpha, bias,
                                            streams=n, eps=EPS)
        out = jnp.tanh(u @ w).astype(data.dtype)
        return jnp.sum(seq.mhc_post(x, out, res, post) * cot) + 0.0 * dev[0]

    return loss, (o["x"][None], o["phi"], o["alpha"], o["bias"], w)


@pytest.mark.parametrize("n,c", [(2, 128), (4, 128)])
@DTYPES
def test_a_sublayer_through_the_kernels_is_the_plain_form_with_every_gradient(
        dtype, n, c, monkeypatch, request):
    """Value and the gradients for the streams, ``phi``, ``alpha``, the
    bias and the sublayer's weight (through ``y`` and ``u``), the 20
    Sinkhorn iterations in XLA between the kernels."""
    o = _operands(n, c, jnp.dtype(dtype), seed=11 * n)
    w = jnp.asarray(np.random.default_rng(3).normal(size=(c, c)) / c ** 0.5,
                    jnp.dtype(dtype))
    loss, args = _sublayer(o, n, c, w)
    fn = jax.value_and_grad(loss, argnums=range(5))
    with monkeypatch.context() as m:
        m.setattr(mhc_kernel, "takes", lambda *a: False)
        want = fn(*args)
    request.getfixturevalue("kernels_here")
    got = fn(*args)
    tol = 2e-4 if dtype == "float32" else 2.0 ** -5
    names = ("loss", "d_data", "d_phi", "d_alpha", "d_bias", "d_w")
    for name, a, b in zip(names, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        _close(a, b, tol, name)


def test_a_unit_through_the_kernels_keeps_what_the_plain_form_keeps(
        kernels_here):
    """The product, the mean square and ``y``: not ``u``, not a map."""
    from mxnet_tpu.ops import remat
    n, c = 4, 128
    o = _operands(n, c, jnp.bfloat16)
    w = jnp.eye(c, dtype=jnp.bfloat16)

    def unit(data):
        x, u, post, res, _ = seq.mhc_read(data, o["phi"], o["alpha"],
                                          o["bias"], streams=n)
        return seq.mhc_post(x, u @ w, res, post)

    got = remat.kept_bytes(jax.make_jaxpr(
        jax.checkpoint(unit, policy=remat.POLICY))(o["x"][None]).jaxpr)
    assert got == TOKENS * ((n * (n + 2) + 1) * 4 + c * 2)


# ---------------------------------------------------------------------------
# which form a program takes
# ---------------------------------------------------------------------------
def test_the_rule_of_shapes_reads_shapes_alone():
    """2 to 8 streams of whole lane tiles, the tokens whole blocks of 128,
    bfloat16 or float32, the blocks under the VMEM budget: the cell's
    shapes are taken; a stream that is no lane tile, a token count that is
    no whole block, one stream, another dtype and blocks that would not
    fit are not."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert mhc_kernel.takes(4096, 4, 14336, bf16)
    assert mhc_kernel.takes(128, 2, 256, f32)
    assert mhc_kernel.takes(256, 8, 1024, bf16)
    assert not mhc_kernel.takes(4096, 4, 4 * 3584 + 4, bf16)
    assert not mhc_kernel.takes(4096, 4, 4 * 64, bf16)      # half a lane tile
    assert not mhc_kernel.takes(4096, 3, 4 * 128, bf16)     # not n streams
    assert not mhc_kernel.takes(88, 4, 512, bf16)
    assert not mhc_kernel.takes(4096 + 64, 4, 512, bf16)
    assert not mhc_kernel.takes(4096, 1, 128, bf16)
    assert not mhc_kernel.takes(4096, 9, 9 * 128, bf16)
    assert not mhc_kernel.takes(4096, 4, 512, jnp.float16)
    assert not mhc_kernel.takes(4096, 4, 14336, f32)        # 62 MB of blocks
    assert not mhc_kernel.takes(4096, 4, 4 * 8192, bf16)
    assert 2e7 < mhc_kernel.held_bytes(4, 14336, 2) \
        < mhc_kernel._BUDGET_BYTES < mhc_kernel._VMEM_LIMIT_BYTES
    assert mhc_kernel.map_rows(4) == (32, 24)
    assert mhc_kernel.map_rows(2) == (16, 8)
    assert mhc_kernel.map_rows(8) == (80, 72)


def _layer_ops(n, c, tokens, dtype=jnp.bfloat16):
    o = _operands(n, c, dtype, tokens=tokens)
    w = jnp.eye(c, dtype=dtype)
    return _sublayer(o, n, c, w)


def _lowered(n, c, tokens, platform):
    """The text of a sublayer's value and gradients lowered for
    ``platform``, and what the gauge counted."""
    loss, args = _layer_ops(n, c, tokens)
    mx.telemetry.gauge(mhc_kernel.GAUGE).set(0)
    text = jax.jit(jax.value_and_grad(loss, argnums=range(5))).trace(
        *args).lower(lowering_platforms=(platform,)).as_text()
    return text, mx.telemetry.gauge(mhc_kernel.GAUGE).get()


@pytest.mark.parametrize("c,tokens,platform,sites,calls", [
    (128, 256, "tpu", 1, 4),    # the kernels: two forward, two backward
    (128, 256, "cpu", 0, 0),    # another platform: the plain form
    (64, 256, "tpu", 0, 0),     # half a lane tile: the same
    (128, 200, "tpu", 0, 0)])   # no whole blocks of tokens: the same
def test_kernel_sites_follow_the_platform_and_the_rule_of_shapes(
        c, tokens, platform, sites, calls):
    text, counted = _lowered(4, c, tokens, platform)
    assert counted == sites
    assert text.count("tpu_custom_call") == calls
    for kernel in ("mhc_read_kernel", "mhc_post_kernel",
                   "mhc_post_bwd_kernel", "mhc_read_bwd_kernel"):
        assert (kernel in text) == bool(calls), kernel


@pytest.mark.parametrize("c,tokens", [(64, 256), (128, 200)])
def test_shapes_the_rule_refuses_are_the_plain_ops_lowered_text(c, tokens):
    """``mhc_read`` is then ``mhc_maps`` and ``mhc_pre`` called one after
    the other, and ``mhc_post`` the plain sums: the same program, whatever
    it is lowered for."""
    n = 4
    loss, args = _layer_ops(n, c, tokens)
    cot = jnp.asarray(np.random.default_rng(5).normal(
        size=args[0].shape), jnp.float32)

    def plain(data, phi, alpha, bias, w):
        pre, post, res, dev = seq.mhc_maps(data, phi, alpha, bias, streams=n,
                                           eps=EPS)
        out = jnp.tanh(seq.mhc_pre(data, pre) @ w).astype(data.dtype)
        with jax.named_scope("mx_mhc_post"):
            new = seq._post_plain(data, out, res, post, keep=True)
        return jnp.sum(new * cot) + 0.0 * dev[0]

    plain.__name__ = loss.__name__      # the module's name in the text

    def text(fn, platform):
        return jax.jit(jax.value_and_grad(fn, argnums=range(5))).trace(
            *args).lower(lowering_platforms=(platform,)).as_text()

    for platform in ("cpu", "tpu"):
        assert text(loss, platform) == text(plain, platform)


@DTYPES
def test_off_a_tpu_taken_shapes_give_the_plain_form_s_value_and_gradients(
        dtype, monkeypatch):
    """Where the rule takes the shapes and the platform is not a TPU, both
    sides run ``_read_plain`` and ``_post_plain`` as JAX differentiates
    them."""
    n, c = 4, 128
    o = _operands(n, c, jnp.dtype(dtype), seed=7)
    w = jnp.asarray(np.random.default_rng(3).normal(size=(c, c)) / c ** 0.5,
                    jnp.dtype(dtype))
    loss, args = _sublayer(o, n, c, w)
    fn = jax.jit(jax.value_and_grad(loss, argnums=range(5)))
    assert mhc_kernel.takes(TOKENS, n, n * c, jnp.dtype(dtype))
    got = fn(*args)
    monkeypatch.setattr(mhc_kernel, "takes", lambda *a: False)
    want = jax.jit(jax.value_and_grad(loss, argnums=range(5)))(*args)
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, tol, "leaf")


def test_a_train_step_sets_the_gauge_to_zero_where_it_traces():
    """``TrainStep`` resets ``mhc::kernel_sites`` beside the other kernels'
    gauges, so that a step's reading is that step's: a model with streams
    traced for this CPU reads 0 whatever stood there."""
    from mxnet_tpu.gluon.model_zoo import PatternLM
    from mxnet_tpu.parallel import TrainStep
    net = PatternLM("G", 31, 16, mlp=dict(units=24),
                    residual_streams=2)
    net.initialize(mx.init.Normal(0.3))
    step = TrainStep(net, loss="softmax_ce", optimizer="sgd",
                     optimizer_params=dict(learning_rate=1e-2))
    gauge = mx.telemetry.gauge(mhc_kernel.GAUGE)
    gauge.set(7)
    step(mx.nd.array(np.zeros((2, 6), np.int32)),
         mx.nd.array(np.zeros((12,), np.int32)))
    assert gauge.get() == 0
