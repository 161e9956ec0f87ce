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

import numerics
from numerics import kernel_tol

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
    # raw, mean_sq, u
    numerics.agree(
        lambda *a: mhc_kernel.read(*a, n=n, eps=EPS, interpret=True),
        lambda *a: seq._read_plain(*a, n, EPS),
        (o["x"], o["phi"], o["alpha"][0], o["bias"][:n]),
        value=kernel_tol(_tol(dtype)), same_dtype=True)


@SHAPES
@DTYPES
def test_post_is_the_plain_sums(dtype, n, c):
    o = _operands(n, c, jnp.dtype(dtype), seed=n + c + 1)
    numerics.agree(functools.partial(mhc_kernel.post, interpret=True),
                   seq._post_rows, (o["x"], o["y"], o["res"], o["post"]),
                   value=kernel_tol(_tol(dtype)), same_dtype=True)


@SHAPES
@DTYPES
def test_post_backward_is_jax_s_derivative_of_the_plain_sums(dtype, n, c):
    o = _operands(n, c, jnp.dtype(dtype), seed=n + c + 2)
    args = (o["x"], o["y"], o["res"], o["post"])
    # one token's product of two streams, 128 or 256 terms in float32
    tol = _tol(dtype) if dtype == "bfloat16" else 1e-4
    # dxp, dy, d_res, d_post
    numerics.agree(
        lambda g, *a: mhc_kernel.post_backward(g, *a, interpret=True),
        lambda g, *a: jax.vjp(seq._post_rows, *a)[1](g), (o["g"], *args),
        value=kernel_tol(tol), same_dtype=True)


@SHAPES
@DTYPES
def test_read_backward_sums_one_cotangent_of_the_streams(dtype, n, c):
    """``dX`` with the write side's share added in, ``d phi`` summed over
    both token blocks, and ``alpha_pre``'s and ``bias_pre``'s gradients
    from the logits' cotangent."""
    o = _operands(n, c, jnp.dtype(dtype), seed=n + c + 3)
    a, b = o["alpha"][0], o["bias"][:n]

    def kernel(x, phi, a, b):
        raw, ms, _ = seq._read_plain(x, phi, a, b, n, EPS)
        dx, d_phi, d_logits = mhc_kernel.read_backward(
            x, o["g"], o["du"], raw, ms, o["d_raw"], o["d_ms"], phi, a, b,
            n=n, eps=EPS, interpret=True)
        scaled = raw[:n] * lax.rsqrt(ms + EPS)[None]
        return dx, d_phi, jnp.sum(d_logits * scaled), jnp.sum(d_logits,
                                                              axis=1)

    def plain(x, phi, a, b):
        return jax.vjp(lambda *p: seq._read_plain(*p, n, EPS), x, phi, a, b)[
            1]((o["d_raw"], o["d_ms"], o["du"]))

    args = (o["x"], o["phi"], a, b)
    (dx, d_phi, da, db), _ = numerics.traced(kernel, args)
    (wx, w_phi, wa, wb), _ = numerics.traced(plain, args)
    # the plain form rounds its share before the write side's is added
    tol = kernel_tol(1e-4 if dtype == "float32" else 2.0 ** -6)
    numerics.close(
        dx, (wx.astype(jnp.float32) + o["g"].astype(jnp.float32)).astype(
            wx.dtype), tol, "dx", same_dtype=True)
    numerics.close(d_phi.astype(w_phi.dtype), w_phi, tol, "d_phi")
    numerics.close((da, db), (wa, wb), kernel_tol(1e-4),
                   "d_alpha_pre, d_bias_pre", same_dtype=True)


# ---------------------------------------------------------------------------
# a whole sublayer through both custom_vjp
# ---------------------------------------------------------------------------
@pytest.fixture
def kernels_here(monkeypatch):
    """The TPU's branches on this CPU, their kernels interpreted."""
    monkeypatch.setattr(seq, "lax", numerics.LoweredForATpu())
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
    with monkeypatch.context() as m:
        m.setattr(mhc_kernel, "takes", lambda *a: False)
        want = numerics.traced(loss, args, 1.0, range(5))
    request.getfixturevalue("kernels_here")
    got = numerics.traced(loss, args, 1.0, range(5))
    tol = 2e-4 if dtype == "float32" else 2.0 ** -5
    # the loss; the streams, phi, alpha, the bias, w
    numerics.close(got, want, kernel_tol(tol), same_dtype=True)


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
    numerics.close(got, want, kernel_tol(tol), same_dtype=True)


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
