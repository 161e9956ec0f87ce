"""``ops.attn_kernel``: causal grouped-query attention as fused kernels,
here on the CPU with the kernels interpreted. The kernels against the
dense softmax and against the blocked recurrence, values and gradients of
the packed rows; padding; what a recomputation unit around the op keeps
and what its backward pass runs; where the op takes the kernels
(``head_dim`` a multiple of 128 and a program lowered for a TPU) and the
gauge ``attn::kernel_sites`` that counts it. Nothing here is a time."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import attn_kernel, remat, seq

import numerics
from numerics import Tol, kernels_here  # noqa: F401

D = 128


def _packed(length, hq, hk, dtype, seed=0, batch=2, dim=D):
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (batch, length, (hq + 2 * hk) * dim), jnp.float32)
    return x.astype(dtype)


def _dense(data, hq, hk, rope_theta=None, dim=D):
    """Plain softmax attention over the packed rows: the whole (L, L)
    score matrix of every head in float32."""
    bsz, length, _ = data.shape
    q = data[..., :hq * dim].reshape(bsz, length, hq, dim)
    k = data[..., hq * dim:(hq + hk) * dim].reshape(bsz, length, hk, dim)
    v = data[..., (hq + hk) * dim:].reshape(bsz, length, hk, dim)
    if rope_theta is not None:
        q, k = seq.rope(q, rope_theta), seq.rope(k, rope_theta)
    k, v = (jnp.repeat(t, hq // hk, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * dim ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(bsz, length, hq * dim).astype(data.dtype)


def _op(data, hq, hk, rope_theta=None, dim=D, block=1024):
    return seq.causal_gq_attention(data, num_heads=hq, num_kv_heads=hk,
                                   head_dim=dim, block=block,
                                   rope_theta=rope_theta)


def _blocked(data, hq, hk, rope_theta=None, block=128):
    """The op's plain-JAX form at these sizes (what another backend
    runs), several blocks to a sequence."""
    return _op(data, hq, hk, rope_theta, block=block)


def _value_and_grad(fn, data, weight):
    """``fn(data)`` and the gradient of its sum weighted by ``weight``, as
    float32 arrays."""
    out, grad = numerics.traced(lambda d: fn(d).astype(jnp.float32), (data,),
                                weight, 0)
    return np.asarray(out), np.asarray(grad.astype(jnp.float32))


CASES = [(length, hq, hk, theta, dtype)
         for length in (256, 200) for hq, hk in ((4, 4), (4, 1))
         for theta in (None, 1e4) for dtype in ("float32", "bfloat16")]
# three and five blocks of 128: a query block's gradient adds up over the
# key blocks before it, a key block's over the query blocks after it
CASES += [(length, hq, hk, None, dtype)
          for length in (384, 640) for hq, hk in ((4, 4), (4, 1))
          for dtype in ("float32", "bfloat16")]


@pytest.mark.parametrize("length,hq,hk,theta,dtype", CASES)
def test_kernels_agree_with_dense_and_blocked(kernels_here, length, hq, hk,
                                              theta, dtype):
    data = _packed(length, hq, hk, dtype)
    weight = jax.random.normal(jax.random.PRNGKey(1),
                               (2, length, hq * D), jnp.float32)
    got = _value_and_grad(lambda d: _op(d, hq, hk, theta), data, weight)
    # bfloat16: the three forms round the same float32 sums to bfloat16
    # at different places (one ulp of an output of size 1 is 0.008)
    tol = 2e-5 if dtype == "float32" else 4e-2
    for other in (_dense, _blocked):
        want = _value_and_grad(lambda d: other(d, hq, hk, theta), data,
                               weight)
        numerics.close(got, want, Tol(rtol=0.0, scaled=tol), other.__name__)


def test_dq_is_the_float32_sum_over_key_blocks_rounded_once():
    """Three key blocks of 128 in bfloat16: ``dQ`` is each key block's
    ``dS K`` added up in float32 and rounded once at the end, not a sum
    of rounded parts (which this test tells apart)."""
    hq, hk, length, blk = 4, 1, 384, 128
    bf16, f32 = jnp.bfloat16, jnp.float32
    data = _packed(length, hq, hk, "bfloat16", seed=7)
    q, k, v = (data[..., :hq * D], data[..., hq * D:(hq + hk) * D],
               data[..., (hq + hk) * D:])
    dout = jax.random.normal(jax.random.PRNGKey(8), q.shape, f32).astype(bf16)
    scale = D ** -0.5
    out, lse = attn_kernel.forward(q, k, v, hq, hk, scale, interpret=True)
    got = np.asarray(attn_kernel.backward(
        q, k, v, out, lse, dout, hq, hk, scale, interpret=True)[0].astype(f32))

    heads = lambda t, h: t.reshape(2, length, h, D)  # noqa: E731
    qh, doh, oh = heads(q, hq), heads(dout, hq), heads(out, hq)
    kh, vh = (jnp.repeat(heads(t, hk), hq // hk, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                   preferred_element_type=f32) * scale
    s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -1e30)
    p = jnp.exp(s - lse[..., None])
    dp = jnp.einsum("bqhd,bkhd->bhqk", doh, vh, preferred_element_type=f32)
    delta = jnp.sum(doh.astype(f32) * oh.astype(f32), -1).transpose(0, 2, 1)
    ds = (p * (dp - delta[..., None])).astype(bf16)
    parts = [jnp.einsum("bhqk,bkhd->bqhd", ds[..., j:j + blk],
                        kh[:, j:j + blk], preferred_element_type=f32)
             for j in range(0, length, blk)]

    def rows(t):
        return np.asarray(t.astype(f32)).reshape(2, length, hq * D)

    once = rows((sum(parts) * scale).astype(bf16))
    each = rows(sum((part * scale).astype(bf16) for part in parts))
    # the same float32 sums but for the order inside a product: a value
    # in ten thousand lands on the other side of a rounding
    assert (got != once).mean() < 1e-3
    assert np.abs(got - once).max() <= 2.0 ** -9 * np.abs(once).max()
    assert (each != once).mean() > 0.05     # rounded parts would show


@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 1)])
def test_the_plain_form_gives_the_log_sum_exp_the_kernel_gives(hq, hk):
    """Both forms of ``_fused_attention`` keep the same two values."""
    data = _packed(384, hq, hk, "float32", seed=3)
    q, k, v = (data[..., :hq * D], data[..., hq * D:(hq + hk) * D],
               data[..., (hq + hk) * D:])
    out, lse = attn_kernel.forward(q, k, v, hq, hk, D ** -0.5,
                                   interpret=True)
    out2, lse2 = seq._blocked_rows(q, k, v, hq, hk, D ** -0.5, 128)
    assert lse.shape == lse2.shape == (2, hq, 384)
    np.testing.assert_allclose(lse, lse2, atol=2e-5)
    np.testing.assert_allclose(out, out2, atol=2e-5)


@pytest.mark.parametrize("length,block", [(200, 256), (256, 256), (384, 128),
                                          (1100, 128), (4096, 1024),
                                          (8192, 1024), (1536, 512)])
def test_block_is_chosen_from_the_length(length, block):
    got, padded = attn_kernel.block_size(length)
    assert got == block and padded % got == 0
    assert 0 <= padded - length < 128


@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 1)])
def test_rows_past_a_padded_length_change_nothing(kernels_here, hq, hk):
    """200 rows are padded to 256 inside the op: the same 200 rows as the
    head of a 256-row sequence give the same outputs (causal: no later row
    reaches them), and their gradient, when no later row's output is read,
    is the same too."""
    long = _packed(256, hq, hk, "float32", seed=5)
    short = long[:, :200]
    weight = jax.random.normal(jax.random.PRNGKey(2), (2, 256, hq * D),
                               jnp.float32).at[:, 200:].set(0.0)
    o1, g1 = _value_and_grad(lambda d: _op(d, hq, hk), short, weight[:, :200])
    o2, g2 = _value_and_grad(lambda d: _op(d, hq, hk), long, weight)
    np.testing.assert_allclose(o1, o2[:, :200], atol=1e-6)
    np.testing.assert_allclose(g1, g2[:, :200], atol=1e-5)
    assert not g2[:, 200:].any()


# -- inside a recomputation unit -----------------------------------------------
def _kernel_calls(jaxpr, found=None):
    """The names of the ``pallas_call`` equations of a jaxpr and of the
    programs it calls, in order."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, found)
    return found


@pytest.mark.parametrize("hq,hk,theta", [(4, 4, 1e4), (4, 1, None)])
def test_a_unit_s_backward_runs_no_second_forward_kernel(hq, hk, theta):
    data = _packed(256, hq, hk, "bfloat16")

    def unit(d):
        return jnp.sum(_op(d, hq, hk, theta).astype(jnp.float32) ** 2)

    plain = _kernel_calls(jax.make_jaxpr(jax.grad(unit))(data).jaxpr)
    kept = _kernel_calls(jax.make_jaxpr(jax.grad(
        jax.checkpoint(unit, policy=remat.POLICY)))(data).jaxpr)
    # one backward kernel a site: it forms each score block once
    want = ["attn_fwd_kernel", "attn_bwd_kernel"]
    assert plain == want
    assert kept == want, kept
    # with nothing kept, the unit computes its forward again: that is
    # what the names buy
    bare = _kernel_calls(jax.make_jaxpr(jax.grad(jax.checkpoint(
        unit, policy=jax.checkpoint_policies.nothing_saveable)))(data).jaxpr)
    assert bare.count("attn_fwd_kernel") == 2


@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 1)])
def test_a_unit_keeps_the_log_sum_exp_beside_what_it_kept(hq, hk):
    bsz, length = 2, 256

    def kept_by(dim):
        data = _packed(length, hq, hk, "bfloat16", batch=bsz, dim=dim)
        unit = jax.checkpoint(lambda d: _op(d, hq, hk, dim=dim),
                              policy=remat.POLICY)
        return remat.kept_bytes(jax.make_jaxpr(unit)(data).jaxpr), data

    got, data = kept_by(D)
    rows_and_out = data.size * 2 + bsz * length * hq * D * 2
    assert got == rows_and_out + bsz * hq * length * 4
    # half the head: the plain form, which keeps the rows and the output
    got, data = kept_by(D // 2)
    assert got == data.size * 2 + bsz * length * hq * (D // 2) * 2


# -- where the kernels are taken -------------------------------------------------
def _lowered_for(platform, fn, *args):
    """The text lowered for ``platform`` and what the lowering counted:
    ``(attn::kernel_sites, attn::fused_bwd_sites)``."""
    gauges = [mx.telemetry.gauge(g) for g in (attn_kernel.GAUGE,
                                              attn_kernel.FUSED_BWD_GAUGE)]
    for gauge in gauges:
        gauge.set(0)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text()
    return text, tuple(gauge.get() for gauge in gauges)


@pytest.mark.parametrize("dim,platform,sites", [
    (128, "tpu", 1), (256, "tpu", 1), (64, "tpu", 0), (128, "cpu", 0),
    (64, "cpu", 0)])
def test_kernel_sites_follow_the_platform_lowered_for_and_the_head(
        dim, platform, sites):
    data = _packed(256, 2, 1, "bfloat16", dim=dim)

    def loss(d):
        return jnp.sum(_op(d, 2, 1, 1e4, dim=dim).astype(jnp.float32))

    text, counted = _lowered_for(platform, jax.grad(loss), data)
    assert counted == (sites, sites)
    calls = re.findall(r"tpu_custom_call", text)
    assert len(calls) == 2 * sites
    # a (heads, block, block) float32 score value is in the text where
    # the plain form is, and nowhere where the kernels are
    assert ("tensor<2x2x256x256xf32>" in text) == (not sites)


PAST_THE_RULE = [(384, 4, 2, 64, "float32"), (384, 4, 1, 0, "float32"),
                 (200, 4, 4, 64, "bfloat16"), (640, 4, 2, 0, "bfloat16")]


@pytest.mark.parametrize("length,hq,hk,d2,dtype", PAST_THE_RULE)
def test_past_the_rule_two_kernels_give_the_fused_kernel_s_gradients(
        monkeypatch, length, hq, hk, d2, dtype):
    """Where a group's float32 ``dQ`` over all the rows would not fit in
    VMEM the backward is a kernel for each side; the rule is arithmetic
    on the shape (``resident_bytes``), here met by lowering the limit.
    The same sums in the same order: ``dK`` and ``dV`` to the bit, and
    in float32 ``dQ`` too; the rest to a rounding, since the fused kernel
    forms ``dQ``'s score block turned and takes the second part's narrow
    products with their narrow sides turned (another order inside a
    product)."""
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    widths = (hq * D, hk * D, hk * D, hq * D) + ((hq * d2, d2) if d2 else ())
    q, k, v, dout, *extra = (
        jax.random.normal(key, (2, length, w), jnp.float32).astype(dtype)
        for key, w in zip(keys, widths))
    extra = tuple(extra) or None
    out, lse = attn_kernel.forward(q, k, v, hq, hk, 0.08, interpret=True,
                                   extra=extra)

    def grads():
        return [np.asarray(g.astype(jnp.float32)) for g in
                attn_kernel.backward(q, k, v, out, lse, dout, hq, hk, 0.08,
                                     interpret=True, extra=extra)]

    assert attn_kernel.resident_bytes(length, hq // hk, D, d2, 4) \
        <= attn_kernel._RESIDENT_LIMIT_BYTES
    fused = grads()
    monkeypatch.setattr(attn_kernel, "_RESIDENT_LIMIT_BYTES", 0)
    for name, a, b in zip(("dq", "dk", "dv", "dq2", "dk2"), fused, grads()):
        if name in ("dk", "dv") or (dtype, name) == ("float32", "dq"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:       # the turned score block; the narrow products turned
            assert (a != b).mean() < (1e-3 if dtype == "bfloat16" else 1), \
                name
            assert np.abs(a - b).max() <= (2.0 ** -8 if dtype == "bfloat16"
                                           else 1e-6) * np.abs(b).max(), name


def test_past_the_rule_the_gauge_counts_no_fused_backward(monkeypatch):
    data = _packed(256, 2, 1, "bfloat16")

    def loss(d):
        return jnp.sum(_op(d, 2, 1, 1e4).astype(jnp.float32))

    monkeypatch.setattr(attn_kernel, "_RESIDENT_LIMIT_BYTES", 0)
    text, counted = _lowered_for("tpu", jax.grad(loss), data)
    assert counted == (1, 0)
    assert len(re.findall(r"tpu_custom_call", text)) == 3


def test_train_step_publishes_the_sites_of_the_step_it_traced():
    from mxnet_tpu.gluon.model_zoo import PatternLM
    from mxnet_tpu.parallel import TrainStep
    net = PatternLM("*G*G", 64, 128,
                    attention=dict(num_heads=2, num_kv_heads=1, head_dim=128,
                                   rope_theta=1e4),
                    mlp=dict(units=64), post_norm=True, loops=2)
    net.initialize(mx.init.Normal(0.02))
    step = TrainStep(net, loss="softmax_ce", optimizer="adam",
                     compute_dtype="bfloat16", remat="layer")
    x = jnp.zeros((1, 256), jnp.int32)
    y = jnp.zeros((256,), jnp.int32)
    gauges = [mx.telemetry.gauge(g) for g in (attn_kernel.GAUGE,
                                              attn_kernel.FUSED_BWD_GAUGE)]
    for gauge in gauges:
        gauge.set(7)
    step(x, y)                                  # traced and lowered here
    assert [gauge.get() for gauge in gauges] == [0, 0]
    args = (step._pvals, step._opt_state, x, y, step._t_dev,
            jnp.asarray(0.1, jnp.float32))
    text = step._step_jit.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    # two attention layers in the one scanned body: call sites of one
    # shape share one lowered program, which is what the gauges count
    assert [gauge.get() for gauge in gauges] == [1, 1]
    assert len(re.findall(r"tpu_custom_call", text)) == 2
    kept = mx.telemetry.snapshot(prefix="remat::saved_bytes::")
    per_layer = {k: v["value"] for k, v in kept.items() if "_l0_" in k}
    # rows (2 + 2) heads wide, the output 2 heads wide, the log-sum-exp,
    # W_o's product; times the two passes
    (attn,) = per_layer.values()
    assert attn >= 2 * (256 * 4 * 128 * 2 + 256 * 2 * 128 * 2 + 2 * 256 * 4)
