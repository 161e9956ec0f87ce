"""``ops.attn_kernel``: causal grouped-query attention as fused kernels,
here on the CPU with the kernels interpreted. The kernels against the
dense softmax and against the blocked recurrence, values and gradients of
the packed rows; padding; what a recomputation unit around the op keeps
and what its backward pass runs; where the op takes the kernels
(``head_dim`` a multiple of 128 and a program lowered for a TPU) and the
gauge ``attn::kernel_sites`` that counts it. Nothing here is a time."""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu.ops import attn_kernel, remat, seq

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


@pytest.fixture()
def kernels_here(monkeypatch):
    """The op takes its TPU branch on this backend, kernels interpreted."""
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    for name in ("forward", "backward"):
        monkeypatch.setattr(attn_kernel, name, functools.partial(
            getattr(attn_kernel, name), interpret=True))


def _value_and_grad(fn, data, weight):
    def loss(d):
        return jnp.sum(fn(d).astype(jnp.float32) * weight)
    out = fn(data).astype(jnp.float32)
    return np.asarray(out), np.asarray(jax.grad(loss)(data)
                                       .astype(jnp.float32))


CASES = [(length, hq, hk, theta, dtype)
         for length in (256, 200) for hq, hk in ((4, 4), (4, 1))
         for theta in (None, 1e4) for dtype in ("float32", "bfloat16")]


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
        for g, w in zip(got, want):
            scale = np.abs(w).max()
            assert np.abs(g - w).max() <= tol * scale, other.__name__


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
    want = ["attn_fwd_kernel", "attn_bwd_dq_kernel", "attn_bwd_dkv_kernel"]
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
    mx.telemetry.gauge(attn_kernel.GAUGE).set(0)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text()
    return text, mx.telemetry.gauge(attn_kernel.GAUGE).get()


@pytest.mark.parametrize("dim,platform,sites", [
    (128, "tpu", 1), (256, "tpu", 1), (64, "tpu", 0), (128, "cpu", 0),
    (64, "cpu", 0)])
def test_kernel_sites_follow_the_platform_lowered_for_and_the_head(
        dim, platform, sites):
    data = _packed(256, 2, 1, "bfloat16", dim=dim)

    def loss(d):
        return jnp.sum(_op(d, 2, 1, 1e4, dim=dim).astype(jnp.float32))

    text, counted = _lowered_for(platform, jax.grad(loss), data)
    assert counted == sites
    calls = re.findall(r"tpu_custom_call", text)
    assert len(calls) == 3 * sites
    # a (heads, block, block) float32 score value is in the text where
    # the plain form is, and nowhere where the kernels are
    assert ("tensor<2x2x256x256xf32>" in text) == (not sites)


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
    mx.telemetry.gauge(attn_kernel.GAUGE).set(7)
    step(x, y)                                  # traced and lowered here
    assert mx.telemetry.gauge(attn_kernel.GAUGE).get() == 0
    args = (step._pvals, step._opt_state, x, y, step._t_dev,
            jnp.asarray(0.1, jnp.float32))
    mx.telemetry.gauge(attn_kernel.GAUGE).set(0)
    text = step._step_jit.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    # two attention layers in the one scanned body: call sites of one
    # shape share one lowered program, which is what the gauge counts
    assert mx.telemetry.gauge(attn_kernel.GAUGE).get() == 1
    assert len(re.findall(r"tpu_custom_call", text)) == 3
    kept = mx.telemetry.snapshot(prefix="remat::saved_bytes::")
    per_layer = {k: v["value"] for k, v in kept.items() if "_l0_" in k}
    # rows (2 + 2) heads wide, the output 2 heads wide, the log-sum-exp,
    # W_o's product; times the two passes
    (attn,) = per_layer.values()
    assert attn >= 2 * (256 * 4 * 128 * 2 + 256 * 2 * 128 * 2 + 2 * 256 * 4)
