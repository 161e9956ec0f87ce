"""Latent attention with a query latent under YaRN:
``ops.seq.latent_attention`` with ``q_down_weight`` / ``q_norm_weight``,
``yarn`` and ``scale`` against the plain reference's equations (``benchmark/configs/xing4.0-29b-a4b.py``),
values and gradients; the YaRN frequencies against their closed form and
the softmax scale; the kernels of ``ops.attn_kernel``, interpreted, at 4
heads of 192 / 128; a share of the heads with both latents whole, whose
outputs add up to the uncut layer's; what a unit keeps; and the defaults,
which compute what they computed. Nothing here is a time."""
import functools
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import attn_kernel, remat, seq

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402
import numerics  # noqa: E402
from numerics import Tol, kernels_here  # noqa: E402, F401

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
SMALL = {"hidden_size": 48, "num_attention_heads": 8, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 10, "kv_lora_rank": 20,
         "q_lora_rank": 14, "rope_theta": 10000, "rope_scaling": YARN,
         "rms_norm_eps": 1e-6, "reference_attention_block": 8}
LEAVES = ("q_weight", "kv_down_weight", "kv_norm_weight", "kv_up_weight",
          "o_weight", "q_down_weight", "q_norm_weight")


def _reference():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", "xing4.0-29b-a4b.py"))


def _weights(sz, seed=0, dtype=jnp.float32):
    d, h, r, rq = sz["hidden_size"], sz["num_attention_heads"], \
        sz["kv_lora_rank"], sz["q_lora_rank"]
    dn, dr, dv = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    shapes = {"q_weight": (h * (dn + dr), rq), "kv_down_weight": (r + dr, d),
              "kv_norm_weight": (r,), "kv_up_weight": (h * (dn + dv), r),
              "o_weight": (d, h * dv), "q_down_weight": (rq, d),
              "q_norm_weight": (rq,)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    w = {k: 0.2 * jax.random.normal(key, s, jnp.float32)
         for key, (k, s) in zip(keys, shapes.items())}
    for k in ("kv_norm_weight", "q_norm_weight"):
        w[k] = 1.0 + w[k]
    return {k: v.astype(dtype) for k, v in w.items()}


def _yarn_attrs(sz):
    group = sz["rope_scaling"]
    return dict(
        yarn=(group["factor"], group["original_max_position_embeddings"],
              group["beta_fast"], group["beta_slow"]),
        scale=(sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"]) ** -0.5
        * seq.yarn_mscale(group["factor"], group["mscale_all_dim"]) ** 2)


def _op(sz, w, x, block=8, heads=None):
    return seq.latent_attention(
        x, *(w[k] for k in LEAVES),
        num_heads=heads or sz["num_attention_heads"],
        nope_dim=sz["qk_nope_head_dim"], rope_dim=sz["qk_rope_head_dim"],
        v_dim=sz["v_head_dim"], latent_dim=sz["kv_lora_rank"],
        rope_theta=sz["rope_theta"], eps=sz["rms_norm_eps"], block=block,
        **_yarn_attrs(sz))


# -- frequencies and scale ----------------------------------------------------
def test_yarn_frequencies_are_the_closed_form():
    """64-wide slices, theta 10000, factor 64 over 4096 positions, beta 32
    and 1: pairs up to 10 keep their frequency, pairs from 23 turn 64
    times slower, and between the two the mix is linear in the pair."""
    got = seq.rope_frequencies(64, 10000.0, (64, 4096, 32, 1))
    i = np.arange(32)
    f = 10000.0 ** (-i / 32)
    low = 64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(10000))
    high = 64 * math.log(4096 / (2 * math.pi * 1)) / (2 * math.log(10000))
    assert (round(low, 2), round(high, 2)) == (10.47, 22.51)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(got, f * (1 - ramp) + f / 64 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 64, rtol=1e-6)
    assert got.dtype == np.float32
    # the configuration's reference computes the same numbers on its own
    ref = _reference()
    np.testing.assert_allclose(ref.yarn_frequencies(
        {"qk_rope_head_dim": 64, "rope_theta": 10000,
         "rope_scaling": YARN}), got, rtol=1e-6)
    # without the rule, the frequencies the rotation always had
    np.testing.assert_array_equal(
        seq.rope_frequencies(64, 10000.0),
        np.asarray(1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64), np.float32))


def test_rope_under_yarn_turns_the_slow_pairs_slower():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 2, 64), jnp.float32)
    plain = seq.rope(x, 10000.0)
    slow = seq.rope(x, 10000.0, yarn=(64, 4096, 32, 1))
    # pairs 0-10 (elements i and i + 32) are rotated as before
    np.testing.assert_allclose(slow[..., :11], plain[..., :11], atol=1e-6)
    np.testing.assert_allclose(slow[..., 32:43], plain[..., 32:43],
                               atol=1e-6)
    assert float(jnp.abs(slow[..., 23:32] - plain[..., 23:32]).max()) > 1e-4
    # a rotation keeps every pair's length: cos and sin are not scaled
    np.testing.assert_allclose(
        slow[..., :32] ** 2 + slow[..., 32:] ** 2,
        x[..., :32] ** 2 + x[..., 32:] ** 2, rtol=1e-4, atol=1e-5)
    part = seq.rope(x, 10000.0, rotary_dim=16, yarn=(64, 4096, 32, 1))
    np.testing.assert_array_equal(part[..., 16:], x[..., 16:])


def test_the_softmax_scale_is_the_published_rule():
    assert seq.yarn_mscale(64, 1) == pytest.approx(0.1 * math.log(64) + 1)
    assert seq.yarn_mscale(1, 1) == 1.0 and seq.yarn_mscale(0.5, 3) == 1.0
    block = nn.LatentAttention(3584, 4, q_latent_dim=768,
                               rope_scaling=YARN)
    assert block._attrs["scale"] == pytest.approx(0.07217 * 2.00474,
                                                  rel=2e-4)
    assert block._attrs["yarn"] == (64.0, 4096.0, 32.0, 1.0)
    assert block.q_down_weight.shape == (768, 3584)
    assert block.q_weight.shape == (4 * 192, 768)
    ref = _reference()
    assert ref.softmax_scale(dict(SMALL, qk_nope_head_dim=128,
                                  qk_rope_head_dim=64)) \
        == pytest.approx(block._attrs["scale"], rel=1e-6)
    # where mscale equals mscale_all_dim the softmax carries all of the
    # factor and cos and sin none; a group whose two differ puts their
    # ratio on cos and sin (``rope``'s ``mscale``), and one that gives
    # ``attention_factor`` puts that there; a rule that is not YaRN's is
    # refused
    assert "mscale" not in block._attrs
    both = nn.LatentAttention(64, 2, rope_scaling=dict(YARN, mscale=2))
    assert both._attrs["mscale"] == pytest.approx(
        seq.yarn_mscale(64, 2) / seq.yarn_mscale(64, 1))
    assert both._attrs["scale"] == pytest.approx(
        192 ** -0.5 * seq.yarn_mscale(64, 1) ** 2)
    assert nn.LatentAttention(64, 2, rope_scaling=dict(
        YARN, attention_factor=1.25))._attrs["mscale"] == 1.25
    with pytest.raises(ValueError, match="yarn"):
        nn.LatentAttention(64, 2, rope_scaling=dict(YARN, type="linear"))
    # no mscale_all_dim: the plain scale
    assert "scale" not in nn.LatentAttention(
        64, 2, rope_scaling={k: v for k, v in YARN.items()
                             if k != "mscale_all_dim"})._attrs


# -- the op against the reference's equations ---------------------------------
@pytest.mark.parametrize("length,block", [(24, 8), (21, 8)])
def test_op_with_a_query_latent_is_the_reference_s_equations(length, block):
    ref = _reference()
    sz = dict(SMALL, num_attention_heads=3)
    w = _weights(sz)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, length, sz["hidden_size"]), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)

    def want(w, x):
        p = {"l0_" + k: v for k, v in w.items()}
        return jax.vmap(lambda u: ref.latent_attention(
            sz, p, 0, u, "float32"))(x)

    with jax.default_matmul_precision("highest"):
        got, _ = numerics.agree(
            lambda w, x: _op(sz, w, x, block), want, (w, x), cot, (0, 1),
            value=Tol(atol=2e-5), grads=Tol(scaled=3e-5))
    # the scale and the frequencies both matter at these sizes
    bare, _ = numerics.traced(lambda w, x: seq.latent_attention(
        x, *(w[k] for k in LEAVES), num_heads=3, nope_dim=16, rope_dim=8,
        v_dim=10, latent_dim=20, rope_theta=10000, eps=1e-6, block=block),
        (w, x))
    assert float(jnp.abs(bare - got).max()) > 1e-3


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 of 8 heads, each with both latents whole and its
    heads' rows of ``Wqb`` and ``Wkvb`` and columns of ``Wo``: their
    outputs add up to the layer with all 8."""
    sz, h, per = SMALL, 8, 2
    dn, dr, dv = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    w = _weights(sz, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 48), jnp.float32)

    def rows(matrix, width, first, parts):
        """A share's rows of a matrix whose rows are grouped by part,
        each part every head's ``width`` rows."""
        return jnp.concatenate(
            [matrix[part * h * w_ + first * w_:
                    part * h * w_ + (first + per) * w_]
             for part, w_ in zip(parts, width)])

    def shares(w, x):
        whole = _op(sz, w, x)
        total = jnp.zeros_like(whole)
        for share in range(h // per):
            first = share * per
            q = jnp.concatenate([
                w["q_weight"][first * dn:(first + per) * dn],
                w["q_weight"][h * dn + first * dr:
                              h * dn + (first + per) * dr]])
            kv = jnp.concatenate([
                w["kv_up_weight"][first * dn:(first + per) * dn],
                w["kv_up_weight"][h * dn + first * dv:
                                  h * dn + (first + per) * dv]])
            mine = dict(w, q_weight=q, kv_up_weight=kv,
                        o_weight=w["o_weight"][:, first * dv:
                                               (first + per) * dv])
            total = total + _op(sz, mine, x, heads=per)
        return total, whole

    with jax.default_matmul_precision("highest"):
        (total, whole), _ = numerics.traced(shares, (w, x))
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert float(jnp.abs(whole).max()) > 1e-2


# -- through the kernels, at 4 heads of 192 / 128 -----------------------------
WIDE = dict(SMALL, hidden_size=256, num_attention_heads=4,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            kv_lora_rank=64, q_lora_rank=96)


def test_op_through_the_kernels_at_four_heads_is_the_plain_form(
        kernels_here):
    w = _weights(WIDE, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 256, 256), jnp.float32)

    def loss(w, x):
        return jnp.sum(_op(WIDE, w, x, block=128) ** 2)

    got = numerics.traced(loss, (w, x), 1.0, (0, 1))
    with pytest.MonkeyPatch.context() as plain:
        plain.setattr(lax, "platform_dependent",
                      lambda *args, tpu, default: default(*args))
        want = numerics.traced(loss, (w, x), 1.0, (0, 1))
    numerics.close(got, want, numerics.kernel_tol(2e-4))


def test_the_site_is_lowered_to_the_kernels_for_a_tpu():
    w = _weights(WIDE, seed=5, dtype=jnp.bfloat16)
    x = jnp.ones((1, 256, 256), jnp.bfloat16)
    mx.telemetry.gauge(attn_kernel.GAUGE).set(0)
    text = jax.jit(jax.grad(lambda w, x: jnp.sum(
        _op(WIDE, w, x, block=1024).astype(jnp.float32)))).trace(w, x).lower(
            lowering_platforms=("tpu",)).as_text()
    assert mx.telemetry.gauge(attn_kernel.GAUGE).get() == 1
    assert text.count("tpu_custom_call") == 2
    assert "tensor<1x4x256x256xf32>" not in text


def test_a_unit_keeps_both_query_products():
    w = _weights(WIDE, seed=5, dtype=jnp.bfloat16)
    x = jnp.ones((1, 256, 256), jnp.bfloat16)
    unit = jax.checkpoint(lambda x: _op(WIDE, w, x), policy=remat.POLICY)
    got = remat.kept_bytes(jax.make_jaxpr(unit)(x).jaxpr)
    tokens, h = 256, 4
    q = tokens * 96 * 2 + tokens * 4 + tokens * h * 192 * 2
    latent = tokens * (64 + 64) * 2 + tokens * 64 * 2    # [c | k_pe], N(c)
    out = tokens * h * 128 * 2
    lse, norm_sum = h * tokens * 4, tokens * 4
    assert got == q + latent + out + lse + norm_sum


# -- the block ----------------------------------------------------------------
def test_block_with_a_query_latent_is_the_op_and_without_it_the_old_block():
    block = nn.LatentAttention(48, 3, nope_dim=16, rope_dim=8, v_dim=10,
                               latent_dim=20, q_latent_dim=14,
                               rope_theta=10000, epsilon=1e-6, block=8,
                               rope_scaling=YARN, prefix="a_")
    block.initialize(mx.init.Normal(0.2))
    names = [k.split("_", 1)[1] for k in block.collect_params()]
    assert sorted(names) == sorted(LEAVES)
    w = {k: block.collect_params()["a_" + k].data()._data for k in LEAVES}
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, 48), jnp.float32)
    np.testing.assert_allclose(
        block(mx.nd.array(np.asarray(x))).asnumpy(),
        numerics.traced(functools.partial(
            _op, dict(SMALL, num_attention_heads=3)), (w, x))[0], atol=1e-5)
    old = nn.LatentAttention(48, 3, nope_dim=16, rope_dim=8, v_dim=10,
                             latent_dim=20)
    assert sorted(k.split("_", 1)[1] for k in old.collect_params()) \
        == sorted(LEAVES[:5])
    assert "yarn" not in old._attrs and "scale" not in old._attrs
    assert old.q_weight.shape == (3 * 24, 48)
