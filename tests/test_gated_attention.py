"""``ops.seq.causal_gq_attention`` / ``nn.GQAttention`` with the
``qwen3_next`` family's options: an RMSNorm on every query and key head,
rotary positions over a part of the head, a sigmoid gate on the heads'
output read from the projection. Each option against the plain attention
of the benchmark's reference (``benchmark/configs/qwen3-next-80b-a3b.py``),
values and gradients; the fused kernels interpreted at heads of 256 with
eight query heads on one key/value head; the backward's choice of its
by-side form at that cell's 8192 rows. Nothing here is a time."""
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import attn_kernel, remat, seq

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402
import numerics  # noqa: E402
from numerics import Tol, kernels_here  # noqa: E402, F401


@pytest.fixture(autouse=True)
def _highest_precision():
    """Float32 products at full precision inside these tests only (a
    process-wide setting would change every other file's lowered text)."""
    with jax.default_matmul_precision("highest"):
        yield


SZ = {"hidden_size": 24, "num_attention_heads": 4, "num_key_value_heads": 2,
      "head_dim": 8, "partial_rotary_factor": 0.25, "rope_theta": 100.0,
      "rms_norm_eps": 1e-6, "reference_attention_block": 4}


def _reference():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.py"))


def _weights(seed=0, sz=SZ):
    ha, hkv, dh, d = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"], sz["hidden_size"]
    rng = np.random.default_rng(seed)
    shapes = {"qkv_weight": ((2 * ha + 2 * hkv) * dh, d),
              "q_norm_weight": (dh,), "k_norm_weight": (dh,),
              "o_weight": (d, ha * dh)}
    return {k: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
            for k, s in shapes.items()}


def _layer(w, x, sz=SZ, **more):
    """The block's arithmetic from the op: projection, attention with the
    three options, output projection."""
    kw = dict(num_heads=sz["num_attention_heads"],
              num_kv_heads=sz["num_key_value_heads"],
              head_dim=sz["head_dim"], block=4,
              rope_theta=sz["rope_theta"],
              rotary_dim=int(sz["head_dim"] * sz["partial_rotary_factor"]),
              gated=True, eps=sz["rms_norm_eps"], unit_offset=True)
    kw.update(more)
    out = seq.causal_gq_attention(seq._mm(x, w["qkv_weight"]),
                                  w["q_norm_weight"], w["k_norm_weight"],
                                  **kw)
    return seq._mm(out, w["o_weight"])


def _plain(w, x, sz=SZ):
    ref = _reference()
    p = {"l3_" + k: v for k, v in w.items()}
    return jax.vmap(lambda u: ref.gated_attention(sz, p, 3, u, "float32"))(x)


@pytest.mark.parametrize("length", [12, 7])
def test_gated_attention_is_the_plain_one(length):
    w = _weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, length, 24)),
                    jnp.float32)
    weight = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, length, 24)), jnp.float32)
    numerics.agree(_layer, _plain, (w, x), weight, (0, 1),
                   value=Tol(rtol=2e-5, atol=2e-6), grads=Tol(scaled=3e-5))


def test_partial_rotation_turns_the_first_part_alone():
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 9, 3, 16)),
                    jnp.float32)
    got = seq.rope(x, theta=50.0, rotary_dim=4)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    # the first 4 as a head of 4: pairs (0, 2) and (1, 3)
    np.testing.assert_allclose(got[..., :4], seq.rope(x[..., :4], theta=50.0),
                               atol=1e-7)
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :4] - x[:, 1:, :, :4]))) > 0.1
    # the whole head where the part is the whole, or not given
    for whole in (16, None):
        np.testing.assert_array_equal(seq.rope(x, 50.0, rotary_dim=whole),
                                      seq.rope(x, 50.0))
    ref = _reference()
    np.testing.assert_allclose(got[0], ref.rotate(x[0], 50.0, 4), atol=1e-6)


def test_head_norm_scales_by_one_plus_w_before_the_rotation():
    """With ``w = 0`` the norm leaves unit-rms heads: scaling a query head
    by any factor then changes nothing, which it would without the norm;
    and ``w = -1`` (a scale of zero on the keys) gives uniform causal
    averages of the values."""
    w = _weights()
    zero = dict(w, q_norm_weight=jnp.zeros(8), k_norm_weight=jnp.zeros(8))
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 6, 24)),
                    jnp.float32)
    scaled = dict(zero, qkv_weight=zero["qkv_weight"].at[:32].multiply(3.0))
    numerics.agree(_layer, lambda w, x: _layer(scaled, x), (zero, x),
                   value=Tol(rtol=1e-4, atol=1e-6))
    flat = dict(zero, k_norm_weight=-jnp.ones(8))

    def uniform(w, x):
        qkv = seq._mm(x, w["qkv_weight"])[..., :64]      # no gate
        return qkv, seq.causal_gq_attention(
            qkv, w["q_norm_weight"], w["k_norm_weight"], num_heads=4,
            num_kv_heads=2, head_dim=8, block=4, unit_offset=True)

    (qkv, out), _ = numerics.traced(uniform, (flat, x))
    v = qkv[..., 48:64].reshape(1, 6, 2, 8)
    means = jnp.cumsum(v, axis=1) / jnp.arange(1, 7)[None, :, None, None]
    np.testing.assert_allclose(out.reshape(1, 6, 4, 8),
                               jnp.repeat(means, 2, axis=2), atol=1e-5)


def test_the_gate_is_read_behind_k_and_v_and_multiplies_each_head():
    w = _weights()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 6, 24)),
                    jnp.float32)
    kw = dict(num_heads=4, num_kv_heads=2, head_dim=8, block=4)
    numerics.agree(
        lambda qkv: seq.causal_gq_attention(qkv, gated=True, **kw),
        lambda qkv: seq.causal_gq_attention(qkv[..., :64], **kw)
        * jax.nn.sigmoid(qkv[..., 64:]),
        (seq._mm(x, w["qkv_weight"]),), value=Tol(rtol=1e-5))


def test_block_holds_the_gate_and_both_head_norms():
    mx.random.seed(1)
    block = nn.GQAttention(24, 4, 2, head_dim=8, block=4, rope_theta=100.0,
                           rotary_dim=2, qk_norm=True, gated=True,
                           epsilon=1e-6, norm_unit_offset=True)
    block.initialize(mx.init.Normal(0.3))
    params = {n.split("_", 1)[1]: p for n, p in
              block.collect_params().items()}
    assert {k: p.shape for k, p in params.items()} == {
        "qkv_weight": (96, 24), "o_weight": (24, 32),
        "q_norm_weight": (8,), "k_norm_weight": (8,)}
    # 1 + w from w = 0, whatever the initializer of the matrices
    assert not params["q_norm_weight"].data().asnumpy().any()
    x = mx.nd.array(np.random.default_rng(0).normal(size=(2, 7, 24))
                    .astype(np.float32))
    w = {k: p.data()._data for k, p in params.items()}
    np.testing.assert_allclose(
        block(x).asnumpy(), numerics.traced(_layer, (w, x._data))[0],
        rtol=1e-5, atol=1e-6)
    # without the options the block is the one it was
    plain = nn.GQAttention(24, 4, 2, head_dim=8)
    assert {n.split("_", 1)[1]: p.shape for n, p in
            plain.collect_params().items()} == {
        "qkv_weight": (64, 24), "o_weight": (24, 32)}
    assert plain._attrs == {"num_heads": 4, "num_kv_heads": 2,
                            "head_dim": 8, "block": 1024}


def test_a_unit_keeps_the_packed_rows_once_and_no_norm_or_gate():
    w = _weights()
    x = jnp.zeros((2, 12, 24), jnp.float32)
    qkv = seq._mm(x, w["qkv_weight"])
    jaxpr = jax.make_jaxpr(lambda q: seq.causal_gq_attention(
        q, w["q_norm_weight"], w["k_norm_weight"], num_heads=4,
        num_kv_heads=2, head_dim=8, block=4, rope_theta=100.0, rotary_dim=2,
        gated=True, unit_offset=True))(qkv).jaxpr
    rows = 2 * 12
    # the packed rows, the output, and both head norms' sum of squares
    assert remat.kept_bytes(jaxpr) == rows * (96 + 32 + 4 + 2) * 4


# -- the fused kernels at this family's heads ---------------------------------
WIDE = dict(SZ, num_attention_heads=8, num_key_value_heads=1, head_dim=256,
            hidden_size=64, partial_rotary_factor=0.25, rope_theta=1e7,
            reference_attention_block=128)


@pytest.mark.parametrize("length,limit", [(256, None), (200, 0)])
def test_kernels_at_heads_of_256_with_eight_on_one(kernels_here, monkeypatch,
                                                   length, limit):
    """Eight query heads of 256 on one key/value head through the
    interpreted kernels, fused backward and by side, against the plain
    attention, values and gradients."""
    if limit is not None:
        monkeypatch.setattr(attn_kernel, "_RESIDENT_LIMIT_BYTES", limit)
    w = _weights(seed=6, sz=WIDE)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(1, length, 64)),
                    jnp.float32)
    weight = jnp.asarray(np.random.default_rng(8).normal(
        size=(1, length, 64)), jnp.float32)
    # the weighted sum of the outputs, and its gradients
    numerics.agree(
        lambda w, x: jnp.sum(_layer(w, x, WIDE, block=128) * weight),
        lambda w, x: jnp.sum(_plain(w, x, WIDE) * weight), (w, x), 1.0,
        (0, 1), value=Tol(rtol=2e-5), grads=Tol(scaled=5e-5))


def test_the_backward_is_by_side_at_the_cell_s_rows_and_fused_below():
    """``resident_bytes``: a group of eight heads of 256 holds 16 KiB a
    row in bfloat16 (8 x 256 x (4 + 2 x 2)): 128 MiB at 8192 rows, over
    the 64 MiB the fused backward may hold, and within it at 4096."""
    assert attn_kernel.resident_bytes(8192, 8, 256, 0, 2) \
        == 8192 * 2048 * 8 == 128 * 1024 * 1024
    assert attn_kernel.resident_bytes(8192, 8, 256, 0, 2) \
        > attn_kernel._RESIDENT_LIMIT_BYTES \
        >= attn_kernel.resident_bytes(4096, 8, 256, 0, 2)
    # Moonlight's 16 whole heads of 128 + 64 at 8192 rows stay fused
    assert attn_kernel.resident_bytes(8192, 1, 128, 64, 2) \
        <= attn_kernel._RESIDENT_LIMIT_BYTES
    gauges = [mx.telemetry.gauge(g) for g in (attn_kernel.GAUGE,
                                              attn_kernel.FUSED_BWD_GAUGE)]

    def sites(length):
        for gauge in gauges:
            gauge.set(0)
        data = jax.ShapeDtypeStruct((1, length, (16 * 2 + 2 * 2) * 256),
                                    jnp.bfloat16)
        norm = jax.ShapeDtypeStruct((256,), jnp.float32)
        text = jax.jit(jax.grad(lambda d, a, b: jnp.sum(
            seq.causal_gq_attention(
                d, a, b, num_heads=16, num_kv_heads=2, head_dim=256,
                rope_theta=1e7, rotary_dim=64, gated=True, unit_offset=True
            ).astype(jnp.float32)))).trace(data, norm, norm).lower(
                lowering_platforms=("tpu",)).as_text()
        return tuple(g.get() for g in gauges), \
            len(re.findall(r"tpu_custom_call", text))

    assert sites(8192) == ((1, 0), 3)
    assert sites(4096) == ((1, 1), 2)


def test_a_norm_scales_by_one_plus_its_weight_where_asked():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 5, 16)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(16,)), jnp.float32)
    np.testing.assert_allclose(seq.rms_norm(x, w, eps=1e-6, unit_offset=True),
                               seq.rms_norm(x, 1.0 + w, eps=1e-6), rtol=1e-6)
    np.testing.assert_allclose(
        seq.rms_norm(x, w, eps=1e-6, unit_offset=True),
        _reference()._rms(x, w, 1e-6), rtol=1e-5, atol=1e-6)
    block = nn.RMSNorm(16, 1e-6, unit_offset=True)
    block.initialize()
    assert not block.gamma.data().asnumpy().any()
    got = block(mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(np.sqrt(np.mean(got ** 2, -1)), 1.0, rtol=1e-4)
    plain = nn.RMSNorm(16, 1e-6)
    assert plain._attrs == {"eps": 1e-6}
