"""``ops.seq.gated_short_conv`` / ``nn.GatedShortConv`` (the ``lfm2``
family's mixer: three causal taps between two linear gates), a mixture of
experts with no shared expert (``nn.GatedMoE(shared_units=0)``) and
64-wide grouped-query heads under head norms, each against the plain
reference of the benchmark's cell (``benchmark/configs/lfm2-24b-a2b.py``),
values and gradients; nothing leaks across sequences or from the future;
**the share test**: the eight shares of an attention sublayer (4 query
heads on 1 key/value head each) and of an expert sublayer (8 of 64
experts each) add up to the uncut reference layers, what every chip
computes alike counted once. Nothing here is a time."""
import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import PatternLM
from mxnet_tpu.ops import seq

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402
import numerics  # noqa: E402
from numerics import TO_THE_BIT, Tol  # noqa: E402


@pytest.fixture(autouse=True)
def _highest_precision():
    """Float32 products at full precision inside these tests only."""
    with jax.default_matmul_precision("highest"):
        yield


def _reference():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", "lfm2-24b-a2b.py"))


def _normal(seed, shape, scale=1.0, dtype=jnp.float32):
    return jnp.asarray(scale * np.random.default_rng(seed).normal(size=shape),
                       dtype)


# -- the gated short convolution ---------------------------------------------
HIDDEN = 32


def _conv_weights(kernel, dtype=jnp.float32, seed=0):
    return {"in_weight": _normal(seed, (3 * HIDDEN, HIDDEN), 0.3, dtype),
            "conv_weight": _normal(seed + 1, (HIDDEN, kernel), 0.5, dtype),
            "out_weight": _normal(seed + 2, (HIDDEN, HIDDEN), 0.3, dtype)}


def _mixer(w, x):
    return seq.gated_short_conv(x, w["in_weight"], w["conv_weight"],
                                w["out_weight"])


def _plain_mixer(w, x):
    """The reference's mixer, a sequence at a time, in float32."""
    ref = _reference()
    kernel = w["conv_weight"].shape[1]
    sz = {"hidden_size": HIDDEN, "conv_L_cache": kernel}
    p = {"l0_" + k: v.astype(jnp.float32) for k, v in w.items()}
    return jax.vmap(lambda u: ref.short_conv(sz, p, 0, u, "float32"))(
        x.astype(jnp.float32))


#: float32 against float32: rounding of sums in another order. The
#: bfloat16-for-float32 mistake reads 1e-2 of the largest entry and fails
#: either; bfloat16's own tolerance is that mistake's size
F32 = Tol(rtol=2e-5, scaled=2e-6)
BF16 = Tol(rtol=0.0, scaled=3e-2)


@pytest.mark.parametrize("kernel,length,dtype", [
    (3, 11, jnp.float32), (4, 11, jnp.float32), (3, 2, jnp.float32),
    (3, 11, jnp.bfloat16), (4, 9, jnp.bfloat16)])
def test_the_mixer_is_the_reference_s(kernel, length, dtype):
    """Value and every gradient, batch 2, at 3 taps and at 4, a sequence
    shorter than the kernel among them."""
    w = _conv_weights(kernel, dtype)
    x = _normal(7, (2, length, HIDDEN), dtype=dtype)
    cot = _normal(8, (2, length, HIDDEN))
    tol = F32 if dtype == jnp.float32 else BF16
    (out, _) = numerics.agree(
        lambda w, x: _mixer(w, x).astype(jnp.float32), _plain_mixer,
        (w, x), cot, (0, 1), value=tol, grads=tol)
    assert out.shape == x.shape


def test_float32_s_tolerance_fails_bfloat16():
    """The tolerances are tight enough to tell the precisions apart: the
    mixer given bfloat16 copies of float32 operands misses ``F32``."""
    w = _conv_weights(3)
    x = _normal(7, (2, 11, HIDDEN))
    low = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), (w, x))
    with pytest.raises(AssertionError):
        numerics.agree(lambda w, x: _mixer(*low).astype(jnp.float32),
                       _plain_mixer, (w, x), value=F32)


def test_nothing_leaks_across_sequences_or_from_the_future():
    """Token ``t`` of sequence 0 perturbed: sequence 0 before ``t`` and
    all of sequence 1 unchanged to the bit, ``t`` to ``t + K - 1``
    changed, later tokens unchanged again (a convolution has no state
    beyond its taps)."""
    w = _conv_weights(3)
    x = _normal(9, (2, 12, HIDDEN))
    t = 5
    moved = x.at[0, t].add(1.0)
    (a, b), _ = numerics.traced(lambda w, x, y: (_mixer(w, x), _mixer(w, y)),
                                (w, x, moved))
    numerics.close(b[0, :t], a[0, :t], TO_THE_BIT)
    numerics.close(b[1], a[1], TO_THE_BIT)
    numerics.close(b[0, t + 3:], a[0, t + 3:], TO_THE_BIT)
    for step in range(3):
        assert float(jnp.max(jnp.abs(b[0, t + step] - a[0, t + step]))) > 1e-3


def test_the_last_tap_is_on_the_current_token():
    """With taps ``(0, 0, 1)`` the convolution is the identity: the mixer
    is ``W_out (C * B * z)``; with ``(1, 0, 0)`` it reads two tokens
    back."""
    w = _conv_weights(3)
    x = _normal(10, (1, 6, HIDDEN))
    bcz = seq._mm(x, w["in_weight"])
    b, c, z = (bcz[..., i * HIDDEN:(i + 1) * HIDDEN] for i in range(3))
    last = jnp.zeros((HIDDEN, 3)).at[:, 2].set(1.0)
    np.testing.assert_allclose(
        _mixer(dict(w, conv_weight=last), x),
        seq._mm(c * b * z, w["out_weight"]), atol=1e-5)
    back = jnp.pad(b * z, ((0, 0), (2, 0), (0, 0)))[:, :6]
    np.testing.assert_allclose(
        _mixer(dict(w, conv_weight=last[:, ::-1]), x),
        seq._mm(c * back, w["out_weight"]), atol=1e-5)


def test_a_unit_keeps_the_packed_projection_alone():
    """What ``TrainStep(remat="layer")`` holds of the mixer beside the
    unit's input: ``[B | C | z]``, three times the input."""
    from mxnet_tpu.ops import remat
    w = _conv_weights(3, jnp.bfloat16)
    x = _normal(7, (2, 16, HIDDEN), dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(_mixer)(w, x).jaxpr
    assert remat.kept_bytes(jaxpr) == 3 * x.size * 2


def test_block_holds_three_leaves_and_pattern_lm_knows_its_letter():
    block = nn.GatedShortConv(HIDDEN, kernel=3)
    shapes = {k.rsplit("_", 2)[-2] + "_" + k.rsplit("_", 1)[-1]: p.shape
              for k, p in block.collect_params().items()}
    assert shapes == {"in_weight": (96, 32), "conv_weight": (32, 3),
                      "out_weight": (32, 32)}
    block.initialize(mx.init.Normal(0.3))
    w = {k.split("conv0_", 1)[1]: p.data()._data
         for k, p in block.collect_params().items()}
    x = _normal(11, (2, 7, HIDDEN))
    np.testing.assert_allclose(block(mx.nd.array(x)).asnumpy(),
                               _plain_mixer(w, x), rtol=1e-4, atol=1e-5)
    net = PatternLM("CG", 50, HIDDEN, mlp=dict(units=48))
    kinds = [type(layer.mixer).__name__ for layer in
             net.stack._children.values() if hasattr(layer, "mixer")]
    assert kinds == ["GatedShortConv", "GatedMLP"]
    with pytest.raises(ValueError, match=r"F, D, C and W are known"):
        PatternLM("CQ", 50, HIDDEN)


# -- experts with no shared expert --------------------------------------------
MOE = {"hidden_size": 32, "moe_intermediate_size": 24, "router_experts": 64,
       "num_experts": 64, "num_experts_per_tok": 4, "norm_topk_prob": True,
       "use_expert_bias": True, "norm_topk_eps": 1e-6,
       "routed_scaling_factor": 1}
TOKENS = 48


def _moe_weights(seed=0):
    d, ff = MOE["hidden_size"], MOE["moe_intermediate_size"]
    w = {"router_weight": _normal(seed, (64, d), 0.3),
         "router_bias": _normal(seed + 1, (64,), 0.02),
         "w1": _normal(seed + 2, (64, d, ff), 0.3),
         "w3": _normal(seed + 3, (64, d, ff), 0.3),
         "w2": _normal(seed + 4, (64, ff, d), 0.3)}
    return w


def _routed(w, x, ids, rows=4 * TOKENS):
    held = jnp.asarray(ids)
    return seq.gated_moe(
        x, w["router_weight"], w["router_bias"], w["w1"][held], w["w3"][held],
        w["w2"][held], None, None, None, expert_ids=tuple(ids), top_k=4,
        buffer_rows=rows, scaling=1.0, norm_topk=True, norm_topk_eps=1e-6)


def _plain_experts(w, x, ids=None):
    ref = _reference()
    sz = dict(MOE) if ids is None else dict(MOE, expert_ids=list(ids))
    held = jnp.arange(64) if ids is None else jnp.asarray(ids)
    p = {"l0_" + k: (v[held] if k in ("w1", "w3", "w2") else v)
         for k, v in w.items()}
    return ref.moe_layer(sz, p, 0, x.reshape(-1, x.shape[-1]), "float32")


def test_experts_without_a_shared_one_are_the_reference_s():
    """The routed sum alone, value and the gradients of the input, the
    router and the held experts."""
    w = _moe_weights()
    x = _normal(5, (1, TOKENS, 32))
    cot = _normal(6, (TOKENS, 32))
    ids = (0, 1, 2, 3, 4, 5, 6, 7)
    (_, grads) = numerics.agree(
        lambda w, x: _routed(w, x, ids)[0][0],
        lambda w, x: _plain_experts(w, x, ids)[0], (w, x), cot, (0, 1),
        value=Tol(atol=2e-5), grads=Tol(atol=5e-5))
    # the bias selects only: no gradient reaches it
    assert not np.asarray(grads[0]["router_bias"]).any()
    assert np.asarray(grads[0]["router_weight"]).any()


def test_no_shared_expert_means_no_parameter_no_product_no_scope():
    block = nn.GatedMoE(32, 64, range(8), 4, 24, 0, 4 * TOKENS,
                        norm_topk_eps=1e-6)
    names = [k.rsplit("moe0_", 1)[1] for k in block.collect_params()]
    assert names == ["router_weight", "router_bias", "w1", "w3", "w2",
                     "counters"]
    with pytest.raises(ValueError, match="shared_gate without"):
        nn.GatedMoE(32, 64, range(8), 4, 24, 0, 64, shared_gate=True)
    w = _moe_weights()
    x = _normal(5, (1, TOKENS, 32))
    text = jax.jit(lambda w, x: _routed(w, x, range(8))).lower(w, x) \
        .as_text(debug_info=True)
    assert "mx_moe_gmm_up" in text and "mx_moe_shared" not in text
    # the block computes what the op computes
    block.initialize(mx.init.Zero())
    for k, p in block.collect_params().items():
        leaf = k.rsplit("moe0_", 1)[1]
        if leaf in w:
            p.set_data(mx.nd.array(w[leaf][:8] if leaf in ("w1", "w3", "w2")
                                   else w[leaf]))
    np.testing.assert_allclose(block(mx.nd.array(x)).asnumpy(),
                               _routed(w, x, range(8))[0], atol=1e-6)


#: sha256 of ``_with_shared``'s lowered text, computed with the function
#: below: cbca6bce...9c69e520 at commit 88c3ea2 (the parent of ISSUE 47) and
#: up to PR 47; since PR 48 the same operations in another order
#: (``_combine`` rounds the routed sum where it forms it, before the shared
#: experts' lines, and ``_dispatch_pooled`` takes the experts' row counts
#: before the gather; 48 tokens are no whole tile: the plain moves), and
#: since PR 49 one name of a private function counted on by one
#: (``silu_109`` for ``silu_108``: ``gated_mlp`` hands its first product to
#: ``kept``, one equation more in the trace and nothing in the text)
PARENT_WITH_SHARED = \
    "8bdcf72290f89f38695aaead7761a7b8e48e00f29321126751548801deba0c88"


def _with_shared():
    w = _moe_weights()
    shared = {"gu": _normal(11, (40, 32), 0.3), "down": _normal(12, (32, 20))}
    x = _normal(5, (1, TOKENS, 32))

    def layer(w, shared, x):
        return seq.gated_moe(
            x, w["router_weight"], w["router_bias"], w["w1"][:8],
            w["w3"][:8], w["w2"][:8], shared["gu"], shared["down"], None,
            expert_ids=tuple(range(8)), top_k=4, buffer_rows=4 * TOKENS,
            scaling=2.0, norm_topk=True)

    return jax.jit(layer).lower(w, shared, x).as_text()


def test_with_shared_experts_the_lowered_text_is_the_parent_s():
    """The option is taken where it is given and nowhere else: a layer
    with shared experts, and no epsilon in its normalisation, lowers to
    the text it had."""
    assert hashlib.sha256(_with_shared().encode()).hexdigest() \
        == PARENT_WITH_SHARED


# -- 64-wide heads under head norms -------------------------------------------
ATT = {"hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 1,
       "head_dim": 64, "norm_eps": 1e-5, "reference_attention_block": 4,
       "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"}}


def _att_weights(sz, seed=0):
    ha, hkv, dh, d = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"], sz["hidden_size"]
    return {"qkv_weight": _normal(seed, ((ha + 2 * hkv) * dh, d), 0.3),
            "q_norm_weight": 1 + _normal(seed + 1, (dh,), 0.2),
            "k_norm_weight": 1 + _normal(seed + 2, (dh,), 0.2),
            "o_weight": _normal(seed + 3, (d, ha * dh), 0.3)}


def _attention(w, x, sz, block=4):
    """``nn.GQAttention``'s arithmetic from the op: projection, attention
    with head norms before a whole-head rotation, output projection."""
    out = seq.causal_gq_attention(
        seq._mm(x, w["qkv_weight"]), w["q_norm_weight"], w["k_norm_weight"],
        num_heads=sz["num_attention_heads"],
        num_kv_heads=sz["num_key_value_heads"], head_dim=sz["head_dim"],
        block=block, rope_theta=sz["rope_parameters"]["rope_theta"],
        eps=sz["norm_eps"])
    return seq._mm(out, w["o_weight"])


def _plain_attention(w, x, sz):
    ref = _reference()
    p = {"l0_" + k: v for k, v in w.items()}
    return jax.vmap(lambda u: ref.attention(sz, p, 0, u, "float32"))(x)


@pytest.mark.parametrize("length", [12, 7])
def test_heads_of_64_under_head_norms_are_the_dense_softmax(length):
    """Four query heads on one key/value head, 64 wide, the blocked
    recurrence in plain JAX against the reference's whole score rows."""
    w = _att_weights(ATT)
    x = _normal(1, (2, length, 48))
    cot = _normal(2, (2, length, 48))
    numerics.agree(lambda w, x: _attention(w, x, ATT),
                   lambda w, x: _plain_attention(w, x, ATT), (w, x), cot,
                   (0, 1), value=Tol(rtol=2e-5, atol=2e-5),
                   grads=Tol(scaled=3e-5))


def test_the_block_at_64_wide_heads_is_the_reference_s():
    """Through ``nn.GQAttention`` as the cell builds it; at 64 the layer
    takes no kernel on any backend (``attn::kernel_sites`` stays 0)."""
    from mxnet_tpu.ops import attn_kernel
    mx.telemetry.gauge(attn_kernel.GAUGE).set(0)
    block = nn.GQAttention(48, num_heads=4, num_kv_heads=1, head_dim=64,
                           block=4, rope_theta=1e6, qk_norm=True,
                           epsilon=1e-5)
    block.initialize(mx.init.Zero())
    w = _att_weights(ATT)
    for k, p in block.collect_params().items():
        # (the block's number counts every GQAttention of the process)
        p.set_data(mx.nd.array(w[k.split("_", 1)[1]]))
    x = _normal(1, (2, 9, 48))
    np.testing.assert_allclose(block(mx.nd.array(x)).asnumpy(),
                               _plain_attention(w, x, ATT), rtol=1e-4,
                               atol=2e-5)
    assert mx.telemetry.gauge(attn_kernel.GAUGE).get() == 0


# -- the shares add up --------------------------------------------------------
def test_eight_shares_of_an_attention_sublayer_add_up():
    """The uncut layer has 32 query heads on 8 key/value heads; share
    ``s`` holds query heads ``4 s .. 4 s + 3`` with key/value head ``s``:
    their rows of the projection and their columns of the output
    product. The eight partial sums are the uncut reference's output."""
    full = dict(ATT, num_attention_heads=32, num_key_value_heads=8,
                head_dim=8)
    w = _att_weights(full, seed=20)
    x = _normal(21, (2, 10, 48))
    dh = 8

    def share(w, s):
        q = w["qkv_weight"][4 * s * dh:(4 * s + 4) * dh]
        k = w["qkv_weight"][(32 + s) * dh:(33 + s) * dh]
        v = w["qkv_weight"][(40 + s) * dh:(41 + s) * dh]
        return dict(w, qkv_weight=jnp.concatenate([q, k, v]),
                    o_weight=w["o_weight"][:, 4 * s * dh:(4 * s + 4) * dh])

    held = dict(full, num_attention_heads=4, num_key_value_heads=1)

    def both(w, x):
        parts = [_attention(share(w, s), x, held) for s in range(8)]
        # a share is its own reference's share too
        mine = _plain_attention(share(w, 3), x, held)
        return sum(parts), parts[3], mine, _plain_attention(w, x, full)

    (total, part, mine, want), _ = numerics.traced(both, (w, x))
    numerics.close(part, mine, Tol(atol=2e-5))
    numerics.close(total, want, Tol(atol=1e-4))


def test_eight_shares_of_an_expert_sublayer_add_up():
    """Expert ids 0-7, 8-15, ... 56-63, the router whole in each: the
    eight routed sums are the uncut reference's layer over all 64
    experts, and every (token, expert) pair was held exactly once."""
    w = _moe_weights(3)
    x = _normal(4, (1, TOKENS, 32))

    def shares(w, x):
        return [_routed(w, x, range(8 * s, 8 * s + 8))[:2]
                for s in range(8)], _plain_experts(w, x)

    (parts, (want, load)), _ = numerics.traced(shares, (w, x))
    total = sum(out[0] for out, _ in parts)
    assert all(float(stats[1]) == 0 for _, stats in parts)    # no overflow
    assert sum(float(stats[0]) for _, stats in parts) == TOKENS * 4 \
        == float(load.sum())
    numerics.close(total, want, Tol(atol=5e-5))


def test_what_every_chip_computes_alike_is_counted_once():
    """A conv layer with its dense MLP through ``PatternLM`` is the
    reference's whole layer: the mixer and the MLP are no share."""
    ref = _reference()
    sz = {"hidden_size": HIDDEN, "conv_L_cache": 3, "norm_eps": 1e-5,
          "intermediate_size": 48, "num_dense_layers": 1,
          "layer_types": ["conv"], "reference_row_block": 8}
    net = PatternLM("CG", 50, HIDDEN, mlp=dict(units=48), epsilon=1e-5)
    net.initialize(mx.init.Normal(0.3))
    p = {}
    for k, param in net.collect_params().items():
        leaf = ref._leaf_of(k)
        if leaf.startswith("l0_"):
            p[leaf] = param.data()._data
    x = _normal(30, (2, 9, HIDDEN))
    h = mx.nd.array(x)
    for unit in list(net.stack._children.values())[:2]:
        h = unit(h)
    want = jax.vmap(lambda u: ref.layer(sz, p, 0, u)[0])(x)
    np.testing.assert_allclose(h.asnumpy(), want, rtol=1e-4, atol=1e-4)
