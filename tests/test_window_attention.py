"""``ops.seq.causal_gq_attention`` / ``nn.GQAttention`` with the ``laguna``
family's options: a window of keys behind the diagonal, one sigmoid gate a
head on the output, YaRN's frequencies on a part of the head with its
attention factor on the rotated part. Each against the plain attention of
the benchmark's reference (``benchmark/configs/laguna-s-2.1.py``), value
and every gradient; the window's two edges to the token; the fused kernels
interpreted under a window that crosses blocks, the forward, the fused
backward and the backward by side, at groups of 6 and 9 query heads a
key/value head; and one chip's shares of a sliding, a full and an expert
sublayer added up to the uncut layers. Nothing here is a time."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import PatternLM
from mxnet_tpu.ops import attn_kernel, seq

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


def _reference():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", "laguna-s-2.1.py"))


#: the published rotation groups at a small head: a rotation that turns
#: over tens of positions, YaRN's ramp inside the rotated half
ROPE = {"full_attention": {"rope_theta": 400, "rope_type": "yarn",
                           "factor": 8,
                           "original_max_position_embeddings": 16,
                           "beta_slow": 1, "beta_fast": 4,
                           "attention_factor": 1.4852030263919618,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                              "partial_rotary_factor": 1}}


def _sizes(heads, kv_heads, window, head_dim=8, hidden=24, block=4):
    """One sliding layer (0) and one full layer (1) of ``heads`` query
    heads each."""
    return {"hidden_size": hidden, "head_dim": head_dim,
            "num_key_value_heads": kv_heads,
            "num_attention_heads_per_layer": [heads, heads],
            "layer_types": ["sliding_attention", "full_attention"],
            "sliding_window": window, "rope_parameters": ROPE,
            "rms_norm_eps": 1e-6, "reference_attention_block": block}


def _weights(sz, seed=0, dtype=jnp.float32):
    h, hkv, dh, d = sz["num_attention_heads_per_layer"][0], \
        sz["num_key_value_heads"], sz["head_dim"], sz["hidden_size"]
    rng = np.random.default_rng(seed)
    return {"qkv_weight": jnp.asarray(0.3 * rng.normal(
                size=((h + 2 * hkv) * dh + h, d)), dtype),
            "o_weight": jnp.asarray(0.3 * rng.normal(size=(d, h * dh)),
                                    dtype)}


def _kw(sz, i, **more):
    """``causal_gq_attention``'s attributes for layer ``i`` of ``sz``, as
    ``nn.GQAttention`` makes them from the configuration's group."""
    sliding = sz["layer_types"][i] == "sliding_attention"
    group = sz["rope_parameters"][sz["layer_types"][i]]
    kw = dict(num_heads=sz["num_attention_heads_per_layer"][i],
              num_kv_heads=sz["num_key_value_heads"],
              head_dim=sz["head_dim"], block=4,
              rope_theta=group["rope_theta"], head_gate=True)
    if sliding:
        kw["window"] = sz["sliding_window"]
    else:
        kw["rotary_dim"] = int(sz["head_dim"]
                               * group["partial_rotary_factor"])
        kw.update(nn.seq_layers._yarn_attrs(group))
    kw.update(more)
    return kw


def _layer(sz, i, w, x, **more):
    """The block's arithmetic from the op: projection, attention, output
    projection."""
    out = seq.causal_gq_attention(seq._mm(x, w["qkv_weight"]),
                                  **_kw(sz, i, **more))
    return seq._mm(out, w["o_weight"])


def _plain(sz, i, w, x):
    ref = _reference()
    p = {f"l{i}_" + k: v.astype(jnp.float32) for k, v in w.items()}
    return jax.vmap(lambda u: ref.attention(
        sz, p, i, u.astype(jnp.float32), "float32"))(x)


def _inputs(sz, length, seed=1, batch=2, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    shape = (batch, length, sz["hidden_size"])
    return (jnp.asarray(rng.normal(size=shape), dtype),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


# -- the window against the reference's explicit mask ---------------------------
F32 = dict(value=Tol(rtol=2e-5, atol=2e-6), grads=Tol(scaled=3e-5))
#: bfloat16 rows and weights against the float32 reference on the same
#: rounded numbers: the products' and the probabilities' rounding
BF16 = dict(value=Tol(scaled=2e-2), grads=Tol(scaled=4e-2))


@pytest.mark.parametrize("length,window,heads,kv_heads,dtype", [
    (23, 5, 6, 1, jnp.float32),     # below the length, no multiple of 4
    (23, 8, 9, 1, jnp.float32),     # two blocks
    (23, 1, 6, 2, jnp.float32),     # itself alone
    (24, 24, 6, 1, jnp.float32),    # at the length: no key is left out
    (12, 40, 9, 1, jnp.float32),    # above it
    (23, 7, 9, 1, jnp.bfloat16),
    (23, 12, 6, 1, jnp.bfloat16)])
def test_window_attention_is_the_reference_s(length, window, heads, kv_heads,
                                             dtype):
    sz = _sizes(heads, kv_heads, window)
    w = _weights(sz, dtype=dtype)
    x, cot = _inputs(sz, length, dtype=dtype)
    numerics.agree(lambda w, x: _layer(sz, 0, w, x).astype(jnp.float32),
                   lambda w, x: _plain(sz, 0, w, x), (w, x), cot, (0, 1),
                   **(F32 if dtype == jnp.float32 else BF16))


def test_the_float32_tolerances_fail_bfloat16():
    """The tolerances above are no wider than the mistake they are there
    for: the same layer computed in bfloat16 misses them."""
    sz = _sizes(6, 1, 5)
    w = _weights(sz)
    x, cot = _inputs(sz, 23)

    def rounded(w, x):
        return _layer(sz, 0, {k: v.astype(jnp.bfloat16) for k, v in
                              w.items()},
                      x.astype(jnp.bfloat16)).astype(jnp.float32)

    with pytest.raises(AssertionError):
        numerics.agree(rounded, lambda w, x: _plain(sz, 0, w, x), (w, x),
                       cot, (0, 1), **F32)


def test_a_window_that_reaches_the_length_is_none():
    """The same lowered text as without one, and the text with the new
    arguments at their defaults is the text without them."""
    data = jax.ShapeDtypeStruct((2, 24, (4 + 2 * 2) * 8), jnp.float32)

    def text(**kw):
        return jax.jit(lambda d: seq.causal_gq_attention(
            d, num_heads=4, num_kv_heads=2, head_dim=8, block=8,
            rope_theta=100.0, **kw)).lower(data).as_text()

    plain = text()
    assert text(window=24) == text(window=99) == plain
    assert text(window=None, head_gate=False, yarn=None, mscale=None) \
        == plain
    assert text(window=23) != plain
    assert "mx_swa_fwd" not in plain


# -- the window's two edges, to the token --------------------------------------
def _packed(length, heads, kv_heads, head_dim, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(
        size=(2, length, (heads + 2 * kv_heads) * head_dim)), jnp.float32)


def _edges(attend, data, heads, kv_heads, head_dim, window, t):
    """Output ``t`` with key and value ``t - window`` perturbed, and with
    key and value ``t - window + 1`` perturbed, beside the output as it
    is."""
    keys = slice(heads * head_dim, (heads + 2 * kv_heads) * head_dim)
    base = attend(data)

    def moved(j):
        return attend(data.at[:, j, keys].add(1.5))

    return base[:, t], moved(t - window)[:, t], moved(t - window + 1)[:, t]


@pytest.mark.parametrize("length,window,t,block", [
    (23, 5, 11, 4), (23, 8, 22, 4), (23, 9, 16, 8), (23, 4, 4, 4)])
def test_the_window_s_edges_are_exact_to_the_token(length, window, t, block):
    data = _packed(length, 6, 2, 8)
    attend = jax.jit(lambda d: seq.causal_gq_attention(
        d, num_heads=6, num_kv_heads=2, head_dim=8, block=block,
        window=window))
    base, outside, inside = _edges(attend, data, 6, 2, 8, window, t)
    np.testing.assert_array_equal(np.asarray(outside), np.asarray(base))
    assert float(jnp.max(jnp.abs(inside - base))) > 1e-3


def test_the_kernels_edges_are_exact_to_the_token(kernels_here):  # noqa: F811
    """The same through the interpreted kernels, with the edge inside a
    block, on a block's first row and on its last."""
    length, window = 384, 130
    data = _packed(length, 2, 1, 128, seed=4)
    attend = jax.jit(lambda d: seq.causal_gq_attention(
        d, num_heads=2, num_kv_heads=1, head_dim=128, window=window))
    for t in (200, 256, 383, 130):
        base, outside, inside = _edges(attend, data, 2, 1, 128, window, t)
        np.testing.assert_array_equal(np.asarray(outside), np.asarray(base))
        assert float(jnp.max(jnp.abs(inside - base))) > 1e-4, t


# -- the kernels, interpreted, against the blocked recurrence -------------------
def _rows(length, hq, hk, dtype, seed=5, batch=2):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(batch, length, h * 128)),
                             dtype) for h in (hq, hk, hk, hq))


@pytest.mark.parametrize("length,hq,hk,window,blocks,by_side,dtype", [
    (512, 6, 1, 200, (128,), False, jnp.float32),    # three blocks a band
    (512, 9, 1, 200, (128,), True, jnp.float32),
    (384, 6, 2, 130, (128,), True, jnp.float32),     # the edge off a tile
    (300, 2, 1, 77, (128,), False, jnp.float32),     # a padded length
    (512, 2, 1, 300, (256, 128), False, jnp.float32),
    (512, 4, 2, 64, (256,), True, jnp.float32),      # the block over the window
    (512, 4, 2, 64, (256,), False, jnp.float32),
    (512, 9, 1, 256, (128,), False, jnp.bfloat16),   # a whole block in the band
    (512, 6, 1, 256, (128,), True, jnp.bfloat16)])
def test_kernels_under_a_window_are_the_blocked_recurrence(
        length, hq, hk, window, blocks, by_side, dtype, monkeypatch):
    """Forward, the fused backward and the backward by side (reached by
    lowering the limit, as a long sequence reaches it by the rule)."""
    monkeypatch.setattr(attn_kernel, "_WINDOW_BLOCKS", blocks)
    if by_side:
        monkeypatch.setattr(attn_kernel, "_RESIDENT_LIMIT_BYTES", 0)
    q, k, v, dout = _rows(length, hq, hk, dtype)
    scale = 128 ** -0.5

    def kernels(q, k, v):
        out, lse = attn_kernel.forward(q, k, v, hq, hk, scale,
                                       interpret=True, window=window)
        grads = attn_kernel.backward(q, k, v, out, lse, dout.astype(dtype),
                                     hq, hk, scale, interpret=True,
                                     window=window)
        return out, lse, grads

    def plain(q, k, v):
        (out, lse), vjp = jax.vjp(lambda q, k, v: seq._blocked_rows(
            q, k, v, hq, hk, scale, 128, None, window), q, k, v)
        return out, lse, vjp((dout.astype(dtype), jnp.zeros_like(lse)))

    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    numerics.agree(kernels, plain, (q, k, v), value=numerics.kernel_tol(tol))


def test_a_window_site_is_counted_and_its_grid_is_the_band():
    """Lowered for a TPU without one: the window's kernels by name, the
    three gauges, the by-side backward at the cell's nine heads a group
    and 8192 rows and the fused one at six."""
    def lowered(hq, hk, window, length=8192):
        for gauge in (attn_kernel.GAUGE, attn_kernel.FUSED_BWD_GAUGE,
                      attn_kernel.WINDOW_GAUGE):
            mx.telemetry.gauge(gauge).set(0)
        data = jax.ShapeDtypeStruct((1, length, (hq + 2 * hk) * 128 + hq),
                                    jnp.bfloat16)
        text = jax.jit(jax.grad(lambda d: jnp.sum(seq.causal_gq_attention(
            d, num_heads=hq, num_kv_heads=hk, head_dim=128, head_gate=True,
            window=window).astype(jnp.float32)))).trace(data).lower(
                lowering_platforms=("tpu",)).as_text()
        return text, tuple(mx.telemetry.gauge(g).get() for g in (
            attn_kernel.GAUGE, attn_kernel.FUSED_BWD_GAUGE,
            attn_kernel.WINDOW_GAUGE))

    text, counted = lowered(36, 4, 512)
    assert counted == (1, 0, 1)
    for kernel in ("attn_swa_fwd_kernel", "attn_swa_bwd_dq_kernel",
                   "attn_swa_bwd_dkv_kernel"):
        assert text.count(f'kernel_name = "{kernel}"') == 1, kernel
    assert 'kernel_name = "attn_fwd_kernel"' not in text
    text, counted = lowered(24, 4, None)
    assert counted == (1, 1, 0)
    assert "attn_swa" not in text
    assert text.count('kernel_name = "attn_fwd_kernel"') \
        == text.count('kernel_name = "attn_bwd_kernel"') == 1
    # a short sequence's dQ fits: the fused backward under the window
    text, counted = lowered(36, 4, 512, length=2048)
    assert counted == (1, 1, 1) and "attn_swa_bwd_kernel" in text
    # the band: blocks of 512 under a window of 512 meet two key blocks
    assert attn_kernel.block_size(8192, 512) == (512, 8192)
    assert attn_kernel.block_size(2304, 512) == (256, 2304)
    assert attn_kernel._band(512, 256) == 3 and attn_kernel._band(512, 128) \
        == 5 and attn_kernel._band(512, 512) == 2 \
        and attn_kernel._band(1, 128) == 1
    assert attn_kernel.block_size(8192) == (1024, 8192)


def test_the_op_takes_the_kernels_under_a_window(kernels_here):  # noqa: F811
    """``causal_gq_attention`` through its TPU branch (kernels interpreted)
    against its plain branch: value and the packed rows' gradient, gate
    and rotation around the kernels."""
    rng = np.random.default_rng(6)
    data = jnp.asarray(rng.normal(size=(1, 384, (6 + 2) * 128 + 6)),
                       jnp.float32)
    cot = jnp.asarray(rng.normal(size=(1, 384, 6 * 128)), jnp.float32)

    def op(d):
        return seq.causal_gq_attention(
            d, num_heads=6, num_kv_heads=1, head_dim=128, block=128,
            rope_theta=1e4, head_gate=True, window=150)

    got = numerics.traced(op, (data,), cot, (0,))
    with pytest.MonkeyPatch.context() as plain:
        plain.setattr(lax, "platform_dependent",
                      lambda *args, tpu, default: default(*args))
        want = numerics.traced(op, (data,), cot, (0,))
    numerics.close(got, want, numerics.kernel_tol(2e-5))


# -- the gate a head ------------------------------------------------------------
@pytest.mark.parametrize("layer,length", [(0, 13), (1, 13)])
def test_head_gate_is_the_reference_s(layer, length):
    """A sliding and a full layer, each under its own rotation; and a
    gate read from another head's row is another layer."""
    sz = _sizes(6, 2, 5)
    w = _weights(sz, seed=7)
    x, cot = _inputs(sz, length, seed=8)
    numerics.agree(lambda w, x: _layer(sz, layer, w, x),
                   lambda w, x: _plain(sz, layer, w, x), (w, x), cot,
                   (0, 1), **F32)
    gates = (6 + 2 * 2) * 8
    swapped = dict(w, qkv_weight=w["qkv_weight"].at[gates:].set(
        jnp.roll(w["qkv_weight"][gates:], 1, axis=0)))
    with pytest.raises(AssertionError):
        numerics.agree(lambda w, x: _layer(sz, layer, swapped, x),
                       lambda w, x: _plain(sz, layer, w, x), (w, x),
                       value=F32["value"])


def test_head_gate_is_one_number_a_head():
    """All of a head's elements go through the same gate: with the gates'
    rows at zero every output is half the ungated one."""
    sz = _sizes(6, 2, 5)
    w = _weights(sz, seed=9)
    x, _ = _inputs(sz, 11, seed=10)
    gates = (6 + 2 * 2) * 8
    ungated = dict(w, qkv_weight=w["qkv_weight"][:gates])
    halves = dict(w, qkv_weight=w["qkv_weight"].at[gates:].set(0.0))
    numerics.agree(lambda x: _layer(sz, 0, halves, x),
                   lambda x: 0.5 * _layer(sz, 0, ungated, x,
                                          head_gate=False), (x,),
                   value=Tol(rtol=1e-6, atol=1e-7))
    block = nn.GQAttention(24, 6, 2, head_dim=8, head_gate=True, window=5)
    assert block.qkv_weight.shape == (gates + 6, 24)
    assert block._attrs["window"] == 5 and block._attrs["head_gate"] is True
    with pytest.raises(ValueError, match="one gate"):
        nn.GQAttention(24, 6, 2, head_dim=8, head_gate=True, gated=True)


# -- YaRN on half a head, its factor on the rotated half ------------------------
#: the published group of a full-attention layer
PUBLISHED = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
             "original_max_position_embeddings": 8192, "beta_slow": 1,
             "beta_fast": 32, "attention_factor": 1.4852030263919618,
             "partial_rotary_factor": 0.5}


def test_rotation_is_the_reference_s_written_out_one():
    ref = _reference()
    attrs = nn.seq_layers._yarn_attrs(PUBLISHED)
    assert attrs == {"yarn": (128.0, 8192.0, 32.0, 1.0),
                     "mscale": 1.4852030263919618}
    assert attrs["mscale"] == pytest.approx(0.1 * np.log(128) + 1)
    np.testing.assert_allclose(
        seq.rope_frequencies(64, 5e5, attrs["yarn"]),
        ref.frequencies(64, PUBLISHED), rtol=1e-6)
    # YaRN moves the slow pairs alone, and by the factor at most
    plain = seq.rope_frequencies(64, 5e5)
    turned = seq.rope_frequencies(64, 5e5, attrs["yarn"])
    assert turned[0] == plain[0] and turned[-1] == pytest.approx(
        plain[-1] / 128, rel=1e-6)
    x = jnp.asarray(np.random.default_rng(11).normal(size=(2, 300, 3, 128)),
                    jnp.float32)
    got = seq.rope(x, 5e5, 64, attrs["yarn"], attrs["mscale"])
    want = jax.vmap(lambda u: ref.rotate(u, PUBLISHED))(x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the other half goes through as it is, unscaled; the rotated half
    # carries the factor: position 0 turns by nothing
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    np.testing.assert_allclose(got[:, 0, :, :64],
                               x[:, 0, :, :64] * attrs["mscale"], rtol=1e-6)
    # without the factor it is another rotation
    assert float(jnp.max(jnp.abs(
        seq.rope(x, 5e5, 64, attrs["yarn"]) - want))) > 0.1
    # a sliding layer's group: the whole head at base 10,000, no factor
    sliding = {"rope_type": "default", "rope_theta": 10000,
               "partial_rotary_factor": 1}
    np.testing.assert_allclose(
        seq.rope(x, 1e4), jax.vmap(lambda u: ref.rotate(u, sliding))(x),
        rtol=2e-5, atol=2e-5)


def test_rope_without_the_new_arguments_is_the_text_it_was():
    x = jax.ShapeDtypeStruct((2, 9, 3, 16), jnp.float32)
    plain = jax.jit(lambda x: seq.rope(x, 50.0, 8)).lower(x).as_text()
    assert jax.jit(lambda x: seq.rope(x, 50.0, 8, None, None)).lower(
        x).as_text() == plain
    assert jax.jit(lambda x: seq.rope(x, 50.0, 8, mscale=1.5)).lower(
        x).as_text() != plain


# -- the shares add up ------------------------------------------------------------
def _uncut():
    """A small ``laguna``: 2 key/value heads under 12 (sliding) and 8
    (full) query heads, 32 experts, what 2 head shares and 32 expert
    shares divide."""
    return {"hidden_size": 32, "head_dim": 8, "num_key_value_heads": 2,
            "num_attention_heads_per_layer": [12, 8],
            "layer_types": ["sliding_attention", "full_attention"],
            "mlp_layer_types": ["sparse", "sparse"],
            "sliding_window": 6, "rope_parameters": ROPE,
            "rms_norm_eps": 1e-6, "reference_attention_block": 4,
            "reference_row_block": 8, "moe_intermediate_size": 24,
            "shared_expert_intermediate_size": 16, "router_experts": 32,
            "num_experts": 32, "num_experts_per_tok": 10,
            "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5}


def _head_share(sz, w, i, share, shares=2):
    """Share ``share`` of layer ``i``'s heads: its query heads with their
    key/value heads and gates, and their columns of ``W_o``."""
    h, hkv, dh = sz["num_attention_heads_per_layer"][i], \
        sz["num_key_value_heads"], sz["head_dim"]
    hs, ks = h // shares, hkv // shares
    rows = np.r_[share * hs * dh:(share + 1) * hs * dh,
                 h * dh + share * ks * dh:h * dh + (share + 1) * ks * dh,
                 (h + hkv) * dh + share * ks * dh:
                 (h + hkv) * dh + (share + 1) * ks * dh,
                 (h + 2 * hkv) * dh + share * hs:
                 (h + 2 * hkv) * dh + (share + 1) * hs]
    cut = dict(sz, num_key_value_heads=ks, num_attention_heads_per_layer=[
        hs if j == i else n
        for j, n in enumerate(sz["num_attention_heads_per_layer"])])
    return cut, {f"l{i}_qkv_weight": w[f"l{i}_qkv_weight"][rows],
                 f"l{i}_o_weight": w[f"l{i}_o_weight"][
                     :, share * hs * dh:(share + 1) * hs * dh]}


@pytest.mark.parametrize("layer", [0, 1])
def test_two_head_shares_add_up_to_the_uncut_attention(layer):
    """The op on each half of the heads (heads ``0..h/2`` on key/value
    head 0, the rest on 1, six and four a key/value head as whole) gives
    partial sums of the output projection that add up to the reference's
    uncut sublayer."""
    ref, sz = _reference(), _uncut()
    shapes = {k: s for k, s in ref.param_shapes(
        dict(sz, num_hidden_layers=2, vocab_size=8,
             intermediate_size=8)).items() if k.startswith(f"l{layer}_")}
    rng = np.random.default_rng(12)
    w = {k: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
         for k, s in shapes.items()}
    u = jnp.asarray(rng.normal(size=(14, 32)), jnp.float32)
    whole = ref.attention(sz, w, layer, u, "float32")
    parts = []
    for share in range(2):
        cut, mine = _head_share(sz, w, layer, share)
        parts.append(_layer(cut, layer, {k[3:]: v for k, v in mine.items()},
                            u[None])[0])
        # the reference on the same share gives the same partial sum
        np.testing.assert_allclose(
            parts[-1], ref.attention(cut, mine, layer, u, "float32"),
            rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(parts[0] + parts[1], whole, rtol=2e-5,
                               atol=5e-6)


def test_thirty_two_expert_shares_add_up_to_the_uncut_expert_layer():
    """``nn.GatedMoE`` told which one of 32 experts it holds, 32 times,
    with the shared expert and the router's choice counted once, against
    the reference's layer over all 32."""
    ref, sz = _reference(), _uncut()
    rng = np.random.default_rng(13)
    shapes = {k: s for k, s in ref.param_shapes(
        dict(sz, num_hidden_layers=1, vocab_size=8,
             intermediate_size=8)).items() if k.startswith("l0_")}
    w = {k: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
         for k, s in shapes.items()}
    u = jnp.asarray(rng.normal(size=(20, 32)), jnp.float32)
    whole = ref.moe_layer(sz, w, 0, u, "float32")
    shared = ref.gated_mlp(sz, u, w["l0_shared_gate_up_weight"],
                           w["l0_shared_down_weight"], "float32")
    total = jnp.zeros_like(whole)
    counters = jnp.zeros(len(nn.MOE_COUNTERS), jnp.float32)
    bias = jnp.zeros(32, jnp.float32)
    for e in range(32):
        out, _, _ = seq.gated_moe(
            u[None], w["l0_router_weight"], bias, w["l0_w1"][e:e + 1],
            w["l0_w3"][e:e + 1], w["l0_w2"][e:e + 1],
            w["l0_shared_gate_up_weight"], w["l0_shared_down_weight"],
            counters, expert_ids=(e,), top_k=10, buffer_rows=24,
            scaling=2.5, norm_topk=True, scoring="softmax")
        total = total + (out[0] - shared)       # what every chip adds alike
    np.testing.assert_allclose(total + shared, whole, rtol=2e-5, atol=1e-5)
    # every token went to 10 of the 32: the routed part is not nothing
    assert float(jnp.max(jnp.abs(whole - shared))) > 1e-2


# -- the pattern's second kind of attention -------------------------------------
def test_pattern_letter_w_is_attention_of_its_own():
    net = PatternLM(
        "*GWF", 50, 24, mlp=dict(units=48),
        attention=dict(num_heads=4, num_kv_heads=2, head_dim=8,
                       rope_theta=400.0, rotary_dim=4, head_gate=True,
                       rope_scaling=ROPE["full_attention"]),
        window_attention=dict(num_heads=6, num_kv_heads=2, head_dim=8,
                              rope_theta=100.0, head_gate=True, window=5),
        experts=dict(num_experts=8, expert_ids=[0, 1], top_k=3,
                     expert_units=16, shared_units=16, buffer_rows=32,
                     scaling=2.5, scoring="softmax"))
    mixers = [layer.mixer for layer in net.stack._children.values()
              if hasattr(layer, "mixer")]
    assert [type(m).__name__ for m in mixers] == [
        "GQAttention", "GatedMLP", "GQAttention", "GatedMoE"]
    full, sliding = mixers[0], mixers[2]
    assert "window" not in full._attrs and sliding._attrs["window"] == 5
    assert full._attrs["yarn"] == (8.0, 16.0, 4.0, 1.0) \
        and full._attrs["mscale"] == pytest.approx(1.4852030263919618)
    assert "yarn" not in sliding._attrs
    assert full.qkv_weight.shape == ((4 + 4) * 8 + 4, 24)
    assert sliding.qkv_weight.shape == ((6 + 4) * 8 + 6, 24)
    net.initialize(mx.init.Zero())
    for name, p in net.collect_params().items():
        if p.grad_req != "null":
            p.set_data(mx.nd.array(0.1 * np.random.default_rng(
                len(name)).normal(size=p.shape)))
    out = net(mx.nd.array(np.arange(22).reshape(2, 11) % 50))
    assert out.shape == (22, 50) and np.isfinite(out.asnumpy()).all()
    assert float(np.abs(out.asnumpy()).max()) > 0
