"""``ops.seq.gated_delta_rule`` / ``gated_delta_net`` / ``nn.GatedDeltaNet``:
the gated delta rule in chunks against its token-by-token recurrence,
values and gradients, at lengths that are and are not whole chunks; the
mixer against the plain mixer of the benchmark's reference
(``benchmark/configs/qwen3-next-80b-a3b.py``); what a recomputation unit
around it keeps; ``PatternLM``'s kind ``D``. Nothing here is a time."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import remat, seq

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402
import numerics  # noqa: E402
from numerics import Tol  # noqa: E402


@pytest.fixture(autouse=True)
def _highest_precision():
    """Float32 products at full precision inside these tests only (a
    process-wide setting would change every other file's lowered text)."""
    with jax.default_matmul_precision("highest"):
        yield


SZ = {"hidden_size": 16, "linear_num_key_heads": 2,
      "linear_num_value_heads": 4, "linear_key_head_dim": 8,
      "linear_value_head_dim": 6, "linear_conv_kernel_dim": 4,
      "rms_norm_eps": 1e-6, "reference_scan_block": 5}


def _reference():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.py"))


def _recurrence(q, k, v, beta, g):
    """The rule as it is stated: one token after another."""
    r = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(t, r, axis=2) for t in (q, k))

    def step(state, x):
        q_t, k_t, v_t, b_t, g_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        u_t = b_t[..., None] * (v_t - jnp.einsum("bhnp,bhn->bhp", state, k_t))
        state = state + k_t[..., :, None] * u_t[..., None, :]
        return state, jnp.einsum("bhnp,bhn->bhp", state, q_t)

    bsz, _, h, n = q.shape
    _, out = lax.scan(step, jnp.zeros((bsz, h, n, v.shape[-1])),
                      tuple(jnp.moveaxis(t, 1, 0)
                            for t in (q, k, v, beta, g)))
    return jnp.moveaxis(out, 0, 1)


def _rule_inputs(length, seed=0, decay=3.0):
    rng = np.random.default_rng(seed)
    bsz, gk, h, n, p = 2, 2, 4, 8, 6

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(bsz, length, gk, n))) * n ** -0.5
    k = unit(rng.normal(size=(bsz, length, gk, n)))
    v = rng.normal(size=(bsz, length, h, p))
    beta = 1 / (1 + np.exp(-rng.normal(size=(bsz, length, h))))
    g = -rng.uniform(0, decay, size=(bsz, length, h))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, beta, g))


@pytest.mark.parametrize("length,chunk", [(64, 16), (48, 16), (37, 16),
                                          (5, 8), (130, 64)])
def test_chunked_rule_is_the_recurrence(length, chunk):
    args = _rule_inputs(length, seed=length)
    got, _ = numerics.agree(
        lambda *a: seq.gated_delta_rule(*a, chunk=chunk), _recurrence, args,
        value=Tol(atol=2e-6))
    assert got.dtype == jnp.float32


@pytest.mark.parametrize("length,chunk", [(32, 16), (27, 8)])
def test_chunked_rule_s_gradients_are_the_recurrence_s(length, chunk):
    args = _rule_inputs(length, seed=3)
    weight = jnp.asarray(np.random.default_rng(9).normal(
        size=(2, length, 4, 6)), jnp.float32)
    got, want = (numerics.traced(fn, args, weight, range(5))[1] for fn in (
        lambda *a: seq.gated_delta_rule(*a, chunk=chunk), _recurrence))
    for a, b, name in zip(got, want, "q k v beta g".split()):
        numerics.close(a, b, Tol(scaled=2e-5), name)


def test_a_strong_decay_underflows_to_zero_and_not_to_nan():
    """A head whose decay wipes the state every step (``A = 16``: ``g``
    near -20 a step, -1300 over a chunk) reads ``exp`` of differences
    only, never a quotient of two underflowed numbers."""
    args = _rule_inputs(40, seed=5, decay=25.0)
    got, grads = numerics.traced(lambda *a: jnp.sum(jnp.square(
        seq.gated_delta_rule(*a, chunk=16))), args, 1.0, range(5))
    want, _ = numerics.traced(
        lambda *a: jnp.sum(jnp.square(_recurrence(*a))), args)
    assert np.isfinite(float(got))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_a_padded_tail_writes_nothing():
    """The same first 20 outputs whether 20 steps are given (padded to
    two chunks) or 32."""
    args = _rule_inputs(32, seed=1)
    numerics.agree(
        lambda *a: seq.gated_delta_rule(*(t[:, :20] for t in a), chunk=16),
        lambda *a: seq.gated_delta_rule(*a, chunk=16)[:, :20], args,
        value=Tol(atol=1e-6))


# -- the mixer ----------------------------------------------------------------
def _mixer_weights(seed=0):
    ref = _reference()
    sz = dict(SZ, num_hidden_layers=1, full_attention_interval=4,
              vocab_size=8, head_dim=4, num_attention_heads=2,
              num_key_value_heads=1, moe_intermediate_size=4,
              shared_expert_intermediate_size=4, router_experts=4,
              num_experts=2, expert_ids=[0, 1])
    names = ("qkvz_weight", "ba_weight", "conv_weight", "dt_bias", "a_log",
             "gate_norm_weight", "out_weight")
    shapes = ref.param_shapes(sz)
    rng = np.random.default_rng(seed)
    w = {n: jnp.asarray(0.4 * rng.normal(size=shapes["l0_" + n]), jnp.float32)
         for n in names}
    w["gate_norm_weight"] = 1.0 + 0.1 * w["gate_norm_weight"]
    return sz, w


def _mixer(w, x, chunk=8):
    return seq.gated_delta_net(
        x, *(w[n] for n in ("qkvz_weight", "ba_weight", "conv_weight",
                            "dt_bias", "a_log", "gate_norm_weight",
                            "out_weight")),
        num_k_heads=2, num_v_heads=4, key_dim=8, value_dim=6,
        chunk_size=chunk, eps=1e-6)


def _plain_mixer(sz, w, x):
    ref = _reference()
    p = {"l0_" + k: v for k, v in w.items()}
    return jax.vmap(lambda u: ref.gated_delta_net(sz, p, 0, u, "float32"))(x)


@pytest.mark.parametrize("length", [16, 21])
def test_mixer_is_the_plain_mixer(length):
    sz, w = _mixer_weights()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, length, 16)),
                    jnp.float32)
    tol = Tol(rtol=2e-5, atol=2e-5)
    numerics.agree(_mixer, lambda w, x: _plain_mixer(sz, w, x), (w, x),
                   value=tol)
    # no result depends on the chunk
    numerics.agree(lambda w, x: _mixer(w, x, chunk=4),
                   lambda w, x: _mixer(w, x, chunk=64), (w, x), value=tol)


def test_mixer_s_gradients_are_the_plain_mixer_s():
    sz, w = _mixer_weights(seed=4)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 13, 16)),
                    jnp.float32)
    weight = jnp.asarray(np.random.default_rng(7).normal(size=(2, 13, 16)),
                         jnp.float32)
    got, want = (numerics.traced(fn, (w, x), weight, (0, 1))[1] for fn in (
        _mixer, lambda w, x: _plain_mixer(sz, w, x)))
    numerics.close(got, want, Tol(atol=1e-7, scaled=3e-5))


def test_the_gate_is_not_convolved_and_the_convolution_has_no_bias():
    """Zero convolution weights leave q, k and v at ``silu(0) = 0``: the
    rule writes nothing and the output is zero whatever the gate ``z``,
    which a convolved gate or a bias would not give."""
    _, w = _mixer_weights()
    w = dict(w, conv_weight=jnp.zeros_like(w["conv_weight"]))
    x = jnp.ones((1, 9, 16), jnp.float32)
    assert float(jnp.max(jnp.abs(numerics.traced(_mixer, (w, x))[0]))) == 0.0


def test_a_unit_keeps_both_input_products_and_nothing_of_the_rule():
    _, w = _mixer_weights()
    x = jnp.zeros((2, 24, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda w, x: _mixer(w, x))(w, x).jaxpr
    rows = 2 * 24
    products = rows * (2 * 2 * 8 + 2 * 4 * 6 + 2 * 4) * 4
    # the gated norm's sum of squares a head: one float a row and head
    assert remat.kept_bytes(jaxpr) == products + rows * 4 * 4


def test_block_and_pattern_kind():
    from mxnet_tpu.gluon.model_zoo import PatternLM
    mx.random.seed(3)
    block = nn.GatedDeltaNet(16, num_k_heads=2, num_v_heads=4, key_dim=8,
                             value_dim=6, chunk_size=8)
    block.initialize(mx.init.Normal(0.3))
    names = {n.split("_", 1)[1]: p.shape
             for n, p in block.collect_params().items()}
    assert names == {"qkvz_weight": (80, 16), "ba_weight": (8, 16),
                     "conv_weight": (56, 4), "dt_bias": (4,), "a_log": (4,),
                     "gate_norm_weight": (6,), "out_weight": (16, 24)}
    x = mx.nd.array(np.random.default_rng(0).normal(size=(2, 11, 16))
                    .astype(np.float32))
    w = {n.split("_", 1)[1]: p.data()._data
         for n, p in block.collect_params().items()}
    np.testing.assert_allclose(
        block(x).asnumpy(), numerics.traced(_mixer, (w, x._data))[0],
        rtol=2e-5, atol=2e-5)
    net = PatternLM("D*", 32, 16,
                    linear_attention=dict(num_k_heads=2, num_v_heads=4,
                                          key_dim=8, value_dim=6,
                                          chunk_size=8),
                    attention=dict(num_heads=2, num_kv_heads=1, head_dim=8),
                    epsilon=1e-6, norm_unit_offset=True)
    net.initialize(mx.init.Normal(0.1))
    out = net(mx.nd.array(np.arange(14).reshape(2, 7) % 32))
    assert out.shape == (14, 32) and np.isfinite(out.asnumpy()).all()
    # 1 + w from w = 0 on every layer's norm and the final norm
    gammas = [p for n, p in net.collect_params().items()
              if n.endswith("gamma")]
    assert len(gammas) == 3
    with pytest.raises(ValueError, match=r"M, E, \*, G, L, F, D, C and W are known"):
        PatternLM("X", 32, 16)


def test_the_operator_names_its_parts():
    from mxnet_tpu.ops import registry
    assert registry.get_op("GatedDeltaNet").names_its_parts
    from mxnet_tpu.telemetry import trace
    _, w = _mixer_weights()
    x = jnp.zeros((1, 16, 16), jnp.float32)
    text = jax.jit(jax.grad(lambda w, x: jnp.sum(_mixer(w, x)))).lower(
        w, x).compile().as_text()
    scopes = set(trace.hlo_scopes(text, path=True).values())
    assert {"mx_gdn_proj", "mx_gdn_conv", "mx_gdn_rule",
            "mx_gdn_gate"} <= scopes


def test_block_s_decay_parameters_start_where_the_family_starts_them():
    block = nn.GatedDeltaNet(16, num_k_heads=2, num_v_heads=4, key_dim=8,
                             value_dim=6)
    block.initialize(mx.init.Normal(0.3))
    assert (block.dt_bias.data().asnumpy() == 1).all()      # a bias, at 1
    assert (block.gate_norm_weight.data().asnumpy() == 1).all()
    assert not block.a_log.data().asnumpy().any()
