"""``ops.seq.gated_moe`` / ``nn.GatedMoE``: gated experts on the full
hidden vector on ``latent_moe``'s routing path. The expert body against a
dense loop over experts; the layer against the plain reference
(``benchmark/configs/moonlight-16b-a3b.py``), values and gradients; **the
share test**: the routed parts of all eight shares of a 64-expert layer
plus the shared experts once add up to the uncut reference layer; one
lowered text for every routing; counters, overflow and the balancing step.
Nothing here is a time."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import seq

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402
import numerics  # noqa: E402
from numerics import Tol  # noqa: E402

SZ = {"hidden_size": 32, "moe_intermediate_size": 24, "n_shared_experts": 2,
      "router_experts": 64, "n_routed_experts": 64, "num_experts_per_tok": 6,
      "norm_topk_prob": True, "routed_scaling_factor": 2.446,
      "reference_row_block": 16}
TOKENS = 48


def _reference():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", "moonlight-16b-a3b.py"))


def _weights(seed=0, experts=64):
    d, ff = SZ["hidden_size"], SZ["moe_intermediate_size"]
    shapes = {"router_weight": (64, d), "router_bias": (64,),
              "w1": (experts, d, ff), "w3": (experts, d, ff),
              "w2": (experts, ff, d),
              "shared_gate_up_weight": (4 * ff, d),
              "shared_down_weight": (d, 2 * ff)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    w = {k: 0.3 * jax.random.normal(key, s, jnp.float32)
         for key, (k, s) in zip(keys, shapes.items())}
    w["router_bias"] = 0.05 * w["router_bias"]
    return w


def _layer(w, x, ids, buffer_rows, counters=None, bias_rate=0.0):
    held = jnp.asarray(ids)
    return seq.gated_moe(
        x, w["router_weight"], w["router_bias"], w["w1"][held], w["w3"][held],
        w["w2"][held], w["shared_gate_up_weight"], w["shared_down_weight"],
        counters, expert_ids=tuple(ids), top_k=6, buffer_rows=buffer_rows,
        scaling=SZ["routed_scaling_factor"], norm_topk=True,
        bias_rate=bias_rate)


def _ref_layer(w, x, ids=None):
    ref = _reference()
    sz = dict(SZ) if ids is None else dict(SZ, expert_ids=list(ids))
    held = jnp.arange(64) if ids is None else jnp.asarray(ids)
    p = {"l0_" + k: (v[held] if k in ("w1", "w3", "w2") else v)
         for k, v in w.items()}
    return ref.moe_layer(sz, p, 0, x.reshape(-1, x.shape[-1]), "float32")


@pytest.mark.parametrize("sizes", [(10, 10, 10, 10), (0, 23, 1, 16),
                                   (40, 0, 0, 0)])
def test_pooled_gated_product_is_a_loop_over_experts(sizes):
    """Each expert's rows of the pool, wherever they lie and however
    many: a loop over experts, values and gradients."""
    buf = jax.random.normal(jax.random.PRNGKey(1), (40, 32))
    w = _weights(2, experts=4)
    cot = jax.random.normal(jax.random.PRNGKey(3), buf.shape)
    ends = np.cumsum(sizes)

    def loop(buf, w1, w3, w2):
        rows = []
        for e, (a, b) in enumerate(zip(ends - np.asarray(sizes), ends)):
            x = buf[a:b]
            rows.append((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
        return jnp.concatenate(rows)

    def pooled(buf, w1, w3, w2):
        return seq.pooled_gated_product(buf, w1, w3, w2,
                                        jnp.asarray(sizes, jnp.int32))

    with jax.default_matmul_precision("highest"):
        numerics.agree(pooled, loop, (buf, w["w1"], w["w3"], w["w2"]), cot,
                       (0, 1, 2, 3), value=Tol(atol=1e-5),
                       grads=Tol(atol=2e-5))


def test_the_shares_add_up_to_the_uncut_layer():
    """Expert ids 0-7, 8-15, ... 56-63, each share with its routed part
    alone (shared experts' weights zero), plus the shared experts once:
    the reference's layer over all 64 experts."""
    w = _weights(3)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, TOKENS, 32))
    no_shared = dict(w, shared_down_weight=jnp.zeros_like(
        w["shared_down_weight"]))

    def shares(w, no_shared, x):
        """Each share's ``(out, stats, its reference's share)``."""
        return [(*_layer(no_shared, x, ids, 8 * TOKENS)[:2],
                 _ref_layer(no_shared, x, ids)[0])
                for ids in (list(range(8 * share, 8 * share + 8))
                            for share in range(8))], \
            seq.gated_mlp(x, w["shared_gate_up_weight"],
                          w["shared_down_weight"])[0], _ref_layer(w, x)

    with jax.default_matmul_precision("highest"):
        (parts, shared, (want, load)), _ = numerics.traced(
            shares, (w, no_shared, x))
    total = jnp.zeros((TOKENS, 32))
    held_pairs = 0.0
    for out, stats, part in parts:
        assert float(stats[1]) == 0          # no pair beyond a buffer
        held_pairs += float(stats[0])
        total = total + out[0]
        # one share is its own reference's share too
        np.testing.assert_allclose(out[0], part, atol=2e-5)
    total = total + shared
    np.testing.assert_allclose(total, want, atol=5e-5)
    # every (token, expert) pair was held by exactly one share
    assert held_pairs == TOKENS * 6 == float(load.sum())


@pytest.mark.parametrize("ids", [(0, 1, 2, 3, 4, 5, 6, 7), (5, 17, 40, 63)])
def test_layer_and_gradients_are_the_reference_s(ids):
    w = _weights(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, TOKENS // 2, 32))
    cot = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    held = jnp.asarray(ids)

    def got(w, x):
        return jnp.sum(_layer(w, x, list(ids), len(ids) * TOKENS)[0] * cot)

    def want(w, x):
        return jnp.sum(_ref_layer(w, x, ids)[0].reshape(x.shape) * cot)

    with jax.default_matmul_precision("highest"):
        a, b = (numerics.traced(fn, (w, x), 1.0, (0, 1)) for fn in (got, want))
    np.testing.assert_allclose(a[0], b[0], rtol=1e-5)
    for key in w:
        ga, gb = a[1][0][key], b[1][0][key]
        if key in ("w1", "w3", "w2"):
            # the layer was handed the held experts' slices
            ga, gb = ga[held], gb[held]
            assert not np.asarray(a[1][0][key]).sum() == 0
        if key == "router_bias":        # no gradient reaches the bias
            assert not np.asarray(ga).any()
            continue
        np.testing.assert_allclose(ga, gb, atol=3e-5 * float(
            jnp.abs(gb).max() + 1e-9), err_msg=key)
    np.testing.assert_allclose(a[1][1], b[1][1], atol=3e-5)


def test_lowered_text_is_one_for_every_routing():
    """The whole pool is computed whatever the routing
    (``grouped_product``'s rule): the program is a function of shapes."""
    texts = set()
    for seed in (0, 1):
        w = _weights(seed)
        x = jax.random.normal(jax.random.PRNGKey(seed), (1, TOKENS, 32))
        texts.add(jax.jit(jax.grad(lambda w, x: jnp.sum(_layer(
            w, x, list(range(8)), 64)[0]))).lower(w, x).as_text())
    assert len(texts) == 1


def test_pairs_beyond_the_buffer_are_counted_and_add_up():
    w = _weights(8)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, TOKENS, 32))
    ids = list(range(8))
    _, roomy, _ = _layer(w, x, ids, 8 * TOKENS)
    out, tight, _ = _layer(w, x, ids, 16)           # a pool of 16 rows
    held = float(roomy[0])
    assert float(roomy[1]) == 0 and float(tight[0]) == held > 16
    assert float(tight[1]) == held - 16 and float(tight[3]) == 1.0
    assert float(roomy[3]) == pytest.approx(held / (8 * TOKENS))
    again = _layer(w, x, ids, 16, counters=tight)[1]
    assert float(again[1]) == 2 * float(tight[1])
    assert np.isfinite(np.asarray(out)).all()


def test_the_held_experts_share_one_pool():
    """An expert may draw far more than an equal slice of the rows: a
    pair is beyond the buffer only when the held experts' pairs together
    outnumber its rows, and then the first ``buffer_rows`` pairs, expert
    by expert and token by token, are the ones computed."""
    w = _weights(11)
    # every token chooses expert 0 first: 48 pairs on it, where equal
    # slices of the pool would give an expert 12 rows
    w["router_bias"] = w["router_bias"].at[0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, TOKENS, 32))
    ids = list(range(8))
    w0 = dict(w, w2=w["w2"].at[1:8].set(0.0))
    with jax.default_matmul_precision("highest"):
        ((out, stats, _), want, (only, tight, _), alone), _ = numerics.traced(
            lambda w, w0, x: (
                _layer(w, x, ids, 8 * 12), _ref_layer(w, x, ids)[0],
                # a pool of 48 rows holds expert 0's pairs whole and no other
                _layer(w, x, ids, TOKENS), _ref_layer(w0, x, ids)[0]),
            (w, w0, x))
    held = float(stats[0])
    assert TOKENS < held <= 8 * 12 and float(stats[1]) == 0
    np.testing.assert_allclose(out[0], want, atol=2e-5)
    assert float(tight[1]) == held - TOKENS
    np.testing.assert_allclose(only[0], alone, atol=2e-5)


def test_block_moves_its_bias_when_training_and_publishes_counters():
    mx.random.seed(0)
    block = nn.GatedMoE(32, num_experts=64, expert_ids=range(8), top_k=6,
                        expert_units=24, shared_units=48,
                        buffer_rows=8 * TOKENS, scaling=2.446,
                        bias_update_rate=0.003)
    block.initialize(mx.init.Zero())
    rng = np.random.default_rng(0)
    for name, p in block.collect_params().items():
        if not name.endswith(("router_bias", "counters")):
            p.set_data(mx.nd.array(0.3 * rng.standard_normal(p.shape)))
    x = mx.nd.array(np.asarray(jax.random.normal(jax.random.PRNGKey(10),
                                                 (1, TOKENS, 32))))
    names = {n.split("_", 1)[1] for n in block.collect_params()}
    assert names == {"router_weight", "router_bias", "w1", "w3", "w2",
                     "shared_gate_up_weight", "shared_down_weight",
                     "counters"}
    before = block.router_bias.data().asnumpy().copy()
    block(x)
    np.testing.assert_array_equal(block.router_bias.data().asnumpy(), before)
    with autograd.record():
        block(x)
    moved = block.router_bias.data().asnumpy() - before
    step = np.abs(moved)
    assert np.all((step < 1e-7) | (np.abs(step - 0.003) < 1e-6)) \
        and moved.any()
    gauges = nn.publish_moe_counters(block)
    (held,) = [v for k, v in gauges.items() if "pairs_held" in k]
    assert 0 < held <= TOKENS * 6
    assert [v for k, v in gauges.items() if "overflow_pairs" in k] == [0.0]


def test_latent_moe_keeps_its_names_and_its_scopes():
    """``LatentMoE``'s parameters are what they were, and both bodies'
    matrix products stand under ``mx_moe_gmm_*``."""
    block = nn.LatentMoE(32, num_experts=16, expert_ids=range(4), top_k=3,
                         latent_units=8, expert_units=24, shared_units=12,
                         buffer_rows=64)
    assert {n.split("_", 1)[1] for n in block.collect_params()} == {
        "router_weight", "router_bias", "down_weight", "up_weight", "w1",
        "w2", "shared_w1", "shared_w2", "counters"}
    w = _weights(11)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, TOKENS, 32))
    text = jax.jit(lambda w, x: _layer(w, x, list(range(8)), 64)[0]).lower(
        w, x).compile().as_text()
    for scope in ("mx_moe_gmm_up", "mx_moe_gmm_down", "mx_moe_score",
                  "mx_moe_route", "mx_moe_dispatch", "mx_moe_combine",
                  "mx_moe_shared"):
        assert scope in text, scope
    assert "mx_moe_latent" not in text


# -- a softmax router and a shared expert behind a gate of its own ------------
QSZ = {"hidden_size": 32, "moe_intermediate_size": 24,
       "shared_expert_intermediate_size": 20, "router_experts": 8,
       "num_experts": 8, "num_experts_per_tok": 3, "norm_topk_prob": True,
       "reference_row_block": 16}


def _qwen_reference():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.py"))


def _qwen_weights(seed=0):
    d, ff, fs = 32, 24, 20
    shapes = {"router_weight": (8, d), "w1": (8, d, ff), "w3": (8, d, ff),
              "w2": (8, ff, d), "shared_gate_up_weight": (2 * fs, d),
              "shared_down_weight": (d, fs), "shared_gate_weight": (1, d)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {k: 0.3 * jax.random.normal(key, s, jnp.float32)
            for key, (k, s) in zip(keys, shapes.items())}


def _qwen_layer(w, x, ids, counters=None):
    held = jnp.asarray(ids)
    return seq.gated_moe(
        x, w["router_weight"], jnp.zeros((8,)), w["w1"][held], w["w3"][held],
        w["w2"][held], w["shared_gate_up_weight"], w["shared_down_weight"],
        counters, w["shared_gate_weight"], expert_ids=tuple(ids), top_k=3,
        buffer_rows=3 * TOKENS, norm_topk=True, scoring="softmax")


def _qwen_ref_layer(w, x, ids=None):
    sz = dict(QSZ) if ids is None else dict(QSZ, expert_ids=list(ids))
    held = jnp.arange(8) if ids is None else jnp.asarray(ids)
    p = {"l0_" + k: (v[held] if k in ("w1", "w3", "w2") else v)
         for k, v in w.items()}
    return _qwen_reference().moe_layer(sz, p, 0, x.reshape(-1, 32),
                                       "float32")


def test_softmax_router_takes_the_largest_and_normalises_them():
    w = _qwen_weights(1)
    u = jax.random.normal(jax.random.PRNGKey(2), (TOKENS, 32))
    gate, chosen = seq.route(u, w["router_weight"], jnp.zeros((8,)), 3, 1.0,
                             True, "softmax")
    p = jax.nn.softmax(u @ w["router_weight"].T, axis=-1)
    top = jnp.argsort(-p, axis=-1)[:, :3]
    want = jnp.zeros_like(p).at[jnp.arange(TOKENS)[:, None], top].set(
        jnp.take_along_axis(p, top, axis=-1))
    np.testing.assert_allclose(gate, want / want.sum(-1, keepdims=True),
                               rtol=1e-5)
    assert (np.asarray(chosen).sum(-1) == 3).all()
    np.testing.assert_allclose(gate.sum(-1), 1.0, rtol=1e-5)
    ref_gate, ref_chosen = _qwen_reference().router(
        QSZ, {"l0_router_weight": w["router_weight"]}, 0, u, "float32")
    np.testing.assert_allclose(gate, ref_gate, rtol=1e-5)
    np.testing.assert_array_equal(chosen, ref_chosen)
    # the sigmoid router of the same weights chooses by other scores
    other, _ = seq.route(u, w["router_weight"], jnp.zeros((8,)), 3, 1.0, True)
    assert float(jnp.max(jnp.abs(other - gate))) > 1e-3


def test_shared_expert_goes_through_a_gate_of_its_own():
    w = _qwen_weights(3)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, TOKENS, 32))
    none = dict(w, w2=jnp.zeros_like(w["w2"]))      # the routed part zero

    def want(w, x):
        mlp = seq.gated_mlp(x, w["shared_gate_up_weight"],
                            w["shared_down_weight"])[0]
        return jax.nn.sigmoid(x[0] @ w["shared_gate_weight"].T) * mlp

    with jax.default_matmul_precision("highest"):
        numerics.agree(lambda w, x: _qwen_layer(w, x, range(8))[0][0], want,
                       (none, x), value=Tol(atol=2e-6))


def test_qwen_shares_add_up_to_the_uncut_layer():
    """The routed parts of 4 shares of 2 of 8 experts (ids 0-1, 2-3, 4-5,
    6-7, each with its shared expert's weights zero), plus the gated
    shared expert once, are the reference's layer over all 8 experts."""
    w = _qwen_weights(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, TOKENS, 32))
    no_shared = dict(w, shared_down_weight=jnp.zeros_like(
        w["shared_down_weight"]))
    whole = dict(w, w2=jnp.zeros_like(w["w2"]))

    def shares(w, no_shared, whole, x):
        """Each share's ``(out, stats, its reference's share)``."""
        return [(*_qwen_layer(no_shared, x, ids)[:2],
                 _qwen_ref_layer(no_shared, x, ids))
                for ids in ([2 * share, 2 * share + 1]
                            for share in range(4))], \
            _qwen_layer(whole, x, range(8))[0][0], _qwen_ref_layer(w, x)

    with jax.default_matmul_precision("highest"):
        (parts, shared, want), _ = numerics.traced(
            shares, (w, no_shared, whole, x))
    total = jnp.zeros((TOKENS, 32))
    held_pairs = 0.0
    for out, stats, part in parts:
        assert float(stats[1]) == 0          # no pair beyond the pool
        held_pairs += float(stats[0])
        total = total + out[0]
        # one share is its own reference's share too
        np.testing.assert_allclose(out[0], part, atol=2e-5)
    total = total + shared
    np.testing.assert_allclose(total, want, atol=5e-5)
    # every (token, expert) pair was held by exactly one share
    assert held_pairs == TOKENS * 3


def test_qwen_layer_s_gradients_are_the_reference_s():
    w = _qwen_weights(7)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, TOKENS // 2, 32))
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    ids = (1, 4, 6)
    held = jnp.asarray(ids)

    def got(w, x):
        return jnp.sum(_qwen_layer(w, x, list(ids))[0] * cot)

    def want(w, x):
        return jnp.sum(_qwen_ref_layer(w, x, ids).reshape(x.shape) * cot)

    with jax.default_matmul_precision("highest"):
        a, b = (numerics.traced(fn, (w, x), 1.0, (0, 1))[1]
                for fn in (got, want))
    for name in w:
        ga, gb = a[0][name], b[0][name]
        if name in ("w1", "w3", "w2"):      # the held experts' alone
            assert not np.asarray(ga)[np.setdiff1d(np.arange(8), ids)].any()
            ga, gb = ga[held], gb[held]
        np.testing.assert_allclose(ga, gb, atol=3e-5 * max(float(
            jnp.max(jnp.abs(gb))), 1e-3), err_msg=name)
    np.testing.assert_allclose(a[1], b[1], atol=3e-5)


def test_block_s_options_leave_the_old_block_as_it_was():
    old = nn.GatedMoE(32, 8, [0, 1], 3, 24, 20, 64)
    assert "scoring" not in old._attrs
    assert not any(n.endswith("shared_gate_weight")
                   for n in old.collect_params())
    new = nn.GatedMoE(32, 8, [0, 1], 3, 24, 20, 3 * TOKENS, scoring="softmax",
                      shared_gate=True)
    new.initialize(mx.init.Zero())
    assert new._attrs["scoring"] == "softmax"
    params = {n.split("_", 1)[1]: p for n, p in new.collect_params().items()}
    assert params["shared_gate_weight"].shape == (1, 32)
    rng = np.random.default_rng(2)
    for name, p in params.items():
        if name not in ("counters", "router_bias"):
            p.set_data(mx.nd.array(0.3 * rng.normal(size=p.shape)
                                   .astype(np.float32)))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, TOKENS, 32))
    w = {k: p.data()._data for k, p in params.items()}
    want = seq.gated_moe(
        x, w["router_weight"], w["router_bias"], w["w1"], w["w3"], w["w2"],
        w["shared_gate_up_weight"], w["shared_down_weight"], None,
        w["shared_gate_weight"], expert_ids=(0, 1), top_k=3,
        buffer_rows=3 * TOKENS, scoring="softmax")[0]
    np.testing.assert_allclose(new(mx.nd.array(x)).asnumpy(), want,
                               rtol=1e-5, atol=1e-6)
