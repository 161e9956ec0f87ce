"""A stack run several times (``nn.HybridLoop``) with rotary heads, gated
MLPs between sandwich norms and an exit gate, built by
``model_zoo.PatternLM(loops=, post_norm=, exit_gate=)`` and trained by
``parallel.TrainStep`` under the exit-weighted loss, against the plain
reference ``tests/reference/ouro.py``: the loss, every leaf's gradient
and three Adam steps; the scanned body against the same stack written out
with tied weights; the lowered step's text over seeds and trip counts;
the exit distribution; what the units of a scanned body keep."""
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "reference"))
import ouro as ref  # noqa: E402
import numerics  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.gluon.model_zoo import PatternLM  # noqa: E402
from mxnet_tpu.ndarray.ndarray import NDArray, _wrap  # noqa: E402
from mxnet_tpu.ops import seq  # noqa: E402
from mxnet_tpu.parallel import TrainStep  # noqa: E402
from mxnet_tpu.parallel.step import (exit_weighted_loss,  # noqa: E402
                                     softmax_ce_loss)

SZ = dict(hidden_size=32, vocab_size=101, intermediate_size=48,
          num_attention_heads=4, num_key_value_heads=4, head_dim=8,
          rms_norm_eps=1e-6, rope_theta=1e6, total_ut_steps=4,
          num_hidden_layers=2, attention_block=8, exit_entropy_beta=0.1)
OPT = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
       "epsilon": 1e-8, "wd": 0.0}
BATCH, LENGTH = 2, 19


def _net(sz=SZ, loops=None, exit_gate=True):
    return PatternLM(
        "*G" * sz["num_hidden_layers"], sz["vocab_size"], sz["hidden_size"],
        attention=dict(num_heads=sz["num_attention_heads"],
                       num_kv_heads=sz["num_key_value_heads"],
                       head_dim=sz["head_dim"], block=sz["attention_block"],
                       rope_theta=sz["rope_theta"]),
        mlp=dict(units=sz["intermediate_size"]), epsilon=sz["rms_norm_eps"],
        post_norm=True,
        loops=sz["total_ut_steps"] if loops is None else loops,
        exit_gate=exit_gate)


_BLOCK_LEAF = {"embedding0_weight": "embed_weight",
               "dense0_weight": "head_weight",
               "rmsnorm0_gamma": "final_norm_weight",
               "exitgate0_weight": "gate_weight",
               "exitgate0_bias": "gate_bias"}
_SUBLAYER_LEAF = {"rmsnorm0_gamma": "norm_weight",
                  "rmsnorm1_gamma": "post_norm_weight"}


def _leaf(name):
    """gluon's ``patternlm0_l3_gatedmlp0_down_weight`` -> the reference's
    ``l1_down_weight``: sublayers ``2 l`` and ``2 l + 1`` are layer ``l``'s
    attention and MLP; nothing for the gate's counters."""
    rest = name.split("_", 1)[1]
    if rest in _BLOCK_LEAF:
        return _BLOCK_LEAF[rest]
    if rest.endswith("counters"):
        return None
    sub, leaf = rest.split("_", 1)
    layer, kind = divmod(int(sub[1:]), 2)
    if leaf in _SUBLAYER_LEAF:
        return f"l{layer}_{('attn', 'mlp')[kind]}_{_SUBLAYER_LEAF[leaf]}"
    return f"l{layer}_{leaf.split('_', 1)[1]}"


def _params(sz=SZ, seed=0):
    """Seeded weights of every leaf, the gate's and the norms' too, so
    that no gradient is zero by construction."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 64)
    out = {}
    for i, (name, shape) in enumerate(ref.param_shapes(sz).items()):
        w = jax.random.normal(keys[i], shape, jnp.float32)
        out[name] = 1.0 + 0.1 * w if name.endswith("norm_weight") \
            else 0.3 * w if name.startswith("gate_") else 0.08 * w
    return out


def _load(net, params):
    net.initialize(mx.init.Zero())
    for name, p in net.collect_params().items():
        if _leaf(name) is not None:
            p.set_data(NDArray(jnp.array(params[_leaf(name)])))
    return net


def _read(net):
    return {_leaf(k): np.asarray(p.data()._data)
            for k, p in net.collect_params().items() if _leaf(k) is not None}


def _batches(n, sz=SZ, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, sz["vocab_size"], (BATCH, LENGTH + 1))
        out.append((ids[:, :-1].astype(np.int32),
                    ids[:, 1:].reshape(-1).astype(np.int32)))
    return out


def _step(net, remat="layer", sz=SZ, compute_dtype=None):
    return TrainStep(net, loss=exit_weighted_loss(sz["exit_entropy_beta"]),
                     optimizer="adam", optimizer_params=dict(OPT),
                     compute_dtype=compute_dtype, remat=remat)


def _specs(step, x, y):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (step._pvals, step._opt_state, jnp.asarray(x), jnp.asarray(y),
         step._t_dev, step._lr_cache[1]))


def _lowered(loops, seed, remat="layer"):
    mx.random.seed(0)       # the step's base key is a constant of its text
    step = _step(_load(_net(loops=loops), _params(seed=seed)), remat)
    x, y = _batches(1)[0]
    step(mx.nd.array(x), mx.nd.array(y))
    return step._step_jit.lower(*_specs(step, x, y)).as_text()


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------
def _reference_steps(params, batches, sz=SZ):
    @jax.jit
    def step(p, m, v, t, x, y):
        loss, g = jax.value_and_grad(
            lambda q: ref.reference_loss(sz, q, x, y))(p)
        return ref.adam_step(OPT, p, m, v, t, g) + (loss, g)

    p = params
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first_grads = [], None
    for i, (x, y) in enumerate(batches):
        p, m, v, loss, g = step(p, m, v, jnp.float32(i + 1), x, y)
        losses.append(float(loss))
        first_grads = first_grads or g
    return losses, first_grads, p


@pytest.mark.parametrize("remat", ["layer", None])
def test_three_adam_steps_agree_with_the_reference(remat):
    """Loops 4, float32: the loss of every step, every leaf's first
    gradient (read off Adam's first moment) and every leaf after three
    steps."""
    params, batches = _params(), _batches(3)
    want_losses, want_grads, want = _reference_steps(params, batches)
    net = _load(_net(), params)
    step = _step(net, remat)
    losses = []
    for i, (x, y) in enumerate(batches):
        losses.append(float(step(mx.nd.array(x), mx.nd.array(y)).asnumpy()))
        if i == 0:
            moments = {_leaf(p.name): np.asarray(s[0]) / (1 - OPT["beta1"])
                       for p, s in zip(step.param_list, step._opt_state)
                       if s}
    np.testing.assert_allclose(losses, want_losses, rtol=2e-6)
    assert set(moments) == set(want_grads)
    for k, g in want_grads.items():
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, k
        np.testing.assert_allclose(moments[k], g, atol=2e-5 * scale,
                                   err_msg=k)
    got = _read(net)
    for k, w in want.items():
        # Adam divides by the root of a second moment that starts at the
        # first gradient's square: where that is tiny the step is the
        # sign of rounding noise, so the leaves are held to a share of
        # the three steps' length
        np.testing.assert_allclose(got[k], w, atol=0.05 * 3
                                   * OPT["learning_rate"], err_msg=k)
        assert float(np.mean(np.abs(got[k] - np.asarray(w)))) \
            < 0.002 * 3 * OPT["learning_rate"], k


def test_bfloat16_step_is_near_the_reference():
    """bfloat16 compute over float32 masters, as the cell runs it."""
    params, (x, y) = _params(), _batches(1)[0]
    want = float(numerics.traced(
        lambda p: ref.reference_loss(SZ, p, x, y), (params,))[0])
    step = _step(_load(_net(), params), compute_dtype="bfloat16")
    got = float(step(mx.nd.array(x), mx.nd.array(y)).asnumpy())
    assert abs(got - want) < 2e-2 * want
    assert all(v.dtype == jnp.float32 for v in step._pvals)


# ---------------------------------------------------------------------------
# the scanned body
# ---------------------------------------------------------------------------
class _WrittenOut(nn.HybridLoop):
    """The same children called ``loops`` times one after another, in
    Python: every call a copy of its own in the program, the weights
    tied."""

    def _scan(self, x):
        every = []
        for _ in range(self._loops):
            x = self._once(x)
            every.append(x._data)
        return x, _wrap(jnp.stack(every))


def _loss_and_grads(net, x, y):
    step = _step(net)
    loss = float(step(mx.nd.array(x), mx.nd.array(y)).asnumpy())
    return loss, {p.name: np.asarray(s[0])
                  for p, s in zip(step.param_list, step._opt_state) if s}


def test_scanned_gradients_equal_the_stack_written_out_with_tied_weights():
    params, (x, y) = _params(), _batches(1)[0]
    looped = _load(_net(), params)
    written = _load(_net(), params)
    written.stack.__class__ = _WrittenOut
    loss, grads = _loss_and_grads(looped, x, y)
    loss_w, grads_w = _loss_and_grads(written, x, y)
    assert looped.stack.body_traces == 1
    assert written.stack.body_traces == SZ["total_ut_steps"]
    np.testing.assert_allclose(loss, loss_w, rtol=1e-6)
    assert len(grads) == len(grads_w) == len(ref.param_shapes(SZ))
    for (k, g), g_w in zip(grads.items(), grads_w.values()):
        np.testing.assert_allclose(g, g_w, rtol=2e-4,
                                   atol=1e-6 * np.abs(g_w).max(), err_msg=k)


def test_lowered_step_is_one_text_for_every_seed():
    assert _lowered(4, seed=0) == _lowered(4, seed=1)


def test_lowered_step_differs_between_two_and_four_trips_by_counts_alone():
    """The program holds one copy of the stack whatever the trip count:
    with every number blotted out, the step of 2 loops is the step of 4."""
    two, four = _lowered(2, seed=0), _lowered(4, seed=0)
    assert two != four
    blot = lambda text: re.sub(r"\d+", "#", text)  # noqa: E731
    assert blot(two) == blot(four)
    assert two.count("stablehlo.while") == four.count("stablehlo.while") > 0


def test_a_child_may_not_write_a_parameter_inside_the_loop():
    loop = nn.HybridLoop(2)
    loop.add(nn.BatchNorm(in_channels=4))
    loop.initialize()
    with mx.autograd.train_mode(), pytest.raises(NotImplementedError,
                                                 match="inside the looped"):
        loop(mx.nd.ones((3, 4)))


def test_one_trip_is_no_loop():
    params = _params()
    net = _load(_net(loops=1), params)
    x, _ = _batches(1)[0]
    text = jax.jit(lambda a: net.stack.last(_wrap(a))._data).lower(
        jnp.zeros((BATCH, LENGTH, SZ["hidden_size"]))).as_text()
    assert "stablehlo.while" not in text
    assert net.stack.body_traces == 1


# ---------------------------------------------------------------------------
# the exit distribution and the loss
# ---------------------------------------------------------------------------
def test_exit_probabilities_sum_to_one_and_match_the_products():
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (3, 50))
    p = np.exp(np.asarray(seq.exit_log_probs(z)))
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    lam = 1 / (1 + np.exp(-np.asarray(z, np.float64)))
    want = np.stack([lam[0], lam[1] * (1 - lam[0]),
                     lam[2] * (1 - lam[0]) * (1 - lam[1]),
                     (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    np.testing.assert_allclose(p, want, rtol=2e-5)
    # far into the tails the logarithms stay finite
    assert np.isfinite(np.asarray(seq.exit_log_probs(
        jnp.array([[80.0], [-80.0]])))).all()


def test_one_loop_reduces_to_softmax_ce_through_pattern_lm():
    """``loops=1``: no gate, ``p(1) = 1``, no entropy: the exit-weighted
    loss of the three outputs is ``softmax_ce`` of the plain model's
    logits, and the two steps train alike."""
    params, batches = _params(), _batches(2)
    plain = _load(_net(loops=1, exit_gate=False), params)
    gated = _load(_net(loops=1), params)
    step_p = TrainStep(plain, loss="softmax_ce", optimizer="adam",
                       optimizer_params=dict(OPT), remat="layer")
    step_g = _step(gated)
    for x, y in batches:
        a = float(step_p(mx.nd.array(x), mx.nd.array(y)).asnumpy())
        b = float(step_g(mx.nd.array(x), mx.nd.array(y)).asnumpy())
        np.testing.assert_allclose(a, b, rtol=1e-6)
    x, y = batches[0]
    logits = plain(mx.nd.array(x))._data
    hidden, gates, head = (o._data for o in gated(mx.nd.array(x)))
    assert gates.shape == (0, BATCH * LENGTH) and hidden.shape[0] == 1
    np.testing.assert_allclose(
        float(seq.exit_weighted_ce(hidden, gates, head, y, beta=0.1)),
        float(softmax_ce_loss(logits, y)), rtol=1e-6)


def test_loop_counters_are_published():
    params, (x, y) = _params(), _batches(1)[0]
    net = _load(_net(), params)
    step = _step(net)
    step(mx.nd.array(x), mx.nd.array(y))
    telemetry.remove("loop::")
    got = nn.publish_loop_counters(net)
    hidden = ref.hidden_states(SZ, params, x)
    p = np.asarray(ref.exit_probs(SZ, params, hidden))
    for t in range(4):
        np.testing.assert_allclose(got[f"loop::exit_mass::{t + 1}"],
                                   p[t].mean(), rtol=1e-4)
    np.testing.assert_allclose(got["loop::expected_steps"],
                               (p * np.arange(1, 5)[:, None]).sum(0).mean(),
                               rtol=1e-4)
    np.testing.assert_allclose(got["loop::gate_entropy"],
                               -(p * np.log(p)).sum(0).mean(), rtol=1e-4)
    assert got["loop::trips"] == 4 and got["loop::stack_traces"] == 1
    snap = telemetry.snapshot()
    assert snap["loop::stack_traces"]["value"] == 1
    # no gradient, no optimizer state, not cast
    (state,) = [s for p, s in zip(step.param_list, step._opt_state)
                if p.name.endswith("exitgate0_counters")]
    assert state == ()


def test_saved_bytes_of_a_scanned_unit_are_trips_times_one_trace():
    (x, y), params = _batches(1)[0], _params()
    per = {}
    for loops in (1, 2, 4):
        telemetry.remove("remat::")
        step = _step(_load(_net(loops=loops), params))
        step(mx.nd.array(x), mx.nd.array(y))
        snap = telemetry.snapshot()
        per[loops] = {k.rsplit("::", 1)[1].split("_", 1)[1]: v["value"]
                      for k, v in snap.items()
                      if k.startswith("remat::saved_bytes::")}
        # inside a scan the final norm is a unit too
        assert snap["remat::units"]["value"] == len(per[loops]) \
            == 2 * SZ["num_hidden_layers"] + (loops > 1)
    assert all(v > 0 for v in per[1].values())
    tokens, d = BATCH * LENGTH, SZ["hidden_size"]
    width = SZ["num_attention_heads"] * SZ["head_dim"]
    for loops in (2, 4):
        final = per[loops].pop("rmsnorm0_")
        assert final == loops * 4 * tokens      # a row's sum of squares
        assert per[loops] == {k: loops * v for k, v in per[1].items()}
    # float32 run, 4 bytes. Attention: q k v, the heads' output, W_o's
    # product and two norms' sums of squares a row
    assert per[1]["l0_"] == 4 * tokens * (3 * width + width + d + 2)
    # gated MLP: the 2 f wide gate-and-up product, W_down's product and
    # two norms' sums; a scanned unit holds ``loops`` times that (above)
    assert per[1]["l1_"] == 4 * tokens * (2 * SZ["intermediate_size"] + d + 2)


# ---------------------------------------------------------------------------
# the operators
# ---------------------------------------------------------------------------
def _finite_difference(fn, x, direction, eps):
    x, direction = np.asarray(x, np.float64), np.asarray(direction,
                                                         np.float64)
    return (float(fn(jnp.asarray(x + eps * direction)))
            - float(fn(jnp.asarray(x - eps * direction)))) / (2 * eps)


@pytest.mark.parametrize("name", ["rope", "gated_mlp"])
def test_gradient_against_finite_differences(name):
    with jax.enable_x64(True):
        keys = jax.random.split(jax.random.PRNGKey(11), 4)
        if name == "rope":
            x = jax.random.normal(keys[0], (2, 7, 3, 8), jnp.float64)
            w = jax.random.normal(keys[1], x.shape, jnp.float64)
            fn = lambda a: jnp.sum(seq.rope(a, theta=50.0) * w)  # noqa: E731
        else:
            x = jax.random.normal(keys[0], (2, 5, 6), jnp.float64)
            gu = 0.5 * jax.random.normal(keys[1], (2 * 9, 6), jnp.float64)
            dn = 0.5 * jax.random.normal(keys[2], (6, 9), jnp.float64)
            fn = lambda a: jnp.sum(jnp.sin(seq.gated_mlp(a, gu, dn)))  # noqa
        fn = jax.jit(fn)        # seven calls of one program
        grad = np.asarray(jax.grad(fn)(x))
        for key in jax.random.split(keys[3], 3):
            direction = np.asarray(jax.random.normal(key, x.shape))
            np.testing.assert_allclose(
                np.sum(grad * direction),
                _finite_difference(fn, x, direction, 1e-3), rtol=5e-4)


def test_rope_is_the_rotation_by_position():
    """Position 0 is left alone, a pair's norm is kept, and the scores of
    rotated queries and keys depend on the distance alone."""
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 2, 8))
    out = np.asarray(seq.rope(q, theta=100.0))
    np.testing.assert_allclose(out[:, 0], np.asarray(q)[:, 0], rtol=1e-6)
    pair = lambda a: a[..., :4] ** 2 + a[..., 4:] ** 2  # noqa: E731
    np.testing.assert_allclose(pair(out), pair(np.asarray(q)), rtol=1e-5)
    same = jnp.broadcast_to(q[:, :1], q.shape)
    r = np.asarray(seq.rope(same, theta=100.0))[0, :, 0]
    scores = r @ r.T
    np.testing.assert_allclose(scores[1, 3], scores[5, 7], rtol=1e-5)
    np.testing.assert_allclose(out[0], np.asarray(ref.rotate(q[0], 100.0)),
                               rtol=1e-5, atol=1e-6)


def test_attention_without_rope_theta_lowers_to_the_text_it_had():
    """No ``rope_theta``, no rotary code: the operator's lowered text
    holds no cosine and is the same whether the argument is left out or
    given as nothing; with it the text changes."""
    qkv = jnp.zeros((1, 16, 3 * 2 * 8), jnp.bfloat16)
    kw = dict(num_heads=2, num_kv_heads=2, head_dim=8, block=8)
    lower = lambda **more: jax.jit(  # noqa: E731
        lambda a: seq.causal_gq_attention(a, **kw, **more)).lower(
            qkv).as_text()
    plain = lower()
    assert plain == lower(rope_theta=None)
    assert "cosine" not in plain and "sine" not in plain
    assert "cosine" in lower(rope_theta=1e4)
