"""``TrainStep``'s cross entropy (``parallel.step.softmax_ce_loss``).

The label's log-probability is picked by a compare and a masked row sum:
a gather over the logits makes XLA materialise them row-major (on the
v5e, 40 % of the LM's step went into producing that one scalar: PERF.md
section 6, PR 27). These tests hold the value and the gradient to the
gather form written out here, and the traced program to holding no
gather over the logits.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.gluon import HybridBlock, nn
from mxnet_tpu.parallel import TrainStep
from mxnet_tpu.parallel.step import softmax_ce_loss

import numerics


def _gather_form(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.mean(picked)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _indexed(jaxpr):
    return [e for e in _eqns(jaxpr)
            if "gather" in e.primitive.name or "scatter" in e.primitive.name]


@pytest.mark.parametrize("label_dtype", [np.int32, np.float32])
@pytest.mark.parametrize("logit_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,classes", [(24, 10), (16, 333)])
def test_value_and_gradient_equal_the_gather_form(rows, classes, logit_dtype,
                                                  label_dtype):
    rs = np.random.RandomState(rows + classes)
    logits = jnp.asarray(4.0 * rs.randn(rows, classes), logit_dtype)
    labels = jnp.asarray(rs.randint(0, classes, (rows,)).astype(label_dtype))
    labels = labels.at[0].set(0).at[1].set(classes - 1)
    (got, got_g), (want, want_g) = (
        numerics.traced(fn, (logits, labels), 1.0, 0)
        for fn in (softmax_ce_loss, _gather_form))
    assert got.dtype == jnp.float32 and got_g.dtype == logit_dtype
    np.testing.assert_allclose(got, want, rtol=3e-7, atol=0)
    # one step of the gradient's own dtype
    np.testing.assert_allclose(
        np.asarray(got_g, np.float32), np.asarray(want_g, np.float32),
        rtol=float(jnp.finfo(logit_dtype).eps), atol=1e-9)


def test_label_out_of_range_picks_nothing():
    logits = jnp.asarray(np.random.RandomState(0).randn(4, 7), jnp.float32)
    # the stray row's loss: log-sum-exp of its logits less their maximum
    stray_loss = jax.nn.logsumexp(logits[3] - jnp.max(logits[3]))
    inside = softmax_ce_loss(logits[:3], jnp.asarray([1, 6, 0]))
    for stray in (7, -1):
        labels = jnp.asarray([1, 6, 0, stray])
        loss, grad = numerics.traced(softmax_ce_loss, (logits, labels), 1.0,
                                     0)
        np.testing.assert_allclose(
            loss, (3 * inside + stray_loss) / 4, rtol=1e-6)
        np.testing.assert_allclose(
            grad[3], jax.nn.softmax(logits[3]) / 4, rtol=1e-6)


def test_no_gather_and_no_scatter_in_value_and_grad():
    logits = jnp.zeros((12, 333), jnp.bfloat16)
    labels = jnp.zeros((12,), jnp.int32)
    assert _indexed(jax.make_jaxpr(
        jax.value_and_grad(_gather_form))(logits, labels).jaxpr)
    assert not _indexed(jax.make_jaxpr(
        jax.value_and_grad(softmax_ce_loss))(logits, labels).jaxpr)


def test_train_step_gathers_only_the_embedding():
    """The LM cell's shape: ``Dense(flatten=False)`` + ``reshape((-1, V))``
    into ``softmax_ce``. The embedding's lookup and its gradient's
    scatter-add are the program's only indexed reads and writes."""
    vocab, embed, batch, steps = 37, 8, 4, 5

    class TinyLM(HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.embed = nn.Embedding(vocab, embed)
                self.decoder = nn.Dense(vocab, flatten=False, in_units=embed)

        def hybrid_forward(self, F, x):
            return self.decoder(self.embed(x)).reshape((-1, vocab))

    mx.random.seed(7)
    net = TinyLM()
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, loss="softmax_ce", optimizer="sgd",
                     optimizer_params={"momentum": 0.9}, lr=0.1)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randint(0, vocab, (batch, steps)), jnp.int32)
    y = jnp.asarray(rs.randint(0, vocab, (batch * steps,)), jnp.int32)
    first = float(step(x, y).asscalar())
    assert np.isfinite(first) and abs(first - np.log(vocab)) < 1.0
    traced = jax.make_jaxpr(step._step_jit)(
        step._pvals, step._opt_state, x, y, step._t_dev,
        jnp.asarray(0.1, jnp.float32))
    indexed = _indexed(traced.jaxpr)
    assert {e.primitive.name for e in indexed} == {"gather", "scatter-add"}
    for e in indexed:
        assert e.invars[0].aval.shape == (vocab, embed), e
