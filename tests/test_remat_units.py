"""What a recomputation unit keeps (``ops.remat``; ``TrainStep(remat=
"layer")``): the kept values change nothing that is computed, the
backward pass chooses, sorts and multiplies the kept products once (a
gated MLP's 2 f wide first product among them, at every cell's sizes),
the gauges say what the units hold, and a step built without
recomputation is the program it was."""
import collections
import functools
import hashlib
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark"))
import harness  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.ops import remat  # noqa: E402
from jax._src.ad_checkpoint import saved_residuals  # noqa: E402

import test_pattern_lm as lm  # noqa: E402  (the small model and its helpers)

SZ = dict(lm.SZ, hybrid_override_pattern="ME")
KINDS = {"M": "l0", "E": "l1"}


def _trained(mode, steps=3):
    params = lm.ref.random_params(SZ, 7, scale=0.05)
    net = lm._net(SZ)
    lm._load(net, params)
    step = lm._step(net, remat=mode)
    losses = [float(step(mx.nd.array(x), mx.nd.array(y)).asnumpy())
              for x, y in lm._batches(steps, seed=1)]
    return step, net, losses


def _step_jaxpr(step):
    x, y = lm._batches(1, seed=1)[0]
    return jax.make_jaxpr(step._step_jit)(*lm._specs(step, x, y)).jaxpr


def test_kept_values_change_nothing_that_is_computed():
    """Three Adam steps with the units' kept values against three with no
    recomputation at all: a kept value is the value that would have been
    computed again, so the losses and every parameter are the same to the
    last bit."""
    got = {}
    for mode in ("layer", None):
        _, net, losses = _trained(mode)
        got[mode] = (losses, lm._read(net))
    assert got["layer"][0] == got[None][0]
    for leaf, value in got[None][1].items():
        np.testing.assert_array_equal(got["layer"][1][leaf], value, leaf)
    assert len(got[None][1]) > 15 and np.isfinite(got[None][0]).all()


def _recomputed(jaxpr, inside=0, out=None):
    """Primitive -> how often the backward pass of ``jaxpr`` computes it
    again: the equations a differentiated ``remat2`` holds under JAX's
    ``rematted_computation`` scope, in the programs they call too."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        again = inside == 2 or (inside == 1 and "rematted_computation"
                                in str(eqn.source_info.name_stack))
        if again:
            out[eqn.primitive.name] += 1
        backward = eqn.primitive.name == "remat2" \
            and eqn.params["differentiated"]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _recomputed(sub, 2 if again else max(inside, int(backward)),
                        out)
    return out


def test_backward_chooses_sorts_and_multiplies_once(monkeypatch):
    """A unit that keeps its input alone computes everything again, the
    choice of the experts, the sort, every matrix product and every
    norm's reduction included; with ``ops.remat``'s policy none of
    them."""
    step, _, _ = _trained("layer", steps=1)
    now = _recomputed(_step_jaxpr(step))
    monkeypatch.setattr(remat, "POLICY", None)
    step, _, _ = _trained("layer", steps=1)
    bare = _recomputed(_step_jaxpr(step))
    assert bare["top_k"] == 1 and bare["sort"] == 1
    # every product but the units' last ones (which no backward reads):
    # the in-projection and the scan's five, the expert layer's five
    assert bare["scatter-add"] == 1 and bare["dot_general"] == 11
    for dear in ("top_k", "sort", "scatter-add", "dot_general"):
        assert now[dear] == 0, dear
    # three norms' sums of squares (two pre-norms, the gated norm) go;
    # the gates' normalisation and the loads stay
    assert bare["reduce_sum"] - now["reduce_sum"] == 3
    # activations, gates and decay masks are computed again, as before
    for cheap in ("exp", "logistic", "max", "cumsum"):
        assert now[cheap] == bare[cheap] > 0, cheap


# -- the gated MLPs of the five cells that call ``ops.seq.gated_mlp`` ---------
#: (cell, call site): the dense layer's ``nn.GatedMLP`` or the shared experts
#: inside ``gated_moe``, each at the cell's rehearsal sizes
GATED_MLPS = [("ouro-2.6b-train-4k", "dense"),
              ("lfm2-24b-a2b-train-8k", "dense"),
              ("moonlight-16b-a3b-train-8k", "dense"),
              ("moonlight-16b-a3b-train-8k", "shared"),
              ("xing4.0-29b-a4b-train-4k", "dense"),
              ("xing4.0-29b-a4b-train-4k", "shared"),
              ("qwen3-next-80b-a3b-train-8k", "shared")]
gated_mlps = pytest.mark.parametrize("cell,site", GATED_MLPS)


@functools.cache
def _mixers(cell):
    """The mixers of ``cell``'s net as its configuration builds it at the
    rehearsal sizes, and those sizes."""
    cell = harness.load_cell(cell, rehearsal=True)
    layers = cell.model._net(cell.sizes).stack._children.values()
    return [m.mixer for m in layers if hasattr(m, "mixer")], cell.sizes


def _gated_mlp_unit(cell, site, dtype=jnp.bfloat16):
    """The first gated MLP of ``cell`` at that call site: ``op``, a
    function of its input and its weights, ``args`` (seeded, in the cells'
    compute dtype), the ``tokens`` of a step, the MLP's width ``f`` and,
    of an expert layer, its ``attrs`` and whether its shared experts go
    through a gate of their own."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.ops import seq
    mixers, sizes = _mixers(cell)
    kind = nn.GatedMLP if site == "dense" else nn.GatedMoE
    mixer = next(m for m in mixers if isinstance(m, kind))
    rng = np.random.default_rng(len(cell))
    shapes = {k.rsplit("0_", 1)[1]: p.shape
              for k, p in mixer.collect_params().items()}

    def w(leaf):
        return jnp.asarray(0.2 * rng.standard_normal(shapes[leaf]), dtype)

    hidden = shapes["gate_up_weight" if site == "dense"
                    else "router_weight"][1]
    x = jnp.asarray(rng.standard_normal(
        (sizes["batch"], sizes["seq_len"], hidden)), dtype)
    tokens = sizes["batch"] * sizes["seq_len"]
    if site == "dense":
        return types.SimpleNamespace(
            op=seq.gated_mlp, tokens=tokens, f=shapes["down_weight"][1],
            args=(x, w("gate_up_weight"), w("down_weight")), own_gate=False)
    own_gate = "shared_gate_weight" in shapes
    args = (x, w("router_weight"), jnp.zeros(shapes["router_bias"]),
            w("w1"), w("w3"), w("w2"), w("shared_gate_up_weight"),
            w("shared_down_weight"), None) \
        + ((w("shared_gate_weight"),) if own_gate else ())
    return types.SimpleNamespace(
        op=lambda *a: seq.gated_moe(*a, **mixer._attrs)[0], args=args,
        tokens=tokens, f=shapes["shared_down_weight"][1],
        attrs=mixer._attrs, own_gate=own_gate)


def _loss_and_gradients(op, args):
    given = [i for i, a in enumerate(args) if a is not None
             and jnp.issubdtype(a.dtype, jnp.floating)]
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(op(*a).astype(jnp.float32) ** 2),
        argnums=given))(*args)


@gated_mlps
def test_a_kept_gate_and_up_product_changes_nothing_that_is_computed(
        cell, site):
    """The unit around a gated MLP against the same layer with no
    recomputation: the output's loss and every gradient to the last
    bit."""
    mlp = _gated_mlp_unit(cell, site)
    got = _loss_and_gradients(jax.checkpoint(mlp.op, policy=remat.POLICY),
                              mlp.args)
    want = _loss_and_gradients(mlp.op, mlp.args)
    assert np.isfinite(float(want[0])) and float(want[0]) > 0
    moved = 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        moved += bool(np.asarray(b, np.float32).any())
    # no gradient reaches a router's bias, and every other leaf has one
    assert moved == len(jax.tree.leaves(want)) - (site == "shared")


def _products(jaxpr, rows, width, out=0):
    """How many ``dot_general`` of ``jaxpr`` and of the programs it calls
    give ``rows`` rows ``width`` wide."""
    for eqn in jaxpr.eqns:
        aval = eqn.outvars[0].aval
        out += eqn.primitive.name == "dot_general" \
            and aval.shape[-1:] == (width,) and aval.size == rows * width
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out = _products(sub, rows, width, out)
    return out


@gated_mlps
def test_backward_forms_the_gate_and_up_product_once(cell, site):
    """Forward and backward of a unit around a gated MLP hold the 2 f wide
    product once, and the backward multiplies nothing again (but where the
    shared experts go through a gate of their own, ``qwen3_next``'s: the
    gate's one column, and the down product its derivative reads); a unit
    that keeps its input alone forms the wide product twice."""
    mlp = _gated_mlp_unit(cell, site)

    def backward(policy):
        unit = jax.checkpoint(mlp.op, policy=policy)
        return jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
            unit(*a).astype(jnp.float32))))(*mlp.args).jaxpr

    now, bare = backward(remat.POLICY), backward(None)
    assert [_products(j, mlp.tokens, 2 * mlp.f) for j in (now, bare)] == [1, 2]
    assert _recomputed(now)["dot_general"] == 2 * mlp.own_gate
    assert _recomputed(bare)["dot_general"] > 2 * mlp.own_gate
    # the gating and the activation are computed again from the product
    assert _recomputed(now)["logistic"] == _recomputed(bare)["logistic"] > 0


def _held(op, *args):
    """What JAX itself says ``op`` under the units' policy leaves for its
    backward pass beside its arguments, in bytes, and what it names."""
    held = sum(aval.size * aval.dtype.itemsize
               for aval, where in saved_residuals(
                   jax.checkpoint(op, policy=remat.POLICY), *args)
               if "argument" not in where and "constant" not in where)
    return held, remat.kept_bytes(jax.make_jaxpr(op)(*args).jaxpr)


def test_gauges_say_what_the_units_hold():
    _trained("layer", steps=1)
    snap = mx.telemetry.snapshot(prefix="remat::")
    assert snap["remat::units"]["value"] == 2
    saved = {k.rsplit("::", 1)[1]: v["value"] for k, v in snap.items()
             if k.startswith("remat::saved_bytes::")}
    assert len(saved) == 2 and all(v > 0 for v in saved.values())
    # by the shapes (float32 here), a float a row for each norm first.
    # A Mamba-2 layer keeps its in-projection, its convolution, the
    # scan's four inner products (over whole chunks) and its output;
    # an expert layer the router's scores, the threshold of its choice,
    # what the sort gave (rows and their gates), the gathered rows, both
    # grouped products, the combined rows and the shared expert's first
    # product
    t = lm.BATCH * lm.LENGTH
    heads, n = SZ["mamba_num_heads"], SZ["n_groups"] * SZ["ssm_state_size"]
    d_in = heads * SZ["mamba_head_dim"]
    q = SZ["chunk_size"]
    chunks = lm.BATCH * -(-lm.LENGTH // q)
    rows, lat = lm.ROWS, SZ["moe_latent_size"]
    want = {
        "M": 4 * (2 * t + t * (2 * d_in + 2 * n + heads)
                  + t * (d_in + 2 * n) + t * d_in
                  + chunks * (SZ["n_groups"] * q * q + 2 * d_in * n
                              + q * d_in)),
        "E": 4 * (t + t * SZ["router_experts"] + t + 2 * rows + rows * lat
                  + rows * SZ["moe_intermediate_size"] + rows * lat
                  + t * lat + t * lm.ref.shared_columns(SZ)),
    }
    for kind, layer in KINDS.items():
        (got,) = [v for k, v in saved.items() if k.endswith(f"_{layer}_")]
        assert got == want[kind], (kind, got, want[kind])
    # a step built later replaces the gauges
    _trained("layer", steps=1)
    again = mx.telemetry.snapshot(prefix="remat::saved_bytes::")
    assert len(again) == 2 and not set(again) & set(snap)


def test_what_is_named_is_what_jax_holds():
    """Every value an operator names is one its backward pass reads, so
    the bytes named are the bytes held: JAX's own list of what the
    forward leaves behind, layer kind by layer kind. (The gather of the
    expert layer's rows leaves its index, the rows' tokens again, twice:
    2 x rows integers that no name asked for.)"""
    from mxnet_tpu.ops import seq
    rng = np.random.default_rng(0)

    def f(*shape):
        return jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)

    hid, lat, ff, sh, rows = 24, 8, 12, 8, 4 * 30
    held, named = _held(
        lambda *a: seq.latent_moe(
            *a, expert_ids=(0, 1, 2, 3), top_k=3, buffer_rows=rows,
            scaling=2.0, bias_rate=0.01)[0],
        f(2, 15, hid), f(16, hid), jnp.zeros((16,)), f(lat, hid),
        f(hid, lat), f(4, lat, ff), f(4, ff, lat), f(sh, hid), f(hid, sh))
    assert named > 0 and held == named + 2 * rows * 4
    heads, p, n = 2, 8, 4
    d_in = heads * p
    held, named = _held(
        lambda *a: seq.mamba2_mixer(*a, num_heads=heads, head_dim=p,
                                    state_size=n, chunk_size=8),
        f(2, 15, hid), f(2 * d_in + 2 * n + heads, hid),
        f(d_in + 2 * n, 4), f(d_in + 2 * n), f(heads), f(heads), f(heads),
        f(d_in), f(hid, d_in))
    assert named > 0 and held == named
    # attention: its output is read by the projection after it
    w = f(hid, 2 * 8)
    held, named = _held(
        lambda a, w: seq.causal_gq_attention(
            a, num_heads=2, num_kv_heads=1, head_dim=8, block=8) @ w.T,
        f(2, 15, 4 * 8), w)
    assert named > 0 and held == named


@gated_mlps
def test_a_unit_holds_the_gate_and_up_product_and_no_more_of_the_mlp(
        cell, site):
    """``tokens x 2 f`` values in the compute dtype a gated MLP: all a
    dense layer's unit names, and what the shared experts add to an
    expert layer's (the same layer with no shared expert names the rest);
    JAX holds what is named."""
    from mxnet_tpu.ops import seq
    mlp = _gated_mlp_unit(cell, site)
    held, named = _held(mlp.op, *mlp.args)
    rest = unnamed = 0
    if site == "shared":
        rest = remat.kept_bytes(jax.make_jaxpr(lambda *a: seq.routed_moe(
            *a, **mlp.attrs)[0])(*mlp.args[:6]).jaxpr)
        assert rest > 0
        # the gather of the routed rows leaves its index, twice (above)
        unnamed = 2 * mlp.attrs["buffer_rows"] * 4
    assert named - rest \
        == mlp.tokens * 2 * mlp.f * mlp.args[0].dtype.itemsize
    assert held == named + unnamed


def test_kept_is_the_identity_outside_a_unit():
    """No unit, no checkpoint: the step of a net whose operators name
    values holds no trace of the names once lowered."""
    step, _, _ = _trained(None, steps=1)
    x, y = lm._batches(1, seed=1)[0]
    text = step._step_jit.lower(*lm._specs(step, x, y)).as_text()
    assert remat.NAME not in text and "checkpoint" not in text
    assert "optimization_barrier" not in text
    a = jnp.arange(6.0).reshape(2, 3)
    np.testing.assert_array_equal(np.asarray(remat.kept(a)), np.asarray(a))
    assert remat.kept_bytes(jax.make_jaxpr(
        lambda v: remat.kept(v.astype(jnp.bfloat16)) * 2)(a).jaxpr) == 12


#: sha256 of the lowered text below as the PR that cut the ``RNN`` operator's
#: scans down to what is sequential left it (PR 32; f67946b1... from the
#: parent of the PR that brought ``ops.remat``, commit 4c1499b, until then),
#: computed with this function
LSTM_STEP_SHA256 = "fadd698662e942bb9bdb0a691e911ddb16f877c123d04d53178197b31832c5ec"


def _lstm_step_text():
    cell = harness.load_cell("lstm-lm-train", rehearsal=True)
    sizes = cell.sizes
    mx.random.seed(0)       # the step's base key is a constant of its text
    system = cell.model.build(cell.config, sizes, "step",
                              cell.model.make_weights(sizes, 0))
    step = system.step
    step._init_state()
    step._build_step()
    x = jnp.zeros((sizes["batch"], sizes["bptt"]), jnp.int32)
    y = jnp.zeros((sizes["batch"] * sizes["bptt"],), jnp.int32)
    args = (step._pvals, step._opt_state, x, y, step._t_dev,
            jnp.asarray(0.1, jnp.float32))
    return step, args, step._step_jit.lower(*args).as_text()


def test_lstm_lm_step_is_the_program_it_was():
    """``lstm-lm-train`` shares ``TrainStep`` and builds it without
    ``remat``: nothing of the units reaches it. Its lowered step at the
    rehearsal sizes is, to the byte, the text recorded above (a PR that
    means to change this cell's program computes the hash anew)."""
    step, args, text = _lstm_step_text()
    assert step.remat is False
    prims = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            prims[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(step._step_jit)(*args).jaxpr)
    assert prims["remat2"] == 0 and prims["name"] == 0
    assert prims["dot_general"] > 0
    assert hashlib.sha256(text.encode()).hexdigest() == LSTM_STEP_SHA256
