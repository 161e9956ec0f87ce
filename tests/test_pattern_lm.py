"""The pattern-built language model (gluon.model_zoo.PatternLM) through
parallel.TrainStep against the plain reference: the 11-layer model's loss
and first Adam steps, recomputation by layer, the net's own buffers as
the step's, state without a gradient kept out of the compute dtype, the
routers' bias moved by the balancing rule every step, the expert layers'
counters and the scopes of the compiled program."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "reference"))
import nemotron_h as ref  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.gluon.block import HybridBlock  # noqa: E402
from mxnet_tpu.gluon.model_zoo import PatternLM  # noqa: E402
from mxnet_tpu.ndarray.ndarray import NDArray  # noqa: E402
from mxnet_tpu.parallel import TrainStep  # noqa: E402

SZ = dict(hidden_size=64, vocab_size=211,
          hybrid_override_pattern="MEMEMEMEM*E", mamba_num_heads=4,
          mamba_head_dim=16, n_groups=1, ssm_state_size=16, conv_kernel=4,
          chunk_size=8, num_attention_heads=4, num_key_value_heads=1,
          head_dim=16, moe_latent_size=32, moe_intermediate_size=48,
          router_experts=16, n_routed_experts=4, expert_ids=[0, 1, 2, 3],
          num_experts_per_tok=3, moe_shared_expert_intermediate_size=64,
          moe_shared_expert_shards=2, routed_scaling_factor=5.0,
          norm_topk_prob=True, norm_eps=1e-5, router_bias_update_rate=0.01)
OPT = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
       "epsilon": 1e-8, "wd": 0.0}
BATCH, LENGTH, ROWS = 2, 21, 4 * 42


def _net(sz=SZ, rows=ROWS, bias_rate=SZ["router_bias_update_rate"]):
    return PatternLM(
        sz["hybrid_override_pattern"], sz["vocab_size"], sz["hidden_size"],
        mamba=dict(num_heads=sz["mamba_num_heads"],
                   head_dim=sz["mamba_head_dim"],
                   state_size=sz["ssm_state_size"],
                   num_groups=sz["n_groups"], chunk_size=sz["chunk_size"]),
        moe=dict(num_experts=sz["router_experts"],
                 expert_ids=sz["expert_ids"], top_k=sz["num_experts_per_tok"],
                 latent_units=sz["moe_latent_size"],
                 expert_units=sz["moe_intermediate_size"],
                 shared_units=ref.shared_columns(sz), buffer_rows=rows,
                 scaling=sz["routed_scaling_factor"],
                 bias_update_rate=bias_rate),
        attention=dict(num_heads=sz["num_attention_heads"],
                       num_kv_heads=sz["num_key_value_heads"],
                       head_dim=sz["head_dim"], block=8))


def _leaf(name):
    rest = name.split("_", 1)[1]
    for block, leaf in (("embedding", "embed_weight"),
                        ("dense", "head_weight"),
                        ("rmsnorm", "final_norm_weight")):
        if rest.startswith(block):
            return leaf
    layer, block, leaf = rest.split("_", 2)
    if block.startswith("rmsnorm"):
        return f"{layer}_norm_weight"
    return None if leaf == "counters" else f"{layer}_{leaf}"


def _load(net, params):
    net.initialize(mx.init.Zero())
    for name, p in net.collect_params().items():
        if _leaf(name) is not None:
            p.set_data(NDArray(jnp.array(params[_leaf(name)])))


def _read(net):
    return {_leaf(n): p.data().asnumpy() for n, p in
            net.collect_params().items() if _leaf(n) is not None}


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, SZ["vocab_size"], (n, BATCH, LENGTH + 1))
    return [(ids[i, :, :-1].astype(np.int32),
             ids[i, :, 1:].reshape(-1).astype(np.int32)) for i in range(n)]


def _reference_steps(params, batches):
    @jax.jit
    def step(p, m, v, t, x, y):
        (loss, loads), g = jax.value_and_grad(
            lambda q: ref.reference_loss(SZ, q, x, y), has_aux=True)(p)
        p, m, v = ref.adam_step(OPT, p, m, v, t, g)
        return ref.balance_step(SZ, p, loads), m, v, loss

    p = params
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, history = [], []
    for i, (x, y) in enumerate(batches):
        p, m, v, loss = step(p, m, v, jnp.float32(i + 1), x, y)
        losses.append(float(loss))
        history.append(p)
    return losses, history


def _step(net, **kw):
    opt = dict(OPT)
    return TrainStep(net, loss="softmax_ce", optimizer="adam",
                     optimizer_params=opt, **kw)


def _specs(step, x, y):
    """The step program's arguments as shapes, after a call."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (step._pvals, step._opt_state, jnp.asarray(x), jnp.asarray(y),
         step._t_dev, step._lr_cache[1]))


def _distance(got, want, start):
    num = sum(float(np.sum(np.square(
        (got[k] - start[k]) - (np.asarray(want[k]) - start[k]))))
        for k in start)
    den = sum(float(np.sum(np.square(np.asarray(want[k]) - start[k])))
              for k in start)
    return (num / den) ** 0.5


@pytest.fixture(scope="module")
def reference_run():
    params = ref.random_params(SZ, 11, scale=0.05)
    batches = _batches(3)
    losses, history = _reference_steps(params, batches)
    return params, batches, losses, history


@pytest.mark.parametrize("remat", ["layer", None])
def test_model_loss_and_first_adam_steps(reference_run, remat):
    params, batches, want_losses, history = reference_run
    start = {k: np.asarray(v) for k, v in params.items()}
    net = _net()
    _load(net, params)
    step = _step(net, remat=remat)
    losses = []
    for i, (x, y) in enumerate(batches):
        losses.append(float(step(mx.nd.array(x), mx.nd.array(y)).asnumpy()))
        if i == 0:
            first = _read(net)
    last = _read(net)
    np.testing.assert_allclose(losses, want_losses, rtol=2e-6)
    assert _distance(first, history[0], start) < 2e-3
    assert _distance(last, history[2], start) < 5e-3
    # no gradient reaches the routers' bias; every step moves it one step
    # of the balancing rule on its own loads, as the reference does
    for i in (1, 3, 5, 7, 10):
        moved = first[f"l{i}_router_bias"] - start[f"l{i}_router_bias"]
        assert np.isin(np.round(np.abs(moved) * 100, 3), [0, 1]).all()
        assert np.abs(moved).sum() > 0
        np.testing.assert_array_equal(first[f"l{i}_router_bias"],
                                      np.asarray(history[0][f"l{i}_router_bias"]))
        np.testing.assert_allclose(last[f"l{i}_router_bias"],
                                   np.asarray(history[2][f"l{i}_router_bias"]),
                                   atol=1e-6)


@pytest.mark.parametrize("rate,training", [(0.0, True), (0.01, False)])
def test_bias_stays_without_a_rate_and_outside_training(reference_run, rate,
                                                        training):
    params, batches, _, _ = reference_run
    net = _net(bias_rate=rate)
    _load(net, params)
    x, y = batches[0]
    if training:
        _step(net, remat="layer")(mx.nd.array(x), mx.nd.array(y))
    else:
        net(mx.nd.array(x))
    got = _read(net)
    for i in (1, 3, 5, 7, 10):
        np.testing.assert_array_equal(
            got[f"l{i}_router_bias"], np.asarray(params[f"l{i}_router_bias"]))


def test_training_forwards_alone_balance_a_skewed_router():
    """A router that favours some experts is brought to balance by the
    layer's own training forwards: the bias moves against the loads it
    sees, and the largest load over the mean falls to where one batch's
    noise leaves it."""
    rng = np.random.default_rng(5)
    layer = nn.LatentMoE(32, num_experts=16, expert_ids=(0, 1), top_k=3,
                         latent_units=8, expert_units=8, shared_units=8,
                         buffer_rows=2 * 256, bias_update_rate=0.005)
    layer.initialize(mx.init.Zero())
    for p in (layer.down_weight, layer.up_weight, layer.w1, layer.w2,
              layer.shared_w1, layer.shared_w2):
        p.set_data(mx.nd.array(
            0.1 * rng.standard_normal(p.shape).astype(np.float32)))
    skew = np.exp(rng.standard_normal((16, 1))).astype(np.float32)
    layer.router_weight.set_data(mx.nd.array(
        0.3 * skew * rng.standard_normal((16, 32)).astype(np.float32)))
    x = mx.nd.array(rng.standard_normal((2, 128, 32)).astype(np.float32))

    def load_max_over_mean():
        return nn.publish_moe_counters(layer)[
            "moe::load_max_over_mean::" + layer.counters.name.rsplit("_", 1)[0]]

    with mx.autograd.train_mode():
        layer(x)
        before = load_max_over_mean()
        for _ in range(400):
            layer(x)
        after = load_max_over_mean()
    assert before > 1.8 and after < 1.2, (before, after)
    assert np.abs(layer.router_bias.data().asnumpy()).max() > 0.1


def test_the_step_takes_the_net_s_own_buffers(reference_run):
    params, batches, _, _ = reference_run
    net = _net()
    _load(net, params)
    before = net.collect_params()
    first = [p for n, p in before.items() if n.endswith("dense0_weight")][0]
    held = first.data()
    step = _step(net)
    step._materialize(mx.nd.array(batches[0][0]))
    step._init_state()
    i = step.param_list.index(first)
    assert step._pvals[i] is held._data            # no second copy
    step(mx.nd.array(batches[0][0]), mx.nd.array(batches[0][1]))
    # the net points at the step's new buffer; the wrapper the user holds
    # is the parameter's own and follows it
    assert first.data()._data is step._pvals[i]
    assert np.isfinite(held.asnumpy()).all()
    assert not np.array_equal(held.asnumpy(),
                              np.asarray(params["head_weight"]))
    # nothing is left for sync_params to do
    now = held.asnumpy()
    step.sync_params()
    assert first.data()._data is step._pvals[i]
    np.testing.assert_array_equal(held.asnumpy(), now)


def test_state_without_gradient_stays_out_of_the_compute_dtype(
        reference_run):
    params, batches, want_losses, _ = reference_run
    net = _net()
    _load(net, params)
    step = _step(net, compute_dtype="bfloat16", remat="layer")
    x, y = batches[0]
    loss = float(step(mx.nd.array(x), mx.nd.array(y)).asnumpy())
    assert abs(loss - want_losses[0]) < 2e-3 * want_losses[0]
    counters = nn.publish_moe_counters(net)
    held = [v for k, v in counters.items()
            if k.startswith("moe::pairs_held::")]
    assert len(held) == 5
    # a bfloat16 count of 126 pairs would read 126 too, of 8192 it would
    # not: the counters and the bias are float32 in the program
    text = step._step_jit.lower(*_specs(step, x, y)).as_text()
    assert "tensor<16xf32>) -> tensor<16xbf16>" not in text     # the bias
    assert all(v == float(int(v)) and 0 < v <= BATCH * LENGTH * 3
               for v in held)
    assert mx.telemetry.snapshot(prefix="moe::overflow_pairs::")


def test_overflow_adds_up_across_steps_and_layers(reference_run):
    params, batches, _, _ = reference_run
    net = _net(rows=4 * 4)                  # four rows an expert: too few
    _load(net, params)
    step = _step(net, remat="layer")
    x, y = batches[0]
    step(mx.nd.array(x), mx.nd.array(y))
    one = nn.publish_moe_counters(net)
    step(mx.nd.array(x), mx.nd.array(y))
    two = nn.publish_moe_counters(net)
    over = [k for k in one if k.startswith("moe::overflow_pairs::")]
    assert len(over) == 5 and all(one[k] > 0 for k in over)
    assert all(two[k] > one[k] for k in over)
    fill = [v for k, v in two.items()
            if k.startswith("moe::buffer_fill::")]
    assert all(0 < v <= 1 for v in fill)


class _Unit(HybridBlock):
    _remat_unit = True

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.dense = nn.Dense(8, in_units=8)
            self.bn = nn.BatchNorm(in_channels=8)

    def hybrid_forward(self, F, x):
        return x + self.bn(self.dense(x))


class _Stack(HybridBlock):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.a, self.b = _Unit(), _Unit()
            self.out = nn.Dense(3, in_units=8)

    def hybrid_forward(self, F, x):
        return self.out(self.b(self.a(x)))


@pytest.mark.parametrize("remat", ["layer", True])
def test_remat_keeps_values_and_batchnorm_writes(remat):
    """Recomputation changes what is kept, not what is computed: the
    same losses, the same parameters, and BatchNorm's running statistics,
    written inside a recomputed unit, still leave the step."""
    rng = np.random.default_rng(0)
    x = mx.nd.array(rng.standard_normal((16, 8)).astype(np.float32))
    y = mx.nd.array(rng.integers(0, 3, (16,)).astype(np.int32))
    results = []
    for mode in (None, remat):
        mx.random.seed(3)
        net = _Stack()
        net.initialize(mx.init.Xavier())
        step = TrainStep(net, loss="softmax_ce", optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         remat=mode)
        losses = [float(step(x, y).asnumpy()) for _ in range(3)]
        step.sync_params()
        results.append((losses, {n: p.data().asnumpy() for n, p in
                                 net.collect_params().items()}))
    (l0, p0), (l1, p1) = results
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    moved = 0
    for (n0, a), (n1, b) in zip(sorted(p0.items()), sorted(p1.items())):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        if "running_mean" in n0:
            moved += int(np.abs(a).max() > 0)
    assert moved == 2


def test_remat_by_layer_checkpoints_every_unit(reference_run):
    params, batches, _, _ = reference_run
    x, y = batches[0]
    counts = []
    for remat in ("layer", None):
        step = _step(_loaded(params), remat=remat)
        step(mx.nd.array(x), mx.nd.array(y))
        jaxpr = str(jax.make_jaxpr(step._step_jit)(*_specs(step, x, y)))
        counts.append(jaxpr.count("remat2"))
    assert counts[0] >= len(SZ["hybrid_override_pattern"])
    assert counts[1] == 0


def _loaded(params):
    net = _net()
    _load(net, params)
    return net


def test_compiled_step_names_the_scopes(reference_run):
    """Every mechanism has its scope in the compiled step, the expert
    layer's matrix products scopes of their own beside the choice, the
    sort and the scatter."""
    params, batches, _, _ = reference_run
    step = _step(_loaded(params), remat="layer")
    x, y = batches[0]
    step(mx.nd.array(x), mx.nd.array(y))
    text = step._step_jit.lower(*_specs(step, x, y)).compile().as_text()
    table = mx.telemetry.trace.hlo_scopes(text)
    scopes = set(table.values())
    assert {"mx_ssd_fwd", "mx_moe_score", "mx_moe_route", "mx_moe_latent",
            "mx_moe_dispatch", "mx_moe_combine", "mx_moe_shared",
            "mx_attn_fwd"} <= scopes
    assert any(s.startswith("mx_moe_gmm_") for s in scopes)
    assert "mx_train_step" not in scopes
    # no matrix product is left in the choice, the sort or the scatter
    other = [name for name, scope in table.items() if scope in (
        "mx_moe_route", "mx_moe_dispatch", "mx_moe_combine")]
    assert other and not any(
        n.startswith(("dot", "convolution")) for n in other)


def test_hlo_scopes_reads_forward_and_backward_names():
    text = '''
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(mx_train_step)/jit(main)/mx_ssd_fwd/mul" source_file="a.py"}
  ROOT %dot.7 = f32[8]{0} dot(%a, %b), metadata={op_name="jit(mx_train_step)/jit(main)/transpose(jvp(mx_moe_gmm_up))/dot_general"}
  %copy.1 = f32[8]{0} copy(%a), metadata={op_name="jit(mx_train_step)/jit(main)/add"}
  %add.2 = f32[8]{0} add(%a, %b)
'''
    assert mx.telemetry.trace.hlo_scopes(text) == {
        "fusion.3": "mx_ssd_fwd", "dot.7": "mx_moe_gmm_up"}


def test_counters_are_published_from_the_net(reference_run):
    """``nn.publish_moe_counters`` reads what the last step wrote, a
    gauge a counter and layer, whatever else the net holds."""
    params, batches, _, _ = reference_run
    net = _loaded(params)
    x, y = batches[0]
    _step(net)(mx.nd.array(x), mx.nd.array(y))
    mx.telemetry.remove("moe::")
    out = nn.publish_moe_counters(net)
    assert len(out) == 5 * len(nn.MOE_COUNTERS)
    snap = mx.telemetry.snapshot(prefix="moe::")
    assert {k: snap[k]["value"] for k in out} == out
    assert nn.publish_moe_counters(_Stack()) == {}


def test_unknown_layer_kind_is_refused():
    with pytest.raises(ValueError, match="layer kind"):
        PatternLM("MX", 10, 8, mamba=dict(num_heads=1, head_dim=8,
                                          state_size=4))
