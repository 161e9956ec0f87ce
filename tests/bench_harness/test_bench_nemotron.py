"""The cell ``nemotron3-super-train-8k``: its plain reference against the
system at ``rehearsal_sizes`` on the CPU, its fp8 control standing apart,
a step that leaves its state unchanged and a buffer that overflows coming
out as not correct, the router's bias (its calibration at set-up, its
step of the balancing rule in every training step, program and reference
alike), the configuration's sizes against the published ``config.json``
and the new readers. (The
step compiled for a described v5e: ``test_bench_nemotron_compile.py``.)"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import training  # noqa: E402

CELL = "nemotron3-super-train-8k"

# config.json of NVIDIA-Nemotron-3-Super-120B-A12B-BF16 as the catalog
# beside the model-configs guide holds it (source_url in the .json)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEM"
                               "EMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEM"
                               "EME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
WIDTHS = ("hidden_size", "mamba_head_dim", "ssm_state_size", "head_dim",
          "moe_latent_size", "moe_intermediate_size", "intermediate_size",
          "moe_shared_expert_intermediate_size", "chunk_size", "conv_kernel",
          "num_experts_per_tok", "routed_scaling_factor", "expand")


def _float32(cell):
    """The same cell computing in float32, at a rate whose updates float32
    holds to a thousandth (the cell's own 1e-6 is 8 units in the last
    place of a norm's weight): what separates the program from its
    reference is then rounding order alone."""
    cell.config = dict(cell.config, compute_dtype=None,
                       optimizer=dict(cell.config["optimizer"],
                                      learning_rate=1e-4))
    return cell


# -- the configuration's file ------------------------------------------------
def test_sizes_are_the_published_config_but_for_the_reduced_keys():
    cell = harness.load_cell(CELL)
    config, sizes = cell.config, cell.sizes
    (entry,) = [c for c in cell.bench["configs"]
                if c["name"] == config["name"]]
    assert entry["source"] == config["source"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert len(PUBLISHED["hybrid_override_pattern"]) == 88
    for key, value in PUBLISHED.items():
        for where in (config, sizes):       # top level and `sizes` alike
            if key in config["reduced"]:
                assert where[key] != value, key
                assert config["published"][key] == value, key
            else:
                assert where[key] == value, key
    assert not set(config["reduced"]) & set(WIDTHS)
    # what is held: one period of the pattern, an eighth of heads, groups,
    # columns and vocabulary, 8 of 512 experts
    period = PUBLISHED["hybrid_override_pattern"][27:38]
    assert sizes["hybrid_override_pattern"] == period == "MEMEMEMEM*E"
    assert sizes["num_hidden_layers"] == len(period)
    for key in ("mamba_num_heads", "n_groups", "num_attention_heads",
                "vocab_size"):
        assert sizes[key] * 8 == PUBLISHED[key], key
    assert sizes["num_key_value_heads"] == 1
    assert cell.model.shared_columns(sizes) == 5376 // 8 == 672
    assert sizes["router_experts"] == PUBLISHED["n_routed_experts"]
    assert sizes["expert_ids"] == list(range(sizes["n_routed_experts"]))
    assert sizes["n_routed_experts"] == 8
    assert config["deployment"]["chips_sharing_a_layer"] == 64
    # the buffer's rule: 1.25 x the balanced rows, up to the tile
    balanced = sizes["seq_len"] * sizes["batch"] * 22 / 512
    tile = sizes["moe_row_tile"]
    rows = -(-int(1.25 * balanced) // tile) * tile
    assert sizes["moe_buffer_rows"] == 8 * rows == 4096


def test_parameters_held_add_up_to_the_issue_s_count():
    cell = harness.load_cell(CELL)
    shapes = cell.model.param_shapes(cell.sizes)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert 507e6 < total < 509e6
    experts = sum(int(np.prod(s)) for k, s in shapes.items()
                  if k.endswith(("_w1", "_w2")) and "shared" not in k)
    assert experts == 5 * 8 * 2 * 1024 * 2688
    macs = cell.model.forward_macs(cell.sizes)
    flops = 2 * sum(macs.values())
    assert 470e6 < flops < 495e6                       # 483 M a token
    assert 0.26 < 2 * macs["head"] / flops < 0.30      # the head, 28 %
    assert cell.model.flops_per_item(cell.sizes, "train") == 3 * flops
    ops, moved = cell.model.moe_gmm_cost(cell.sizes)
    assert ops == 5 * 6 * 4096 * 2 * 1024 * 2688 and moved > 0
    ops, moved = cell.model.ssd_cost(cell.sizes)
    assert ops > 0 and moved > 0


def test_the_two_copies_of_the_reference_are_one_text():
    def block(path):
        text = open(path).read()
        return text[text.index("# --- reference: begin"):
                    text.index("# --- reference: end")]

    assert block(os.path.join(ROOT, "tests", "reference", "nemotron_h.py")) \
        == block(os.path.join(BENCH, "configs",
                              "nemotron3-super-120b-a12b.py"))
    text = open(os.path.join(BENCH, "configs",
                             "nemotron3-super-120b-a12b.py")).read()
    reference = text[:text.index("# the system under test")]
    assert "import mxnet_tpu" not in reference \
        and "from mxnet_tpu" not in reference


# -- the reference against the system ----------------------------------------
def test_reference_agrees_with_the_system():
    cell = _float32(harness.load_cell(CELL, rehearsal=True))
    session = cell.driver.setup(cell, 7)
    got, want = session["first"], training.reference(cell, 7)
    cell.driver.close(session)
    shapes = cell.model.param_shapes(cell.sizes)
    biases = {f"l{i}_router_bias" for i in (1, 3, 5, 7, 10)}
    assert len(got["losses"]) == 3 and set(got["first_update"]) == set(shapes)
    # the reference compares what the optimizer trains; the routers' bias
    # is state the forward writes (below)
    assert set(want["first_update"]) == set(want["change_norms"]) \
        == set(shapes) - biases
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    assert harness.update_difference(got["first_update"],
                                     want["first_update"]) < 2e-3
    assert harness.worst_leaf_gap(got["change_norms"],
                                  want["change_norms"])[0] < 2e-3
    # Adam's first update is the rate times the gradient's sign
    lr = cell.config["optimizer"]["learning_rate"]
    moved = np.abs(want["first_update"]["head_weight"])
    assert abs(np.median(moved[moved > 0]) / lr - 1) < 1e-2
    assert harness.load_cell(CELL).config["optimizer"] == {
        "name": "adam", "learning_rate": 1e-6, "beta1": 0.9, "beta2": 0.999,
        "epsilon": 1e-8, "wd": 0.0}
    # the routers' bias: one step of the balancing rule a training step,
    # the same in the program and in the reference
    model, rate = cell.model, cell.sizes["router_bias_update_rate"]
    weights = model.make_weights(cell.sizes, 7)
    x, y = model.make_batches(cell.sizes, 7, 1)[0]
    _, loads = model.reference_loss(cell.sizes, weights, x, y)
    after = model.balance_step(cell.sizes, weights, loads)
    for k in biases:
        moved = np.asarray(after[k] - weights[k])
        assert np.isin(np.round(np.abs(moved) / rate, 3), [0, 1]).all()
        assert np.abs(moved).sum() > 0
        np.testing.assert_array_equal(got["first_update"][k], moved)


def test_every_leaf_moves_at_the_cell_s_rate():
    """At the rate the cell trains with, float32 holds a first update of
    every leaf, the leaves of scale 1 (norm weights, D, A_log, dt_bias)
    among them; at a hundredth of it they stay where they were, and the
    comparison would read 0 against 0 there."""
    cell = harness.load_cell(CELL, rehearsal=True)
    assert cell.config["optimizer"]["learning_rate"] \
        == harness.load_cell(CELL).config["optimizer"]["learning_rate"]
    want = training.reference(cell, 9)
    still = [k for k, u in want["first_update"].items() if not u.any()]
    assert not still, still
    cell.config = dict(cell.config, optimizer=dict(
        cell.config["optimizer"], learning_rate=1e-8))
    slow = training.reference(cell, 9)
    still = {k.split("_", 1)[1] for k, u in slow["first_update"].items()
             if not u.any()}
    assert {"norm_weight", "d", "gate_norm_weight"} <= still


def test_lower_precision_control_stands_apart():
    cell = harness.load_cell(CELL, rehearsal=True)
    rows = {name: value for name, value, _, _ in
            cell.driver.control(cell, 5)}
    fine = _float32(harness.load_cell(CELL, rehearsal=True))
    session = fine.driver.setup(fine, 5)
    sound = harness.update_difference(
        session["first"]["first_update"],
        training.reference(fine, 5)["first_update"])
    fine.driver.close(session)
    assert rows["first_step_diff"] > 0.05
    assert rows["first_step_diff"] > 3 * sound


# -- runs with the timed path broken underneath -------------------------------
def _run(argv, capsys):
    import run
    run.main(argv)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


ARGV = ["--workload", CELL, "--seconds", "0.5", "--rehearsal", "1"]


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    from mxnet_tpu.parallel import TrainStep
    real = TrainStep.__init__

    def frozen(self, *a, **kw):
        real(self, *a, **kw)
        self.lr = 0.0

    monkeypatch.setattr(TrainStep, "__init__", frozen)
    line = _run(ARGV + ["--seed", "11", "--trace", "0"], capsys)
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] > 0


def test_pairs_beyond_the_buffer_fail_the_run(monkeypatch, capsys):
    """The reference drops no token: a buffer too small for the routing
    makes every loss infinite, in the compared steps and in the window."""
    real = harness.load_cell

    def small_buffer(*a, **kw):
        cell = real(*a, **kw)
        cell.sizes["moe_buffer_rows"] = 4 * 8
        return cell

    monkeypatch.setattr(harness, "load_cell", small_buffer)
    # the calibration's own check would end the run first: let it pass
    monkeypatch.setitem(small_buffer(CELL, True).model.__dict__,
                        "calibrate_router_bias", lambda sz, w, b: {})
    line = _run(ARGV + ["--seed", "12", "--trace", "0"], capsys)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0


def test_traced_run_reports_the_new_metrics(capsys):
    import mxnet_tpu as mx
    line = _run(ARGV + ["--seed", "3000000019", "--trace", "1"], capsys)
    # (`correct` holds the chip's limits, set at the cell's own sizes)
    assert line["failed"] == 0 and line["rehearsal"] is True
    m = line["metrics"]
    for name in ("ssd_time_share.train", "moe_time_share.train",
                 "expert_load_max_over_mean.train", "moe_buffer_fill.train",
                 "moe_overflow_pairs.train", "step_acquire_s.setup"):
        assert name in m, name
    assert m["moe_overflow_pairs.train"]["value"] == 0
    assert 0 < m["ssd_time_share.train"]["value"] < 100
    assert 0 < m["moe_buffer_fill.train"]["value"] <= 100
    assert 1 <= m["expert_load_max_over_mean.train"]["value"] < 2.5
    # a share of a roofline is a device number: none from a rehearsal
    assert "ssd_roofline.train" not in m
    spans = mx.profiler.aggregate()
    assert any(k == "setup::router_bias" or k.endswith("setup::router_bias")
               for k in spans), sorted(spans)[:40]


# -- the router's bias --------------------------------------------------------
def _skewed(cell, seed, spread):
    """Seeded weights whose routers favour some experts strongly."""
    sizes = dict(cell.sizes, router_bias_iterations=0,
                 router_bias_max_over_mean=1e9, moe_buffer_rows=10 ** 6)
    weights = cell.model.make_weights(sizes, seed)
    rng = np.random.default_rng(seed)
    for k in list(weights):
        if k.endswith("router_weight"):
            scale = np.exp(spread * rng.standard_normal(
                (weights[k].shape[0], 1))).astype(np.float32)
            weights[k] = weights[k] * scale * 8
    return weights


def test_bias_calibration_balances_a_skewed_router():
    cell = harness.load_cell(CELL, rehearsal=True)
    model, sizes = cell.model, cell.sizes
    weights = _skewed(cell, 21, 0.8)
    batches = model.make_batches(sizes, 21, 4)
    # the loads before: far from balance
    raw = dict(sizes, router_bias_iterations=0,
               router_bias_max_over_mean=1e9, moe_buffer_rows=10 ** 6)
    model.calibrate_router_bias(raw, dict(weights), batches)
    bias = model.calibrate_router_bias(sizes, dict(weights), batches)
    assert sorted(bias) == sorted(f"l{i}_router_bias" for i in (1, 3, 5, 7, 10))
    assert all(np.abs(b).max() > 0 for b in bias.values())
    # too few iterations for this router end the run, they do not go on
    few = dict(sizes, router_bias_iterations=2)
    with pytest.raises(SystemExit, match="misses its criterion"):
        model.calibrate_router_bias(few, dict(weights), batches)


def test_bias_is_kept_by_seed_and_no_weight_with_it():
    cell = harness.load_cell(CELL, rehearsal=True)
    model = cell.model
    model._BIAS.clear()
    a = model.make_weights(cell.sizes, 33)
    assert len(model._BIAS) == 1
    (kept,) = model._BIAS.values()
    assert sum(v.size for v in kept.values()) == 5 * 16
    calls = []
    real = model.calibrate_router_bias
    model.calibrate_router_bias = lambda *a, **k: calls.append(1) or real(
        *a, **k)
    try:
        b = model.make_weights(cell.sizes, 33)
    finally:
        model.calibrate_router_bias = real
    assert not calls
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert np.abs(np.asarray(a["l1_router_bias"])).max() > 0


def test_two_seeds_route_differently_and_lower_to_one_program():
    """Same work for every seed: the step's program does not depend on
    what the router chose."""
    cell = harness.load_cell(CELL, rehearsal=True)
    texts, loads = [], []
    for seed in (101, 102):
        weights = cell.model.make_weights(cell.sizes, seed)
        system = cell.model.build(cell.config, cell.sizes, "step", weights)
        x, y = cell.model.make_batches(cell.sizes, seed, 1)[0]
        import mxnet_tpu as mx
        system(mx.nd.array(x), mx.nd.array(y))
        from mxnet_tpu.gluon import nn
        texts.append(system.step._step_jit.lower(*system.specs).as_text())
        loads.append(sorted(nn.publish_moe_counters(system.net).items()))
        cell.model.release_system()
    assert texts[0] == texts[1]
    assert loads[0] != loads[1]


def test_release_frees_the_system_and_the_scope_table_is_made_once():
    """After the window the configuration frees the parameters and the
    optimizer's state for its reference; the scope table is still to be
    had, from shapes, and however many metrics ask the step is compiled
    for it once."""
    import mxnet_tpu as mx
    cell = harness.load_cell(CELL, rehearsal=True)
    model = cell.model
    assert model.scope_table() is None or not model._LIVE   # no system yet
    weights = model.make_weights(cell.sizes, 103)
    system = model.build(cell.config, cell.sizes, "step", weights)
    x, y = model.make_batches(cell.sizes, 103, 1)[0]
    system(mx.nd.array(x), mx.nd.array(y))
    held = list(system.step._pvals) + jax.tree_util.tree_leaves(
        system.step._opt_state)
    model.release_system()
    assert all(a.is_deleted() for a in held) and not model._LIVE
    assert mx.telemetry.snapshot(prefix="moe::overflow_pairs::")
    compiles = []
    lower = system.step._step_jit.lower

    class Counted:
        def __init__(self, jit):
            self._jit = jit

        def lower(self, *a):
            compiles.append(1)
            return lower(*a)

    system.step._step_jit = Counted(system.step._step_jit)
    table = model.scope_table()
    assert model.scope_table() is table and len(compiles) == 1
    assert {"mx_ssd_fwd", "mx_moe_latent", "mx_moe_score",
            "mx_moe_dispatch"} <= set(table.values())


# -- the readers --------------------------------------------------------------
def test_scope_reader_sums_the_scopes_events():
    reader = harness.load_module(os.path.join(BENCH, "readers",
                                              "trace_scope.py"))
    table = {"fusion.1": "mx_ssd_fwd", "fusion.2": "mx_moe_gmm_up",
             "copy.3": "mx_ssd_conv"}
    ops = [(0.0, 1.0, "%fusion.1 = f32[8]{0} fusion(%x), kind=kLoop"),
           (1.0, 2.0, "%fusion.2 = f32[8]{0} fusion(%x), kind=kOutput"),
           (3.0, 4.0, "%copy.3 = f32[8]{0} copy(%x)"),
           (7.0, 8.0, "%fusion.9 = f32[8]{0} fusion(%x), kind=kLoop")]
    assert reader.scope_seconds(ops, table, "^mx_ssd_") == 5.0
    assert reader.scope_seconds(ops, table, "^mx_ssd_fwd$") == 1.0
    assert reader.scope_seconds(ops, table, "^mx_moe_gmm_") == 2.0
    assert reader.scope_seconds(ops, {}, "^mx_") == 0.0

    class Old:                      # a program from before the scopes
        model = object()

    assert reader.read({"what": "share", "scopes": "^mx_"},
                       {"cell": Old, "trace": None}) is None


def test_gauge_reader_reduces_over_layers():
    reader = harness.load_module(os.path.join(BENCH, "readers",
                                              "program_gauge.py"))
    snap = {"moe::buffer_fill::l1": {"kind": "gauge", "value": 0.5},
            "moe::buffer_fill::l3": {"kind": "gauge", "value": 0.7},
            "moe::other::l3": {"kind": "gauge", "value": 9.0},
            "moe::buffer_fill::x": {"kind": "counter", "value": 5}}
    assert reader.reduce_gauges(snap, "^moe::buffer_fill::", "mean") \
        == pytest.approx(0.6)
    assert reader.reduce_gauges(snap, "^moe::buffer_fill::", "max") == 0.7
    assert reader.reduce_gauges(snap, "^moe::buffer_fill::", "sum") \
        == pytest.approx(1.2)
    assert reader.reduce_gauges(snap, "^none::", "sum") is None
