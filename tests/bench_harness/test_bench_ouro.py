"""The cell ``ouro-2.6b-train-4k``: its plain reference against the system
at ``rehearsal_sizes`` on the CPU, its fp8 control standing apart, a step
that leaves its state unchanged coming out as not correct, the
configuration's sizes against the published ``config.json``, the cost
functions against the arithmetic of the cut, and the new readers and
metrics in a traced rehearsal run. (The step compiled for a described
v5e: ``test_bench_ouro_compile.py``.)"""
import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import training  # noqa: E402

CELL = "ouro-2.6b-train-4k"
CONFIG = "ouro-2.6b"

# config.json of ByteDance/Ouro-2.6B as the catalog beside the
# model-configs guide holds it (source_url in the .json)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
NEW_METRICS = ("loop_time_share.train", "attn_time_share.train",
               "attn_roofline.train", "exit_head_time_share.train",
               "exit_head_roofline.train", "loop_stack_traces.setup")
SHARED_METRICS = ("device_idle.train", "step_device_ms.train",
                  "peak_hbm.train", "step_mfu_device.train",
                  "step_program_ms.train", "step_acquire_s.setup",
                  "fresh_compiles.setup", "remat_saved_gb.train")


def _float32(cell):
    cell.config = dict(cell.config, compute_dtype=None)
    return cell


# -- the declaration ----------------------------------------------------------
def test_configuration_is_the_published_one_cut_in_depth_alone():
    cfg = harness.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    (entry,) = [c for c in harness.benchmark_json(proposed=False)["configs"]
                if c["name"] == CONFIG]
    reduced = ["num_hidden_layers", "layer_types", "max_window_layers"]
    assert cfg["reduced"] == entry["reduced"] == reduced
    assert cfg["source"] == entry["source"] \
        == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    for key, value in PUBLISHED.items():
        for where in (cfg, cfg["sizes"]):
            if key in reduced:
                assert where[key] != value
                assert cfg["published"][key] == value
            else:
                assert where[key] == value, key
    assert cfg["num_hidden_layers"] == cfg["max_window_layers"] == 4 \
        == len(cfg["layer_types"])
    # the rehearsal changes sizes, never the structure
    small = cfg["rehearsal_sizes"]
    assert set(small) == set(cfg["sizes"])
    for key in ("total_ut_steps", "num_hidden_layers", "rope_theta",
                "rms_norm_eps", "exit_entropy_beta", "hidden_act"):
        assert small[key] == cfg["sizes"][key], key
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    for key in ("published", "deployment", "assumed", "departures",
                "sizes", "rehearsal_sizes", "limits"):
        assert cfg[key], key
    for name in ("loss_rel", "first_grad_rel", "change_rel",
                 "first_step_diff"):
        limit = cfg["limits"]["step"][name]
        assert 0 < limit["limit"] < 1 and "my chip runs, PR 33" in limit["why"]


def test_benchmark_json_holds_the_cell_and_its_metrics():
    bench = harness.benchmark_json(proposed=False)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "step-ring",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in metrics["train_throughput"]["workloads"]
    for name in SHARED_METRICS:
        assert CELL in metrics[name]["workloads"], name
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL], name
        spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                              name + ".json"))
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(NEW_METRICS)


def test_costs_are_the_arithmetic_of_the_cut():
    cell = harness.load_cell(CELL)
    model, sz = cell.model, cell.sizes
    held = sum(int(np.prod(s)) for s in model.param_shapes(sz).values())
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert held == 4 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1 \
        == 406_884_353
    macs = model.forward_macs(sz)
    assert macs["products"] == 16 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    assert macs["attention"] == 16 * 2 * 2048 * 4097 / 2
    assert macs["heads"] == 4 * 49152 * 2048
    assert 0.29 < macs["heads"] / sum(macs.values()) < 0.31
    assert model.items_per_step(sz) == 4096
    step_flops = model.flops_per_item(sz, "train") * 4096
    assert 33.3e12 < step_flops < 33.5e12
    peaks = harness.peaks_for("TPU v5 lite")
    for cost, flops in ((model.attn_cost, 6 * 4096 * macs["attention"]),
                        (model.exit_head_cost, 6 * 4096 * macs["heads"])):
        operations, moved = cost(sz)
        assert operations == flops
        # both are bound by the MXU, not by memory
        assert operations / peaks["bf16_flops"] \
            > moved / peaks["hbm_bytes_per_s"] > 0


def test_the_two_copies_of_the_reference_are_one_text():
    def block(path):
        text = open(path).read()
        return text[text.index("# --- reference: begin"):
                    text.index("# --- reference: end")]

    assert block(os.path.join(ROOT, "tests", "reference", "ouro.py")) \
        == block(os.path.join(BENCH, "configs", CONFIG + ".py"))
    # the reference imports nothing of the program
    ref = block(os.path.join(BENCH, "configs", CONFIG + ".py"))
    assert "import mxnet_tpu" not in ref and "from mxnet_tpu" not in ref


# -- what the shared classes lower to for the cell that had them first --------
#: sha256 of the lowered step of ``nemotron3-super-train-8k`` at its
#: rehearsal sizes, computed with the function below on the parent of the PR
#: that gave ``PatternLM`` its loop, ``GQAttention`` its rotary parameter
#: and ``TrainStep`` a loss over all outputs (PR 33; commit 44ac7f5)
NEMOTRON_STEP_SHA256 = \
    "f4169444813cd31c901bcbe93c46a76dd09336db3455a863829bd0b001d24d92"


def test_nemotron_cell_s_step_is_the_program_it_was():
    """``PatternLM`` with one loop and no gate, ``GQAttention`` without
    ``rope_theta``, ``softmax_ce`` on the first output: to the byte the
    lowered text they gave before they could do more (a PR that means to
    change that cell's program computes the hash anew)."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    cell = harness.load_cell("nemotron3-super-train-8k", rehearsal=True)
    sizes = cell.sizes
    mx.random.seed(0)       # the step's base key is a constant of its text
    system = cell.model.build(cell.config, sizes, "step",
                              cell.model.make_weights(sizes, 0))
    step = system.step
    step._init_state()
    step._build_step()
    x = jnp.zeros((sizes["batch"], sizes["seq_len"]), jnp.int32)
    y = jnp.zeros((sizes["batch"] * sizes["seq_len"],), jnp.int32)
    text = step._step_jit.lower(
        step._pvals, step._opt_state, x, y, step._t_dev,
        jnp.asarray(0.1, jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == NEMOTRON_STEP_SHA256


# -- the reference against the system -----------------------------------------
def test_reference_agrees_with_the_system():
    cell = _float32(harness.load_cell(CELL, rehearsal=True))
    session = cell.driver.setup(cell, 7)
    got, want = session["first"], training.reference(cell, 7)
    cell.driver.close(session)
    shapes = cell.model.param_shapes(cell.sizes)
    assert len(got["losses"]) == 3
    assert set(got["first_update"]) == set(want["first_update"]) \
        == set(shapes)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    assert harness.update_difference(got["first_update"],
                                     want["first_update"]) < 2e-3
    assert harness.worst_leaf_gap(got["change_norms"],
                                  want["change_norms"])[0] < 2e-3
    # Adam's first update is the rate times the gradient's sign, for
    # every leaf: the gate's weight and bias start at zero and move too
    lr = cell.config["optimizer"]["learning_rate"]
    for leaf in ("head_weight", "gate_weight", "gate_bias",
                 "l0_qkv_weight", "final_norm_weight"):
        moved = np.abs(want["first_update"][leaf])
        # (1e-6 from a weight of 1 is 8 or 17 float32 steps of 5.96e-8
        # or 8.5 of 1.19e-7)
        assert abs(np.median(moved[moved > 0]) / lr - 1) < 6e-2, leaf
    assert harness.load_cell(CELL).config["optimizer"] == {
        "name": "adam", "learning_rate": 1e-6, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0}


def test_weights_are_as_assumed():
    cell = harness.load_cell(CELL, rehearsal=True)
    w = cell.model.make_weights(cell.sizes, 2 ** 31 + 5)
    assert not np.asarray(w["gate_weight"]).any() \
        and not np.asarray(w["gate_bias"]).any()
    assert (np.asarray(w["l1_mlp_post_norm_weight"]) == 1).all()
    std = float(np.std(np.asarray(w["embed_weight"])))
    assert abs(std / cell.sizes["initializer_range"] - 1) < 0.05
    again = cell.model.make_weights(cell.sizes, 2 ** 31 + 5)
    np.testing.assert_array_equal(np.asarray(w["l0_qkv_weight"]),
                                  np.asarray(again["l0_qkv_weight"]))
    (x, y), = cell.model.make_batches(cell.sizes, 2 ** 31 + 5, 1)
    assert x.shape == (cell.sizes["batch"], cell.sizes["seq_len"])
    np.testing.assert_array_equal(x[:, 1:].reshape(-1),
                                  y.reshape(x.shape)[:, :-1].reshape(-1))


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


# -- runs through run.py ------------------------------------------------------
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
    assert set(line["metrics"]) == {"train_throughput", "setup_s"}


def test_traced_run_reports_every_metric_of_the_cell(capsys):
    line = _run(ARGV + ["--seed", "3300000019", "--trace", "1"], capsys)
    # (`correct` holds the chip's limits, set at the cell's own sizes)
    assert line["failed"] == 0 and line["rehearsal"] is True
    m = line["metrics"]
    # shares of a roofline or of a peak, a program's name in the device
    # trace and the device's memory are device numbers: none from a
    # rehearsal on the CPU
    device_only = {"attn_roofline.train", "exit_head_roofline.train",
                   "step_mfu_device.train", "step_program_ms.train",
                   "peak_hbm.train"}
    for name in set(NEW_METRICS + SHARED_METRICS) - device_only:
        assert name in m, name
    assert not device_only & set(m)
    assert m["loop_stack_traces.setup"]["value"] == 1
    assert 0 < m["attn_time_share.train"]["value"] \
        < m["loop_time_share.train"]["value"] < 100
    assert 0 < m["exit_head_time_share.train"]["value"] < 100
    sz = harness.load_cell(CELL, rehearsal=True).sizes
    tokens, d = sz["batch"] * sz["seq_len"], sz["hidden_size"]
    # bf16: q k v, the heads' output, W_o's product and W_down's, and four
    # norms' float32 sums a row, for 4 layers 4 times; the final norm's
    # sum 4 times
    kept = 16 * tokens * (2 * (3 * d + d + d + d) + 4 * 4) + 4 * tokens * 4
    assert m["remat_saved_gb.train"]["value"] == pytest.approx(
        kept * 1e-9, rel=1e-6)


def test_scope_table_names_the_loop_and_leaves_the_loops_out():
    """After a run: every scope of ISSUE 33 is in the step's compiled
    text, those of the scanned body under ``mx_loop_body/``, and no
    ``while`` instruction is in the table (its event spans its body's)."""
    cell = harness.load_cell(CELL, rehearsal=True)
    session = cell.driver.setup(cell, 13)
    table = cell.model.scope_table()
    system = session["system"]
    text = system.step._step_jit.lower(*system.specs).compile().as_text()
    cell.driver.close(session)
    loops = re.findall(r"^\s*%?([\w.\-]+) = .* while\(.*condition=", text,
                       re.M)
    assert len(loops) >= 4 and not set(loops) & set(table)
    scopes = set(table.values())
    for want in ("mx_loop_body", "mx_loop_body/mx_attn_fwd",
                 "mx_loop_body/mx_rope", "mx_loop_body/mx_gated_mlp",
                 "mx_exit_head", "mx_exit_gate"):
        assert want in scopes, (want, sorted(scopes))
    import mxnet_tpu as mx
    cell.model.release_system()
    gauges = {k: v["value"] for k, v in mx.telemetry.snapshot().items()
              if k.startswith("loop::")}
    assert gauges["loop::trips"] == 4 and gauges["loop::stack_traces"] == 1
    assert sum(gauges[f"loop::exit_mass::{t}"] for t in (1, 2, 3, 4)) \
        == pytest.approx(1.0, rel=1e-5)
    assert 1 < gauges["loop::expected_steps"] < 4
    assert 0 < gauges["loop::gate_entropy"] <= np.log(4) + 1e-6


def test_busy_share_reader_counts_a_loop_once():
    """``trace_scope_busy``: a ``while`` event spans the events of its
    body; the share is of the union, so neither the numerator nor the
    denominator counts the loop twice."""
    import types
    import trace_reduce
    reader = harness.load_module(os.path.join(BENCH, "readers",
                                              "trace_scope_busy.py"))
    ops = [(0.0, 1.0, "%before = f32[] add()"),
           (1.0, 4.0, "%while.1 = () while()"),
           (1.0, 1.5, "%fusion.1 = f32[] fusion()"),
           (2.5, 0.5, "%fusion.2 = f32[] fusion()"),
           (3.0, 1.5, "%fusion.1 = f32[] fusion()"),
           (4.5, 0.5, "%fusion.2 = f32[] fusion()"),
           (5.0, 3.0, "%after = f32[] add()")]
    table = {"fusion.1": "mx_loop_body/mx_attn_fwd",
             "fusion.2": "mx_loop_body", "after": "mx_exit_head"}
    model = types.SimpleNamespace(scope_table=lambda: table)
    facts = {"cell": types.SimpleNamespace(model=model),
             "trace": trace_reduce.Trace(
                 [trace_reduce.DeviceTrace("/device:TPU:0", ops=ops)], [])}
    share = lambda rx: reader.read({"scopes": rx}, facts)  # noqa: E731
    assert share("^mx_loop_body") == pytest.approx(50.0)
    assert share("(^|/)mx_attn_fwd$") == pytest.approx(37.5)
    assert share("^mx_exit_head") == pytest.approx(37.5)
    assert share("^mx_nothing") is None
    facts["cell"].model = types.SimpleNamespace()
    assert share("^mx_loop_body") is None
