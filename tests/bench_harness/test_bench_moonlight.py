"""The cell ``moonlight-16b-a3b-train-8k``: its plain reference against
the system at ``rehearsal_sizes`` on the CPU, its fp8 control standing
apart, a step that leaves its state unchanged coming out as not correct,
the configuration's sizes against the published ``config.json``, the cost
functions against the arithmetic of the cut, ``BENCHMARK.json``'s entries
looked up by name, the metrics of a traced rehearsal run, and the lowered
step of ``ouro-2.6b-train-4k``, which is the parent's to the byte. (The
step compiled for a described v5e: ``test_bench_moonlight_compile.py``.)"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import training  # noqa: E402

CELL = "moonlight-16b-a3b-train-8k"
CONFIG = "moonlight-16b-a3b"
SOURCE = "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/" \
    "config.json"

# config.json of moonshotai/Moonlight-16B-A3B as the catalog beside the
# model-configs guide holds it (source_url in the .json)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
}
REDUCED = {"num_hidden_layers": 6, "n_routed_experts": 8,
           "vocab_size": 20480}
NEW_METRICS = ("mla_latent_time_share.train", "mla_latent_roofline.train")
SHARED_METRICS = (
    "device_idle.train", "step_device_ms.train", "step_program_ms.train",
    "peak_hbm.train", "step_mfu_device.train", "fresh_compiles.setup",
    "step_acquire_s.setup", "remat_saved_gb.train",
    "attn_kernel_sites.train", "attn_time_share.train",
    "attn_roofline.train", "moe_time_share.train", "moe_gmm_roofline.train",
    "moe_dispatch_time_share.train", "expert_load_max_over_mean.train",
    "moe_buffer_fill.train", "moe_overflow_pairs.train")


def _float32(cell):
    cell.config = dict(cell.config, compute_dtype=None)
    return cell


# -- the declaration ----------------------------------------------------------
def test_configuration_is_the_published_one_cut_to_a_share():
    cfg = harness.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    (entry,) = [c for c in harness.benchmark_json(proposed=False)["configs"]
                if c["name"] == CONFIG]
    assert cfg["reduced"] == entry["reduced"] == list(REDUCED)
    assert cfg["source"] == entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for key, value in PUBLISHED.items():
        for where in (cfg, cfg["sizes"]):
            if key in REDUCED:
                assert where[key] == REDUCED[key]
                assert cfg["published"][key] == value
            else:
                assert where[key] == value, key
    # no width among the reduced keys, and the floors of the guide: a whole
    # period and four layers after the dense one, 8 experts, an eighth of
    # the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    sizes = cfg["sizes"]
    assert sizes["router_experts"] == 64 \
        and sizes["expert_ids"] == list(range(8))
    assert sizes["seq_len"] == PUBLISHED["max_position_embeddings"]
    # 1.25 x the balanced 768 rows an expert, up to the row tile
    balanced = sizes["seq_len"] * 6 / 64
    assert sizes["moe_buffer_rows"] == 8 * 1024 \
        and 1024 == -(-1.25 * balanced // 128) * 128
    # the rehearsal changes sizes, never the structure
    small = cfg["rehearsal_sizes"]
    assert set(small) == set(sizes)
    for key in ("num_hidden_layers", "first_k_dense_replace", "rope_theta",
                "rms_norm_eps", "n_shared_experts", "routed_scaling_factor",
                "norm_topk_prob", "router_bias_update_rate", "hidden_act"):
        assert small[key] == sizes[key], key
    assert small["qk_rope_head_dim"] != small["qk_nope_head_dim"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == 8
    for key in ("published", "deployment", "assumed", "departures",
                "sizes", "rehearsal_sizes", "limits"):
        assert cfg[key], key
    for name in ("loss_rel", "first_grad_rel", "change_rel",
                 "first_step_diff"):
        limit = cfg["limits"]["step"][name]
        assert 0 < limit["limit"] < 1 and "my chip runs, PR 37" in limit["why"]


def test_benchmark_json_holds_the_cell_and_its_metrics_by_name():
    bench = harness.benchmark_json(proposed=False)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "step-ring",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in metrics["train_throughput"]["workloads"]
    for name in SHARED_METRICS:
        assert metrics[name]["workloads"][-1] == CELL, name
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL], name
        assert metrics[name]["layer"] == "Kernels"
        spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                              name + ".json"))
        assert spec["reader"] == "trace_scope"
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    assert metrics["mla_latent_time_share.train"]["better"] == "lower"
    # no metric of another cell's mechanism lists this one
    for name in ("ssd_time_share.train", "loop_time_share.train",
                 "exit_head_roofline.train", "conv_time_share.train"):
        assert CELL not in metrics[name]["workloads"], name


def test_costs_are_the_arithmetic_of_the_cut():
    cell = harness.load_cell(CELL)
    model, sz = cell.model, cell.sizes
    shapes = model.param_shapes(sz)
    frozen = {k: s for k, s in shapes.items() if k.endswith(model.FROZEN)}
    trained = sum(int(np.prod(s)) for k, s in shapes.items()
                  if k not in frozen)
    mla = 3072 * 2048 + 576 * 2048 + 512 + 4096 * 512 + 2048 * 2048
    assert mla == 13_763_072
    dense = mla + 2 * 2048 + 3 * 2048 * 11264
    expert = mla + 2 * 2048 + 8 * 3 * 2048 * 1408 + 64 * 2048 \
        + 3 * 2048 * 2816
    assert (dense, expert) == (82_973_184, 100_405_760)
    assert trained == dense + 5 * expert + 2 * 20480 * 2048 + 2048 \
        == 668_890_112
    # the routers' 64-wide biases, which no gradient reaches, beside them
    assert sorted(frozen.values()) == [(64,)] * 5
    assert model.pattern(sz) == "LG" + "LF" * 5
    macs = model.forward_macs(sz)
    assert macs["mla.projections"] == 6 * (mla - 512)
    assert macs["mla.scores"] == 6 * 16 * 320 * 8193 / 2
    assert macs["dense.mlp"] == 3 * 2048 * 11264
    assert macs["experts.routed"] == 5 * 3 * 2048 * 1408      # 8192 rows
    assert macs["experts.shared"] == 5 * 3 * 2048 * 2816
    assert macs["experts.router"] == 5 * 64 * 2048
    assert macs["head"] == 20480 * 2048
    total = sum(macs.values())
    assert 449.8e6 < total < 450.0e6
    assert 0.27 < macs["mla.scores"] / total < 0.29
    assert 0.45 < (macs["mla.scores"] + macs["mla.projections"]) / total \
        < 0.47
    assert 0.09 < macs["head"] / total < 0.10
    assert model.items_per_step(sz) == 8192
    assert 22.0e12 < model.flops_per_item(sz, "train") * 8192 < 22.2e12
    peaks = harness.peaks_for("TPU v5 lite")
    for cost, flops in (
            (model.attn_cost, 6 * 8192 * macs["mla.scores"]),
            (model.mla_latent_cost, 6 * 8192 * macs["mla.projections"]),
            (model.moe_gmm_cost, 6 * 8192 * macs["experts.routed"])):
        operations, moved = cost(sz)
        assert operations == flops
        # each is bound by the MXU, not by memory
        assert operations / peaks["bf16_flops"] \
            > moved / peaks["hbm_bytes_per_s"] > 0
    # the second product counts its 64 columns, not the 128 a tile holds
    assert model.attn_cost(sz)[0] == 6 * 6 * 8192 * 16 * (192 + 128) \
        * 8193 / 2


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    ref = text[text.index("# --- reference: begin"):
               text.index("# --- reference: end")]
    assert "import mxnet_tpu" not in ref and "from mxnet_tpu" not in ref
    for name in ("def latent_attention", "def moe_layer", "def router",
                 "def reference_loss", "def adam_step", "def balance_step"):
        assert name in ref, name


# -- what the shared classes lower to for the cell that had them before -------
#: sha256 of the lowered step of ``ouro-2.6b-train-4k`` at its rehearsal
#: sizes, computed with the function below on the parent of the PR that
#: gave ``PatternLM`` its kinds ``L`` and ``F`` and ``_fused_attention``
#: its second part (PR 37; commit 19fb4be). Nemotron's is pinned in
#: ``test_bench_ouro.py`` and holds unedited
OURO_STEP_SHA256 = \
    "7779abce94f1840ecbcde684c21b393f87f7aafc06b443038b0734841258b82f"


def test_ouro_cell_s_step_is_the_program_it_was():
    import jax.numpy as jnp
    import mxnet_tpu as mx
    cell = harness.load_cell("ouro-2.6b-train-4k", rehearsal=True)
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
    assert hashlib.sha256(text.encode()).hexdigest() == OURO_STEP_SHA256


# -- the reference against the system -----------------------------------------
def test_reference_agrees_with_the_system():
    cell = _float32(harness.load_cell(CELL, rehearsal=True))
    session = cell.driver.setup(cell, 7)
    got, want = session["first"], training.reference(cell, 7)
    cell.driver.close(session)
    shapes = cell.model.param_shapes(cell.sizes)
    trained = {k for k in shapes if not k.endswith(cell.model.FROZEN)}
    assert len(got["losses"]) == 3
    assert set(want["first_update"]) == trained
    assert set(got["first_update"]) == set(shapes)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    assert harness.update_difference(got["first_update"],
                                     want["first_update"]) < 2e-3
    assert harness.worst_leaf_gap(got["change_norms"],
                                  want["change_norms"])[0] < 2e-3
    # Adam's first update is the rate times the gradient's sign, in every
    # part of the layer (less where a gradient is as small as epsilon: the
    # attention's products behind a scaled-down W_o at these tiny sizes)
    lr = cell.config["optimizer"]["learning_rate"]
    # (a norm's weight of 1 moves by whole float32 steps: not among them)
    for leaf in ("head_weight", "l0_q_weight", "l0_kv_down_weight",
                 "l3_kv_up_weight", "l0_gate_up_weight", "l1_w1", "l2_w3",
                 "l5_w2", "l4_shared_gate_up_weight", "l1_router_weight"):
        moved = np.abs(want["first_update"][leaf])
        assert abs(np.median(moved[moved > 0]) / lr - 1) < 0.2, leaf


def test_weights_and_the_calibrated_bias_are_as_assumed():
    cell = harness.load_cell(CELL, rehearsal=True)
    sz = cell.sizes
    w = cell.model.make_weights(sz, 2 ** 31 + 5)
    assert (np.asarray(w["l1_ffn_norm_weight"]) == 1).all()
    assert (np.asarray(w["l0_kv_norm_weight"]) == 1).all()
    std = float(np.std(np.asarray(w["embed_weight"])))
    assert abs(std / sz["initializer_range"] - 1) < 0.05
    # a sublayer's last product is scaled down by the published depth
    for leaf in ("l0_o_weight", "l0_down_weight", "l2_w2",
                 "l2_shared_down_weight"):
        std = float(np.std(np.asarray(w[leaf])))
        assert abs(std * (2 * 27) ** 0.5 / sz["initializer_range"] - 1) \
            < 0.08, leaf
    assert "l0_router_bias" not in w and "l0_gate_up_weight" in w
    bias = np.asarray(w["l3_router_bias"])
    assert bias.shape == (sz["router_experts"],) and bias.any()
    again = cell.model.make_weights(sz, 2 ** 31 + 5)
    np.testing.assert_array_equal(bias, np.asarray(again["l3_router_bias"]))
    (x, y), = cell.model.make_batches(sz, 2 ** 31 + 5, 1)
    assert x.shape == (sz["batch"], sz["seq_len"]) and x.max() < 211
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
    line = _run(ARGV + ["--seed", "3700000019", "--trace", "1"], capsys)
    # (`correct` holds the chip's limits, set at the cell's own sizes)
    assert line["failed"] == 0 and line["rehearsal"] is True
    m = line["metrics"]
    # shares of a roofline or of a peak, a program's name in the device
    # trace and the device's memory are device numbers: none from a
    # rehearsal on the CPU
    device_only = {"attn_roofline.train", "mla_latent_roofline.train",
                   "moe_gmm_roofline.train", "step_mfu_device.train",
                   "step_program_ms.train", "peak_hbm.train"}
    for name in set(NEW_METRICS + SHARED_METRICS) - device_only:
        assert name in m, name
    assert not device_only & set(m)
    # heads of 16 on the CPU: the plain form, no kernel site
    assert m["attn_kernel_sites.train"]["value"] == 0
    assert m["moe_overflow_pairs.train"]["value"] == 0
    assert 0 < m["moe_dispatch_time_share.train"]["value"] \
        < m["moe_time_share.train"]["value"] < 100
    assert 0 < m["mla_latent_time_share.train"]["value"] < 100
    assert 0 < m["attn_time_share.train"]["value"] < 100
    assert 0 < m["moe_buffer_fill.train"]["value"] <= 100
    assert m["expert_load_max_over_mean.train"]["value"] >= 1
    assert m["remat_saved_gb.train"]["value"] > 0


def test_scope_table_names_the_latent_path_and_both_gated_mlps():
    cell = harness.load_cell(CELL, rehearsal=True)
    session = cell.driver.setup(cell, 13)
    table = cell.model.scope_table()
    cell.driver.close(session)
    scopes = set(table.values())
    for want in ("mx_mla_q", "mx_mla_kv_down", "mx_mla_kv_up", "mx_mla_out",
                 "mx_attn_fwd", "mx_gated_mlp", "mx_moe_shared/mx_gated_mlp",
                 "mx_moe_score", "mx_moe_route", "mx_moe_dispatch",
                 "mx_moe_gmm_up", "mx_moe_gmm_down", "mx_moe_combine"):
        assert want in scopes, (want, sorted(scopes))
    assert any(s.startswith("mx_mla_rope") for s in scopes)
    assert not any("mx_moe_latent" in s for s in scopes)
    import mxnet_tpu as mx
    cell.model.release_system()
    gauges = {k: v["value"] for k, v in mx.telemetry.snapshot().items()
              if k.startswith("moe::")}
    assert len([k for k in gauges if k.startswith("moe::pairs_held::")]) == 5
    assert all(v == 0 for k, v in gauges.items()
               if k.startswith("moe::overflow_pairs::"))
