"""The cell ``qwen3-next-80b-a3b-train-8k``: its plain reference against
the system at ``rehearsal_sizes`` on the CPU (three Adam steps), its fp8
control standing apart, a step that leaves its state unchanged coming out
as not correct, the configuration's sizes against the published
``config.json``, the cost functions against the arithmetic of the cut,
``BENCHMARK.json``'s entries looked up by name, never by position, and the
metrics of a traced rehearsal run. (The step compiled for a described
v5e: ``test_bench_qwen3_next_compile.py``.)"""
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

CELL = "qwen3-next-80b-a3b-train-8k"
CONFIG = "qwen3-next-80b-a3b"
SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/" \
    "main/config.json"

# config.json of Qwen/Qwen3-Next-80B-A3B-Instruct as the catalog beside the
# model-configs guide holds it (source_url in the .json)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
NEW_METRICS = {"gdn_time_share.train": "lower",
               "gdn_roofline.train": "higher"}
SHARED_METRICS = (
    "device_idle.train", "step_device_ms.train", "step_program_ms.train",
    "peak_hbm.train", "step_mfu_device.train", "fresh_compiles.setup",
    "step_acquire_s.setup", "remat_saved_gb.train",
    "scoped_time_share.train", "opt_update_time_share.train",
    "head_time_share.train", "attn_time_share.train", "attn_roofline.train",
    "attn_kernel_sites.train", "moe_time_share.train",
    "moe_dispatch_time_share.train", "moe_gmm_roofline.train",
    "moe_gmm_kernel_sites.train", "moe_buffer_fill.train",
    "moe_overflow_pairs.train", "expert_load_max_over_mean.train")


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
    # the floors of the guide: one whole period, at least 8 experts, an
    # eighth of the vocabulary; no width among the reduced keys
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    sizes = cfg["sizes"]
    assert sizes["router_experts"] == 512 \
        and sizes["expert_ids"] == list(range(32))
    assert sizes["seq_len"] == 8192 and sizes["batch"] == 1
    # one pool for the 32 held experts: whole tiles of 256 rows, no more
    # than 1.5 x the pairs at balance
    balanced = sizes["seq_len"] * 10 * 32 / 512
    assert balanced == 5120 and sizes["moe_buffer_rows"] % 256 == 0
    assert balanced < sizes["moe_buffer_rows"] <= 1.5 * balanced
    # the rehearsal changes sizes, never the structure
    small = cfg["rehearsal_sizes"]
    assert set(small) == set(sizes)
    for key in ("num_hidden_layers", "full_attention_interval", "rope_theta",
                "rms_norm_eps", "partial_rotary_factor", "norm_topk_prob",
                "linear_conv_kernel_dim", "hidden_act"):
        assert small[key] == sizes[key], key
    assert small["linear_key_head_dim"] != small["linear_value_head_dim"]
    assert small["seq_len"] % small["gdn_chunk"]      # a padded tail
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == 16
    assert dep["vocabulary_parallel"] == 8
    for key in ("published", "deployment", "assumed", "departures",
                "sizes", "rehearsal_sizes", "limits"):
        assert cfg[key], key
    said = " ".join(cfg["departures"])
    for what in ("multi-token-prediction", "auxiliary load-balancing loss",
                 "wd 0"):
        assert what in said, what
    for name in ("loss_rel", "first_grad_rel", "change_rel",
                 "first_step_diff"):
        limit = cfg["limits"]["step"][name]
        assert 0 < limit["limit"] < 1 and "my chip runs, PR 41" in limit["why"]


def test_benchmark_json_holds_the_cell_and_its_metrics_by_name():
    bench = harness.benchmark_json(proposed=False)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "step-ring",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in metrics["train_throughput"]["workloads"]
    for name in SHARED_METRICS:
        assert metrics[name]["workloads"].count(CELL) == 1, name
    for name, better in NEW_METRICS.items():
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["better"] == better, name
        assert (m["layer"], m["moves"], m["unit"], m["source"]) == (
            "Kernels", "train_throughput", "%", "device_trace")
        spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                              name + ".json"))
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    share, roofline = (harness.load_json(os.path.join(
        BENCH, "layer_metrics", name + ".json")) for name in NEW_METRICS)
    assert share["params"]["scopes"] == "^mx_gdn_"
    assert roofline["params"]["scopes"] == "^mx_gdn_rule$" \
        and roofline["params"]["cost"] == "gdn_cost"
    # no metric of another cell's mechanism lists this one
    for name in ("ssd_time_share.train", "loop_time_share.train",
                 "mla_latent_roofline.train", "conv_time_share.train"):
        assert CELL not in metrics[name]["workloads"], name


def test_costs_are_the_arithmetic_of_the_cut():
    cell = harness.load_cell(CELL)
    model, sz = cell.model, cell.sizes
    shapes = model.param_shapes(sz)
    count = lambda keep: sum(int(np.prod(s)) for k, s in shapes.items()  # noqa
                             if keep(k))
    linear = 25_165_824 + 131_072 + 32_768 + 32 + 32 + 128 + 8_388_608
    full = 16_777_216 + 2 * 1_048_576 + 8_388_608 + 512
    experts = 1_048_576 + 32 * 3_145_728 + 3_145_728 + 2_048
    assert (linear, full, experts) == (33_718_464, 27_263_488, 104_859_648)
    names = ("qkvz_weight", "ba_weight", "conv_weight", "dt_bias", "a_log",
             "gate_norm_weight", "out_weight")
    assert count(lambda k: k.startswith("l0_") and k[3:] in names) == linear
    assert count(lambda k: k.startswith("l3_") and k[3:] in (
        "qkv_weight", "q_norm_weight", "k_norm_weight", "o_weight")) == full
    assert count(lambda k: k.startswith("l2_") and k[3:] in (
        "router_weight", "w1", "w3", "w2", "shared_gate_up_weight",
        "shared_down_weight", "shared_gate_weight")) == experts
    assert count(lambda k: True) == 3 * linear + full + 4 * experts \
        + 4 * 4096 + 2 * 18992 * 2048 + 2048 == 625_667_136
    assert model.pattern(sz) == "DFDFDF*F"
    macs = model.forward_macs(sz)
    assert macs["gdn.projections"] == 3 * (linear - 32_768 - 192)
    assert macs["gdn.rule"] == 3 * 3 * 32 * 128 * 128
    assert macs["attn.projections"] == full - 512
    assert macs["attn.scores"] == 16 * 512 * 8193 / 2
    assert macs["head"] == 18992 * 2048
    assert macs["experts.router"] == 4 * 512 * 2048
    assert macs["experts.shared"] == 4 * (3 * 2048 * 512 + 2048)
    assert macs["experts.routed"] == 4 * sz["moe_buffer_rows"] * 3 * 2048 \
        * 512 / 8192
    total = sum(macs.values())
    assert 230e6 < total < 240e6
    mixers = macs["gdn.projections"] + macs["gdn.conv"] + macs["gdn.rule"]
    assert 0.44 < mixers / total < 0.48
    assert 0.24 < (macs["attn.projections"] + macs["attn.scores"]) / total \
        < 0.28
    assert 0.15 < macs["head"] / total < 0.18
    assert model.items_per_step(sz) == 8192
    assert model.flops_per_item(sz, "train") == 6 * total
    peaks = harness.peaks_for("TPU v5 lite")
    operations, moved = model.attn_cost(sz)
    assert operations == 6 * 8192 * macs["attn.scores"]
    assert operations / peaks["bf16_flops"] \
        > moved / peaks["hbm_bytes_per_s"] > 0
    operations, moved = model.moe_gmm_cost(sz)
    assert operations == 6 * 8192 * macs["experts.routed"]
    # the delta rule needs few operations for its bytes: its floor is the
    # memory's, q, k, v, beta, g and o once a pass
    operations, moved = model.gdn_cost(sz)
    assert operations == 6 * 8192 * macs["gdn.rule"]
    assert moved == 3 * 3 * 8192 * (8192 * 2 + 64 * 4 + 4096 * 4)
    assert moved / peaks["hbm_bytes_per_s"] \
        > operations / peaks["bf16_flops"] > 0


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    ref = text[text.index("# --- reference: begin"):
               text.index("# --- reference: end")]
    assert "import mxnet_tpu" not in ref and "from mxnet_tpu" not in ref
    for name in ("def delta_rule", "def gated_delta_net",
                 "def gated_attention", "def moe_layer", "def router",
                 "def reference_loss", "def adam_step"):
        assert name in ref, name
    # the delta rule as its recurrence, a scan over tokens: no chunk, no
    # triangular system
    rule = ref[ref.index("def delta_rule"):ref.index("def gated_delta_net")]
    assert "lax.scan(token, state, xs)" in rule
    assert "triangular" not in ref and "chunk" not in ref
    assert 'jax.nn.softmax(_matmul(u, p[f"l{i}_router_weight"]' in ref


# -- the reference against the system -----------------------------------------
def test_reference_agrees_with_the_system():
    cell = _float32(harness.load_cell(CELL, rehearsal=True))
    session = cell.driver.setup(cell, 7)
    got, want = session["first"], training.reference(cell, 7)
    cell.driver.close(session)
    shapes = cell.model.param_shapes(cell.sizes)
    assert len(got["losses"]) == 3
    assert set(want["first_update"]) == set(got["first_update"]) \
        == set(shapes)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    assert harness.update_difference(got["first_update"],
                                     want["first_update"]) < 2e-3
    assert harness.worst_leaf_gap(got["change_norms"],
                                  want["change_norms"])[0] < 2e-3
    # Adam's first update is the rate times the gradient's sign, in every
    # part of every kind of layer (a weight of 1 moves by whole float32
    # steps, and the decays' few numbers have gradients as small as
    # epsilon: neither is among them)
    lr = cell.config["optimizer"]["learning_rate"]
    for leaf in ("head_weight", "l0_qkvz_weight", "l1_ba_weight",
                 "l2_conv_weight", "l2_out_weight",
                 "l3_qkv_weight", "l3_o_weight", "l1_w1", "l2_w3", "l3_w2",
                 "l0_shared_gate_up_weight", "l3_shared_gate_weight",
                 "l1_router_weight"):
        moved = np.abs(want["first_update"][leaf])
        assert abs(np.median(moved[moved > 0]) / lr - 1) < 0.2, leaf


def test_weights_are_as_assumed():
    cell = harness.load_cell(CELL, rehearsal=True)
    sz = cell.sizes
    w = cell.model.make_weights(sz, 2 ** 31 + 5)
    for leaf in ("l1_ffn_norm_weight", "l0_mixer_norm_weight",
                 "l3_q_norm_weight", "l3_k_norm_weight", "final_norm_weight"):
        assert not np.asarray(w[leaf]).any(), leaf       # 1 + w, w = 0
    assert (np.asarray(w["l0_gate_norm_weight"]) == 1).all()
    assert (np.asarray(w["l2_dt_bias"]) == 1).all()
    a = np.exp(np.asarray(w["l1_a_log"]))
    assert (a > 0).all() and (a <= 16).all() and np.isfinite(a).all()
    conv = np.asarray(w["l0_conv_weight"])
    assert np.abs(conv).max() <= 0.5 and np.abs(conv).mean() > 0.2
    std = float(np.std(np.asarray(w["embed_weight"])))
    assert abs(std / sz["initializer_range"] - 1) < 0.05
    assert "l0_router_bias" not in w
    again = cell.model.make_weights(sz, 2 ** 31 + 5)
    np.testing.assert_array_equal(np.asarray(w["l2_w1"]),
                                  np.asarray(again["l2_w1"]))
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
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attn_kernel
    line = _run(ARGV + ["--seed", "3700000019", "--trace", "1"], capsys)
    # (`correct` holds the chip's limits, set at the cell's own sizes)
    assert line["failed"] == 0 and line["rehearsal"] is True
    m = line["metrics"]
    # shares of a roofline or of a peak, a program's name in the device
    # trace and the device's memory are device numbers: none from a
    # rehearsal on the CPU
    device_only = {"attn_roofline.train", "gdn_roofline.train",
                   "moe_gmm_roofline.train", "step_mfu_device.train",
                   "step_program_ms.train", "peak_hbm.train"}
    for name in (set(NEW_METRICS) | set(SHARED_METRICS)) - device_only:
        assert name in m, name
    assert not device_only & set(m)
    # heads of 16 and experts 48 wide on the CPU: the plain forms
    assert m["attn_kernel_sites.train"]["value"] == 0
    assert m["moe_gmm_kernel_sites.train"]["value"] == 0
    assert mx.telemetry.gauge(attn_kernel.FUSED_BWD_GAUGE).get() == 0
    assert m["moe_overflow_pairs.train"]["value"] == 0
    assert 0 < m["moe_dispatch_time_share.train"]["value"] \
        < m["moe_time_share.train"]["value"] < 100
    assert 0 < m["gdn_time_share.train"]["value"] < 100
    assert 0 < m["attn_time_share.train"]["value"] < 100
    assert 0 < m["moe_buffer_fill.train"]["value"] <= 100
    assert m["expert_load_max_over_mean.train"]["value"] >= 1
    assert m["remat_saved_gb.train"]["value"] > 0
    assert 0 < m["scoped_time_share.train"]["value"] <= 100


def test_scope_table_is_the_program_s_and_names_the_new_parts():
    import mxnet_tpu as mx
    cell = harness.load_cell(CELL, rehearsal=True)
    session = cell.driver.setup(cell, 13)
    table = cell.model.scope_table()
    assert table is session["system"].step.scope_table()
    assert table is mx.telemetry.trace.scope_table("jit_mx_train_step")
    scopes = set(table.values())
    for want in ("mx_gdn_proj", "mx_gdn_conv", "mx_gdn_rule", "mx_gdn_gate",
                 "mx_attn_qk_norm", "mx_attn_gate", "mx_attn_fwd",
                 "mx_attn_proj", "mx_rope", "mx_moe_shared/mx_gated_mlp",
                 "mx_moe_shared", "mx_moe_score", "mx_moe_route",
                 "mx_moe_dispatch", "mx_moe_gmm_up", "mx_moe_gmm_down",
                 "mx_moe_combine", "mx_head/mx_dense", "mx_norm",
                 "mx_opt_update", "mx_embed", "mx_loss"):
        assert want in scopes, (want, sorted(scopes))
    assert not any(s.startswith(("mx_ssd", "mx_mla", "mx_moe_latent"))
                   for s in scopes)
    cell.model.release_system()
    cell.driver.close(session)
    gauges = {k: v["value"] for k, v in mx.telemetry.snapshot().items()
              if k.startswith("moe::")}
    assert len([k for k in gauges if k.startswith("moe::pairs_held::")]) == 4
    assert all(v == 0 for k, v in gauges.items()
               if k.startswith("moe::overflow_pairs::"))
