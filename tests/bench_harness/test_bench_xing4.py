"""The cell ``xing4.0-29b-a4b-train-4k``: its plain reference against the
system at ``rehearsal_sizes`` on the CPU (three Adam steps through four
hyper-connected streams), its fp8 control standing apart, fewer Sinkhorn
iterations coming out apart too, the configuration's sizes against the
published ``config.json``, the cost functions against the arithmetic of
the cut, ``BENCHMARK.json``'s entries looked up by name, never by
position, and the metrics of a traced rehearsal run. (The step compiled
for a described v5e: ``test_bench_xing4_compile.py``.)"""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import training  # noqa: E402

CELL = "xing4.0-29b-a4b-train-4k"
CONFIG = "xing4.0-29b-a4b"
SOURCE = "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/" \
    "config.json"

# config.json of XingChen-AGI/Xing4.0-29B-A4B as the catalog beside the
# model-configs guide holds it (source_url in the .json)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072,
}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "num_attention_heads": 4,
           "num_key_value_heads": 4, "vocab_size": 16384,
           "num_nextn_predict_layers": 0}
NEW_METRICS = {
    "mhc_time_share.train": ("lower", "%", "device_trace", "Kernels"),
    "mhc_roofline.train": ("higher", "%", "device_trace", "Kernels"),
    "mhc_res_sum_dev.train": ("lower", "abs_err", "program_counter",
                              "Step program")}
SHARED_METRICS = (
    "device_idle.train", "step_device_ms.train", "step_program_ms.train",
    "peak_hbm.train", "step_mfu_device.train", "fresh_compiles.setup",
    "step_acquire_s.setup", "remat_saved_gb.train",
    "scoped_time_share.train", "opt_update_time_share.train",
    "head_time_share.train", "attn_time_share.train", "attn_roofline.train",
    "attn_kernel_sites.train", "mla_latent_time_share.train",
    "mla_latent_roofline.train", "moe_time_share.train",
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
    # no width among the reduced keys; an eighth of the heads, of the
    # experts and of the vocabulary: one of 8 chips that share a layer
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_attention_heads"] * 8 == PUBLISHED["num_attention_heads"]
    sizes = cfg["sizes"]
    assert sizes["router_experts"] == 64 \
        and sizes["expert_ids"] == list(range(8))
    assert sizes["seq_len"] == 4096 and sizes["batch"] == 1
    assert sizes["seq_len"] == sizes["rope_scaling"][
        "original_max_position_embeddings"]
    # one pool for the 8 held experts: whole tiles of 256 rows, no more
    # than 1.5 x the pairs at balance, which are the deployment's
    balanced = sizes["seq_len"] * 4 * 8 / 64
    assert balanced == 2048 and sizes["moe_buffer_rows"] % 256 == 0
    assert balanced < sizes["moe_buffer_rows"] <= 1.5 * balanced
    # the rehearsal changes sizes, never the structure
    small = cfg["rehearsal_sizes"]
    assert set(small) == set(sizes)
    for key in ("num_hidden_layers", "first_k_dense_replace", "hc_mult",
                "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                "mhc_h_res_clamp_max", "rope_scaling", "rope_theta",
                "rms_norm_eps", "norm_topk_prob", "routed_scaling_factor",
                "hc_alpha_init", "hc_res_sum_dev_max"):
        assert small[key] == sizes[key], key
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] \
        == dep["tensor_parallel_attention_heads"] \
        == dep["vocabulary_parallel"] == 8
    for key in ("published", "deployment", "assumed", "departures",
                "sizes", "rehearsal_sizes", "limits"):
        assert cfg[key], key
    said = " ".join(cfg["departures"])
    for what in ("multi-token-prediction", "wd 0", "learning_rate 1e-6"):
        assert what in said, what
    assumed = " ".join(cfg["assumed"])
    for what in ("arXiv:2512.24880", "normal(0, 1) FROM THE SEED",
                 "rotate_half", "columns divided by their sums"):
        assert what in assumed, what
    for name in ("loss_rel", "first_grad_rel", "change_rel",
                 "first_step_diff"):
        limit = cfg["limits"]["step"][name]
        assert 0 < limit["limit"] < 1 and "my chip runs, PR 43" in limit["why"]


def test_benchmark_json_holds_the_cell_and_its_metrics_by_name():
    bench = harness.benchmark_json(proposed=False)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "step-ring",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in metrics["train_throughput"]["workloads"]
    assert metrics["train_throughput"]["workloads"].count(CELL) == 1
    for name in SHARED_METRICS:
        assert metrics[name]["workloads"].count(CELL) == 1, name
    for name, (better, unit, source, layer) in NEW_METRICS.items():
        m = metrics[name]
        assert CELL in m["workloads"] and m["workloads"].count(CELL) == 1
        assert (m["better"], m["unit"], m["source"], m["layer"],
                m["moves"]) == (better, unit, source, layer,
                                "train_throughput"), name
        spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                              name + ".json"))
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    share, roofline, left = (harness.load_json(os.path.join(
        BENCH, "layer_metrics", name + ".json")) for name in NEW_METRICS)
    assert share["reader"] == "trace_scope_busy" \
        and share["params"]["scopes"] == "^mx_mhc_"
    assert roofline["params"]["scopes"] == "^mx_mhc_(maps|pre|post)$" \
        and roofline["params"]["cost"] == "mhc_cost"
    assert left == {"reader": "program_gauge", "params": {
        "pattern": "^mhc::res_sum_dev::", "reduce": "max"}}
    # no metric of another cell's mechanism lists this one
    for name in ("ssd_time_share.train", "loop_time_share.train",
                 "gdn_roofline.train", "conv_time_share.train"):
        assert CELL not in metrics[name]["workloads"], name


def test_costs_are_the_arithmetic_of_the_cut():
    cell = harness.load_cell(CELL)
    model, sz = cell.model, cell.sizes
    shapes = model.param_shapes(sz)
    count = lambda keep: sum(int(np.prod(s)) for k, s in shapes.items()  # noqa
                             if keep(k))
    attention = 2_752_512 + 589_824 + 2_064_384 + 524_288 + 1_835_008 + 1_280
    assert attention == 7_767_296
    assert count(lambda k: k.startswith("l2_") and k[3:] in (
        "q_down_weight", "q_norm_weight", "q_weight", "kv_down_weight",
        "kv_norm_weight", "kv_up_weight", "o_weight")) == attention
    assert count(lambda k: k.startswith("l3_") and "_hc_" in k) == 688_182
    assert count(lambda k: k.startswith("l0_") and k[3:] in (
        "gate_up_weight", "down_weight")) == 99_090_432
    assert count(lambda k: k.startswith("l4_") and k[3:] in (
        "router_weight", "w1", "w3", "w2", "shared_gate_up_weight",
        "shared_down_weight")) == 229_376 + 9 * 11_010_048 == 99_319_808
    trained = count(lambda k: not k.endswith("router_bias"))
    assert trained == 5 * (attention + 688_182 + 7_168) + 99_090_432 \
        + 4 * 99_319_808 + 117_444_096 == 656_126_990
    assert model.pattern(sz) == "LGLFLFLFLF"
    macs = model.forward_macs(sz)
    assert macs["mla.projections"] == 5 * (attention - 1_280)
    assert macs["mla.scores"] == 5 * 4 * 320 * 4097 / 2
    assert macs["mhc.maps"] == 10 * 24 * 14336 == 10 * (344_091 - 27)
    assert macs["mhc.mixes"] == 10 * 24 * 3584
    assert macs["dense.mlp"] == 99_090_432
    assert macs["experts.shared"] == 4 * 11_010_048
    assert macs["experts.routed"] == 4 * sz["moe_buffer_rows"] * 11_010_048 \
        / 4096
    assert macs["head"] == 16384 * 3584
    total = sum(macs.values())
    assert 280e6 < total < 300e6
    assert 0.32 < macs["dense.mlp"] / total < 0.36
    assert 0.19 < macs["head"] / total < 0.22
    assert 0.012 < (macs["mhc.maps"] + macs["mhc.mixes"]) / total < 0.018
    assert model.items_per_step(sz) == 4096
    assert model.flops_per_item(sz, "train") == 6 * total
    peaks = harness.peaks_for("TPU v5 lite")
    operations, moved = model.attn_cost(sz)
    assert operations == 6 * 4096 * macs["mla.scores"]
    operations, moved = model.mla_latent_cost(sz)
    assert operations == 6 * 4096 * macs["mla.projections"]
    assert operations / peaks["bf16_flops"] \
        > moved / peaks["hbm_bytes_per_s"] > 0
    operations, moved = model.moe_gmm_cost(sz)
    assert operations == 6 * 4096 * macs["experts.routed"]
    # the hyper-connections need few operations for their bytes: their
    # floor is the memory's: the streams three times read and once
    # written, u and y, 10 sublayers, three passes
    operations, moved = model.mhc_cost(sz)
    assert moved == 10 * 3 * 4096 * 2 * (4 * 14336 + 2 * 3584)
    assert operations > 6 * 4096 * (macs["mhc.maps"] + macs["mhc.mixes"])
    assert moved / peaks["hbm_bytes_per_s"] \
        > 20 * operations / peaks["bf16_flops"] > 0
    assert 0.015 < moved / peaks["hbm_bytes_per_s"] < 0.025


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    ref = text[text.index("# --- reference: begin"):
               text.index("# --- reference: end")]
    assert "import mxnet_tpu" not in ref and "from mxnet_tpu" not in ref
    for name in ("def token_maps", "def hyper_connection",
                 "def yarn_frequencies", "def softmax_scale",
                 "def latent_attention", "def moe_layer", "def router",
                 "def reference_loss", "def adam_step"):
        assert name in ref, name
    # the hyper-connection a token at a time: a vmap of the per-token form,
    # its Sinkhorn a plain loop of the published number of iterations,
    # columns before rows
    maps = ref[ref.index("def token_maps"):ref.index("def stream_maps")]
    assert 'length=sz["hc_sinkhorn_iters"]' in maps
    assert maps.index("axis=0") < maps.index("axis=1")
    assert "jax.vmap(lambda x: token_maps(" in ref


# -- the reference against the system -----------------------------------------
SEED = 7
_SOUND = {}


def _sound():
    """The system's first steps in float32 and the reference's on one
    seed, with what the live system's table and counters showed: one
    set-up for the tests below."""
    if not _SOUND:
        import mxnet_tpu as mx
        cell = _float32(harness.load_cell(CELL, rehearsal=True))
        session = cell.driver.setup(cell, SEED)
        table = cell.model.scope_table()
        _SOUND.update(
            cell=cell, got=session["first"], scopes=set(table.values()),
            own=table is session["system"].step.scope_table()
            and table is mx.telemetry.trace.scope_table("jit_mx_train_step"),
            want=training.reference(cell, SEED))      # releases the system
        cell.driver.close(session)
        _SOUND["gauges"] = {
            k: v["value"] for k, v in mx.telemetry.snapshot().items()
            if k.startswith(("moe::", "mhc::"))}
    return _SOUND


def test_reference_agrees_with_the_system():
    cell, got, want = (_sound()[k] for k in ("cell", "got", "want"))
    shapes = cell.model.param_shapes(cell.sizes)
    assert len(got["losses"]) == 3
    assert set(want["first_update"]) \
        == {k for k in shapes if not k.endswith("router_bias")} \
        <= set(got["first_update"])
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    assert harness.update_difference(got["first_update"],
                                     want["first_update"]) < 2e-3
    big = [k for k, v in want["first_update"].items() if v.size >= 64]
    assert harness.worst_leaf_gap(got["change_norms"],
                                  want["change_norms"], big)[0] < 2e-3
    # Adam's first update is the rate times the gradient's sign, in every
    # part of every kind of sublayer, the hyper-connections' among them
    lr = cell.config["optimizer"]["learning_rate"]
    for leaf in ("head_weight", "l0_q_down_weight", "l2_q_weight",
                 "l1_kv_up_weight", "l4_o_weight", "l0_gate_up_weight",
                 "l1_w1", "l3_w2", "l2_shared_gate_up_weight",
                 "l4_router_weight"):
        moved = np.abs(want["first_update"][leaf])
        assert abs(np.median(moved[moved > 0]) / lr - 1) < 0.2, leaf
    # the hyper-connections' leaves move too, by less than the rate where
    # a gradient (through alpha = 0.01) is as small as Adam's epsilon; in
    # layer 0's first sublayer the streams are still copies of one vector,
    # so H_res X = X whatever the matrix and the norm undoes H_pre's
    # scale: only H_post's rows of phi get a gradient there
    for leaf in ("l3_ffn_hc_weight", "l2_ffn_hc_bias", "l1_attn_hc_alpha",
                 "l4_ffn_hc_alpha", "l0_attn_hc_weight"):
        moved = np.abs(want["first_update"][leaf])
        assert 0.05 < moved.max() / lr < 1.2, leaf
        np.testing.assert_allclose(got["first_update"][leaf],
                                   want["first_update"][leaf],
                                   atol=0.05 * lr)
    first = np.abs(want["first_update"]["l0_attn_hc_weight"])
    assert first[4:8].max() > 100 * max(first[:4].max(), first[8:].max())


def test_scope_table_is_the_program_s_and_names_the_new_parts():
    sound = _sound()
    assert sound["own"]
    for want in ("mx_mhc_maps", "mx_mhc_pre", "mx_mhc_post", "mx_mhc_in",
                 "mx_mhc_out", "mx_mla_q", "mx_mla_kv_down", "mx_mla_kv_up",
                 "mx_mla_out", "mx_mla_rope/mx_rope", "mx_attn_fwd",
                 "mx_gated_mlp", "mx_moe_shared/mx_gated_mlp",
                 "mx_moe_score", "mx_moe_route", "mx_moe_dispatch",
                 "mx_moe_gmm_up", "mx_moe_gmm_down", "mx_moe_combine",
                 "mx_head/mx_dense", "mx_norm", "mx_opt_update", "mx_embed",
                 "mx_loss"):
        assert want in sound["scopes"], (want, sorted(sound["scopes"]))
    assert not any(s.startswith(("mx_ssd", "mx_gdn", "mx_moe_latent"))
                   for s in sound["scopes"])
    gauges = sound["gauges"]
    assert len([k for k in gauges if k.startswith("moe::pairs_held::")]) == 4
    left = [v for k, v in gauges.items()
            if k.startswith("mhc::res_sum_dev::")]
    assert len(left) == 10 and all(0 < v < 1e-4 for v in left)
    assert gauges["mhc::sites"] >= 1
    assert all(v == 0 for k, v in gauges.items()
               if k.startswith("moe::overflow_pairs::"))


def test_lower_precision_and_fewer_iterations_stand_apart(monkeypatch):
    cell, got, want = (_sound()[k] for k in ("cell", "got", "want"))
    sound = harness.update_difference(got["first_update"],
                                      want["first_update"])
    control = harness.update_difference(
        training.reference(cell, SEED, "fp8")["first_update"],
        want["first_update"])
    assert control > 0.03 and control > 3 * sound
    # a program that ran one Sinkhorn iteration where the model asks for
    # twenty is another model: with b of order 1 its first step differs
    # by far more than rounding (same weights, same calibrated bias)
    short = _float32(harness.load_cell(CELL, rehearsal=True))
    net = short.model._net
    monkeypatch.setattr(short.model, "_net", lambda sz: net(
        dict(sz, hc_sinkhorn_iters=1)))
    session = short.driver.setup(short, SEED)
    one = harness.update_difference(session["first"]["first_update"],
                                    want["first_update"])
    # and its H_res is no longer doubly stochastic: every step fails
    assert not np.isfinite(session["first"]["losses"]).any()
    assert np.isfinite(got["losses"]).all()
    short.model.release_system()
    short.driver.close(session)
    assert one > 0.03 and one > 100 * sound


def test_weights_are_as_assumed():
    cell = harness.load_cell(CELL, rehearsal=True)
    sz = cell.sizes
    w = cell.model.make_weights(sz, 2 ** 31 + 5)
    for leaf in ("l1_ffn_norm_weight", "l0_attn_norm_weight",
                 "l3_q_norm_weight", "l3_kv_norm_weight",
                 "final_norm_weight"):
        assert (np.asarray(w[leaf]) == 1).all(), leaf
    assert (np.asarray(w["l2_ffn_hc_alpha"]) == np.float32(0.01)).all()
    # b of order 1: no map starts near the identity
    b = np.concatenate([np.asarray(w[f"l{i}_{s}_hc_bias"])
                        for i in range(5) for s in ("attn", "ffn")])
    assert 0.8 < b.std() < 1.2 and abs(b.mean()) < 0.3
    std = float(np.std(np.asarray(w["embed_weight"])))
    assert abs(std / sz["initializer_range"] - 1) < 0.05
    out = float(np.std(np.asarray(w["l1_w2"])))
    assert abs(out * np.sqrt(80) / sz["initializer_range"] - 1) < 0.1
    assert np.asarray(w["l1_router_bias"]).any()          # calibrated
    assert "l0_router_bias" not in w
    again = cell.model.make_weights(sz, 2 ** 31 + 5)
    np.testing.assert_array_equal(np.asarray(w["l2_w1"]),
                                  np.asarray(again["l2_w1"]))
    (x, y), = cell.model.make_batches(sz, 2 ** 31 + 5, 1)
    assert x.shape == (sz["batch"], sz["seq_len"]) and x.max() < 211
    np.testing.assert_array_equal(x[:, 1:].reshape(-1),
                                  y.reshape(x.shape)[:, :-1].reshape(-1))


# -- runs through run.py ------------------------------------------------------
def _run(argv, capsys):
    import run
    run.main(argv)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


ARGV = ["--workload", CELL, "--seconds", "0.5", "--rehearsal", "1"]


def test_traced_run_reports_every_metric_of_the_cell(capsys):
    import mxnet_tpu as mx
    from mxnet_tpu.ops import seq
    line = _run(ARGV + ["--seed", "4300000019", "--trace", "1"], capsys)
    # (`correct` holds the chip's limits, set at the cell's own sizes)
    assert line["failed"] == 0 and line["rehearsal"] is True
    m = line["metrics"]
    # shares of a roofline or of a peak, a program's name in the device
    # trace and the device's memory are device numbers: none from a
    # rehearsal on the CPU
    device_only = {"attn_roofline.train", "mla_latent_roofline.train",
                   "mhc_roofline.train", "moe_gmm_roofline.train",
                   "step_mfu_device.train", "step_program_ms.train",
                   "peak_hbm.train"}
    for name in (set(NEW_METRICS) | set(SHARED_METRICS)) - device_only:
        assert name in m, name
    assert not device_only & set(m)
    # heads of 16 and experts 48 wide on the CPU: the plain forms
    assert m["attn_kernel_sites.train"]["value"] == 0
    assert m["moe_gmm_kernel_sites.train"]["value"] == 0
    assert mx.telemetry.gauge(seq.MHC_GAUGE).get() >= 1
    assert m["moe_overflow_pairs.train"]["value"] == 0
    assert 0 < m["mhc_time_share.train"]["value"] < 100
    assert 0 < m["mla_latent_time_share.train"]["value"] < 100
    assert 0 < m["moe_time_share.train"]["value"] < 100
    assert 0 < m["mhc_res_sum_dev.train"]["value"] < 1e-2
    assert 0 < m["moe_buffer_fill.train"]["value"] <= 100
    assert m["expert_load_max_over_mean.train"]["value"] >= 1
    assert m["remat_saved_gb.train"]["value"] > 0
    assert 0 < m["scoped_time_share.train"]["value"] <= 100
