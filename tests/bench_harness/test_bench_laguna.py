"""The cell ``laguna-s-2.1-train-8k``: its plain reference against the
system at ``rehearsal_sizes`` on the CPU (three Adam steps through a full
dense layer, three sliding expert layers and a full expert layer), every
row of ``correct`` under the committed limits, the fp8 control and four
wrong programs (the window ignored, the window one key too long, the
gate a head left out, YaRN's attention factor left out) each over one,
the configuration's sizes against the published ``config.json``, the cost
functions against a count by hand, ``BENCHMARK.json``'s entries looked up
by name, never by position, and the metrics of a traced rehearsal run.
(The step compiled for a described v5e: ``tests/test_laguna_v5e.py``.)"""
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

CELL = "laguna-s-2.1-train-8k"
CONFIG = "laguna-s-2.1"
SOURCE = "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"

# config.json of poolside/Laguna-S-2.1 as the catalog beside the
# model-configs guide holds it (source_url in the .json)
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": PERIOD * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0,
}
REDUCED = {"num_hidden_layers": 5,
           "layer_types": PERIOD + ["full_attention"],
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "gating_types": ["per_head"] * 5,
           "num_attention_heads_per_layer": [24, 36, 36, 36, 24],
           "num_attention_heads": 24, "num_key_value_heads": 4,
           "num_experts": 8, "vocab_size": 12544}
NEW_METRICS = {
    "swa_time_share.train": ("lower", "%", "device_trace", "Kernels"),
    "swa_roofline.train": ("higher", "%", "device_trace", "Kernels"),
    "swa_kernel_sites.train": ("higher", "sites", "program_counter",
                               "Kernels")}
SHARED_METRICS = (
    "device_idle.train", "step_device_ms.train", "step_program_ms.train",
    "peak_hbm.train", "step_mfu_device.train", "scoped_time_share.train",
    "opt_update_time_share.train", "head_time_share.train",
    "remat_saved_gb.train", "fresh_compiles.setup", "step_acquire_s.setup",
    "moe_time_share.train", "moe_gmm_roofline.train",
    "moe_gmm_kernel_sites.train", "moe_rows_kernel_sites.train",
    "moe_dispatch_time_share.train", "expert_load_max_over_mean.train",
    "moe_buffer_fill.train", "moe_overflow_pairs.train",
    "attn_time_share.train", "attn_roofline.train",
    "attn_kernel_sites.train")
TRAINED = 672_125_952


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
    assert len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        for where in (cfg, cfg["sizes"]):
            if key in REDUCED:
                assert where[key] == REDUCED[key], key
                assert cfg["published"][key] == value, key
            else:
                assert where[key] == value, key
    # no width among the reduced keys: half the heads, a thirty-second of
    # the experts, an eighth of the vocabulary
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key, share in (("vocab_size", 8), ("num_attention_heads", 2),
                       ("num_key_value_heads", 2), ("num_experts", 32)):
        assert cfg[key] * share == PUBLISHED[key], key
    # the held layers in the published order: layers 0-4, and a held
    # layer's heads stand 6 and 9 to a key/value head as published
    for key in ("layer_types", "mlp_layer_types", "gating_types"):
        assert cfg[key] == PUBLISHED[key][:5], key
    assert cfg["num_attention_heads_per_layer"] == [
        n // 2 for n in PUBLISHED["num_attention_heads_per_layer"][:5]]
    assert [n // cfg["num_key_value_heads"]
            for n in cfg["num_attention_heads_per_layer"]] == [6, 9, 9, 9, 6]
    sizes = cfg["sizes"]
    assert sizes["router_experts"] == 256 \
        and sizes["expert_ids"] == list(range(8))
    assert sizes["seq_len"] == 8192 and sizes["batch"] == 1
    assert sizes["seq_len"] == PUBLISHED["rope_parameters"][
        "full_attention"]["original_max_position_embeddings"]
    # one pool for the 8 held experts: whole tiles of 256 rows, 1.6 x the
    # pairs at balance
    balanced = sizes["seq_len"] * 10 * 8 / 256
    assert balanced == 2560 and sizes["moe_buffer_rows"] % 256 == 0
    assert 1.5 * balanced <= sizes["moe_buffer_rows"] <= 2 * balanced
    # the rehearsal changes sizes, never the structure; its window is
    # shorter than its length, and crosses the plain form's blocks
    small = cfg["rehearsal_sizes"]
    assert set(small) == set(sizes)
    for key in ("num_hidden_layers", "layer_types", "mlp_layer_types",
                "gating_types", "mlp_only_layers", "gating",
                "norm_topk_prob", "moe_routed_scaling_factor",
                "rms_norm_eps", "initializer_range"):
        assert small[key] == sizes[key], key
    assert small["sliding_window"] < small["seq_len"]
    assert small["sliding_window"] % small["attention_block"] == 0 \
        or small["sliding_window"] > small["attention_block"]
    full = small["rope_parameters"]["full_attention"]
    assert full["rope_type"] == "yarn" \
        and full["partial_rotary_factor"] == 0.5 \
        and full["attention_factor"] == 1.4852030263919618
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == 32
    assert dep["head_parallel"] == 2 and dep["data_parallel_groups"] == 16 \
        and dep["vocabulary_parallel"] == 8
    assert "No code stands in for the 31 absent chips" in dep["held"]
    for key in ("published", "deployment", "assumed", "departures",
                "sizes", "rehearsal_sizes", "limits", "precision"):
        assert cfg[key], key
    assumed = " ".join(cfg["assumed"])
    for what in ("softmax", "ungated", "SiLU", "sigmoid(W_g u)",
                 "no head norms", "0 <= t - j < 512", "attention_factor",
                 "64 rotated elements", "rotate_half", "[q | k | v | gate]",
                 "initializer_range", "the plain start", "learning_rate 1e-6",
                 "reference_attention_block"):
        assert what in assumed, what
    for name in ("loss_rel", "first_grad_rel", "change_rel",
                 "first_step_diff"):
        limit = cfg["limits"]["step"][name]
        assert 0 < limit["limit"] < 1 and "my chip runs, PR 50" in limit["why"]


def test_benchmark_json_holds_the_cell_and_its_metrics_by_name():
    bench = harness.benchmark_json(proposed=False)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "step-ring",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "over share" in cell["why"]
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert metrics["train_throughput"]["workloads"].count(CELL) == 1
    for name in SHARED_METRICS:
        assert metrics[name]["workloads"].count(CELL) == 1, name
    for name, (better, unit, source, layer) in NEW_METRICS.items():
        m = metrics[name]
        assert m["workloads"] == [CELL], name
        assert (m["better"], m["unit"], m["source"], m["layer"],
                m["moves"]) == (better, unit, source, layer,
                                "train_throughput"), name
    share, roofline, sites = (harness.load_json(os.path.join(
        BENCH, "layer_metrics", name + ".json")) for name in NEW_METRICS)
    assert share == {"reader": "trace_scope_busy",
                     "params": {"scopes": "(^|/)mx_swa_fwd$"}}
    assert roofline == {"reader": "trace_scope", "params": {
        "what": "roofline", "scopes": "(^|/)mx_swa_fwd$",
        "cost": "swa_cost", "peak_flops": "bf16_flops",
        "peak_bytes_per_s": "hbm_bytes_per_s"}}
    assert sites == {"reader": "program_gauge", "params": {
        "pattern": "^attn::window_sites$", "reduce": "sum"}}
    # no metric of another cell's mechanism lists this one
    for name in ("ssd_time_share.train", "loop_time_share.train",
                 "gdn_roofline.train", "mhc_roofline.train",
                 "mla_latent_roofline.train", "sconv_roofline.train",
                 "conv_time_share.train"):
        assert CELL not in metrics[name]["workloads"], name


def test_costs_are_a_count_by_hand():
    cell = harness.load_cell(CELL)
    model, sz = cell.model, cell.sizes
    shapes = model.param_shapes(sz)
    count = lambda keep: sum(int(np.prod(s)) for k, s in shapes.items()  # noqa
                             if keep(k))
    mixer = ("qkv_weight", "o_weight")
    full = 2 * 9_437_184 + 2 * 1_572_864 + 73_728
    sliding = 2 * 14_155_776 + 3_145_728 + 110_592
    assert (full, sliding) == (22_093_824, 31_567_872)
    for layer, want in ((0, full), (1, sliding), (2, sliding), (3, sliding),
                        (4, full)):
        assert count(lambda k: k.startswith(f"l{layer}_")
                     and k[3:] in mixer) == want, layer
    assert count(lambda k: k.startswith("l0_") and k[3:] in (
        "gate_up_weight", "down_weight")) == 113_246_208
    experts = 8 * 9_437_184 + 786_432 + 9_437_184
    assert count(lambda k: k.startswith("l3_") and k[3:] in (
        "router_weight", "w1", "w3", "w2", "shared_gate_up_weight",
        "shared_down_weight")) == experts == 85_721_088
    assert count(lambda k: k.endswith("norm_weight")) == 11 * 3072
    assert count(lambda k: True) == 2 * full + 3 * sliding + 113_246_208 \
        + 4 * experts + 33_792 + 2 * 38_535_168 == TRAINED
    assert model.pattern(sz) == "*GWFWFWF*F"
    macs = model.forward_macs(sz)
    assert macs["attn.projections"] == 2 * full + 3 * sliding == 138_891_264
    assert macs["attn.scores.full"] == 2 * 24 * 256 * 8193 / 2
    band = 512 * 513 // 2 + (8192 - 512) * 512
    assert model._pairs(sz, True) == band == sum(
        min(t + 1, 512) for t in range(8192))
    assert macs["attn.scores.sliding"] == 3 * 36 * 256 * band / 8192
    assert macs["dense.mlp"] == 113_246_208
    assert macs["experts.router"] == 4 * 786_432
    assert macs["experts.shared"] == 4 * 9_437_184
    assert macs["experts.routed"] == 4 * 4096 * 9_437_184 / 8192
    assert macs["head"] == 12_544 * 3072
    total = sum(macs.values())
    # ISSUE 50's shares of the forward: attention's projections 34 %, the
    # full layers' scores 12 %, the band 3.4 % (an eighth of the
    # unwindowed 113 M), dense MLP 28 %, shared experts 9 %, head 9.5 %
    assert 400e6 < total < 416e6
    assert 0.33 < macs["attn.projections"] / total < 0.35
    assert 0.11 < macs["attn.scores.full"] / total < 0.13
    assert 0.03 < macs["attn.scores.sliding"] / total < 0.04
    unwindowed = 3 * 36 * 256 * 8193 / 2
    assert 7.9 < unwindowed / macs["attn.scores.sliding"] < 8.3
    attention = macs["attn.projections"] + macs["attn.scores.full"] \
        + macs["attn.scores.sliding"]
    assert 0.48 < attention / total < 0.52         # half the step
    assert 0.27 < macs["dense.mlp"] / total < 0.29
    assert model.items_per_step(sz) == 8192
    assert model.flops_per_item(sz, "train") == 6 * total
    # the two kinds' needs: pairs the mask lets through, 256 multiply-
    # accumulates a pair, 2 operations each, three passes
    operations, moved = model.attn_cost(sz)
    assert operations == 6 * 8192 * macs["attn.scores.full"]
    assert moved == 2 * 3 * 8192 * ((2 * 24 + 8) * 128 * 2 + 24 * 4)
    operations, moved = model.swa_cost(sz)
    assert operations == 3 * 6 * 36 * 256 * band \
        == 6 * 8192 * macs["attn.scores.sliding"]
    assert moved == 3 * 3 * 8192 * ((2 * 36 + 8) * 128 * 2 + 36 * 4)
    peaks = harness.peaks_for("TPU v5 lite")
    # bound by the arithmetic, not the bytes: 3.4 ms against 1.8 ms
    assert operations / peaks["bf16_flops"] \
        > 1.5 * moved / peaks["hbm_bytes_per_s"]
    operations, moved = model.moe_gmm_cost(sz)
    assert operations == 6 * 8192 * macs["experts.routed"]
    assert moved == 4 * 3 * 2 * (8 * 9_437_184 + 4096 * (6144 + 2048))


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    ref = text[text.index("# --- reference: begin"):
               text.index("# --- reference: end")]
    assert "import mxnet_tpu" not in ref and "from mxnet_tpu" not in ref
    for name in ("def frequencies", "def rotate", "def attention",
                 "def gated_mlp", "def router", "def moe_layer",
                 "def reference_loss", "def adam_step"):
        assert name in ref, name
    # the mask is written out: both edges, on every key
    body = ref[ref.index("def attention"):ref.index("def gated_mlp")]
    assert "back >= 0" in body and "back < window" in body


# -- the reference against the system -----------------------------------------
SEED = 7
_SOUND = {}


def _rows(cell, got, want):
    return {name: (value, limit) for name, value, limit, _ in
            harness.compare_training(got, want, cell.limits)}


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
            leaves={cell.model._leaf_of(k): int(np.prod(p.shape))
                    for k, p in session["system"].net.collect_params()
                    .items() if p.grad_req != "null"},
            want=training.reference(cell, SEED))      # releases the system
        cell.driver.close(session)
        _SOUND["gauges"] = {
            k: v["value"] for k, v in mx.telemetry.snapshot().items()
            if k.startswith(("moe::", "attn::"))}
    return _SOUND


def test_reference_agrees_with_the_system_and_correct_is_true():
    cell, got, want = (_sound()[k] for k in ("cell", "got", "want"))
    shapes = cell.model.param_shapes(cell.sizes)
    assert len(got["losses"]) == 3
    assert set(want["first_update"]) == set(shapes) \
        == set(got["first_update"])
    # the leaves the program trains are the reference's, size by size
    assert _sound()["leaves"] == {k: int(np.prod(s))
                                  for k, s in shapes.items()}
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    rows = _rows(cell, got, want)
    for name, (value, limit) in rows.items():
        assert value <= limit, (name, value, limit)
    assert rows["first_step_diff"][0] < 0.02
    # Adam's first update is the rate times the gradient's sign, in every
    # part of every kind of sublayer
    lr = cell.config["optimizer"]["learning_rate"]
    for leaf in ("head_weight", "l0_qkv_weight", "l2_qkv_weight",
                 "l4_o_weight", "l0_gate_up_weight", "l1_w1", "l3_w2",
                 "l4_router_weight", "l2_shared_down_weight",
                 "l2_attn_norm_weight"):
        moved = np.abs(want["first_update"][leaf])
        assert abs(np.median(moved[moved > 0]) / lr - 1) < 0.2, leaf
    # the gates' rows, the last of the packed projection, learn too
    gates = np.abs(want["first_update"]["l2_qkv_weight"][-6:])
    assert (gates > 0.5 * lr).mean() > 0.9


def test_the_timed_sizes_leaves_add_up_to_the_count_in_the_file():
    """The parameter count in ``source_note`` is the program's trained
    leaves' (shapes alone: no weight is made)."""
    cell = harness.load_cell(CELL)
    net = cell.model._net(cell.sizes)
    trained = sum(int(np.prod(p.shape))
                  for p in net.collect_params().values()
                  if p.grad_req != "null")
    assert trained == TRAINED
    assert "672,125,952 trained parameters" in cell.config["source_note"]


def test_scope_table_is_the_program_s_and_names_the_new_parts():
    sound = _sound()
    assert sound["own"]
    for want in ("mx_swa_fwd", "mx_attn_fwd", "mx_attn_gate", "mx_attn_proj",
                 "mx_rope", "mx_gated_mlp", "mx_moe_score", "mx_moe_route",
                 "mx_moe_dispatch", "mx_moe_gmm_up", "mx_moe_gmm_down",
                 "mx_moe_combine", "mx_moe_shared/mx_gated_mlp",
                 "mx_head/mx_dense", "mx_norm", "mx_opt_update", "mx_embed",
                 "mx_loss"):
        assert want in sound["scopes"], (want, sorted(sound["scopes"]))
    # the new scope encloses nothing and stands inside nothing
    assert not [p for p in sound["scopes"] if "mx_swa_fwd" in p and "/" in p]
    assert not any(s.startswith(("mx_ssd", "mx_gdn", "mx_mla", "mx_mhc",
                                 "mx_sconv", "mx_attn_qk_norm"))
                   for s in sound["scopes"])
    gauges = sound["gauges"]
    assert len([k for k in gauges if k.startswith("moe::pairs_held::")]) == 4
    assert all(v == 0 for k, v in gauges.items()
               if k.startswith("moe::overflow_pairs::"))
    # off a TPU no site takes the kernels, with a window or without
    assert gauges["attn::kernel_sites"] == gauges["attn::window_sites"] \
        == gauges["attn::fused_bwd_sites"] == 0


def _over(cell, session, want):
    """The rows of ``correct`` that a program's first steps miss."""
    rows = _rows(cell, session["first"], want)
    cell.model.release_system()
    cell.driver.close(session)
    return {name: value for name, (value, limit) in rows.items()
            if not value <= limit}, rows


def _wrong_no_window(monkeypatch):
    """The sliding layers as full attention."""
    from mxnet_tpu.ops import seq
    blocked = seq._blocked_attention
    monkeypatch.setattr(
        seq, "_blocked_attention",
        lambda *a, window=None, **kw: blocked(*a, **kw))


def _wrong_window_edge(monkeypatch):
    """``t - j <= W``: one key too many."""
    from mxnet_tpu.ops import seq
    blocked = seq._blocked_attention
    monkeypatch.setattr(
        seq, "_blocked_attention",
        lambda *a, window=None, **kw: blocked(
            *a, window=None if window is None else window + 1, **kw))


def _wrong_no_gate(monkeypatch):
    """The heads' outputs as they are: the gates' rows of the projection
    are not read."""
    from mxnet_tpu.ops import get_op, seq
    attend = seq.causal_gq_attention

    def ungated(data, *norms, head_gate=False, num_heads=1, **kw):
        assert head_gate
        return attend(data[..., :data.shape[-1] - num_heads], *norms,
                      num_heads=num_heads, **kw)

    monkeypatch.setattr(get_op("CausalGQAttention"), "fn", ungated)


def _wrong_no_factor(monkeypatch):
    """YaRN's frequencies without its attention factor on cos and sin."""
    from mxnet_tpu.ops import seq
    rope = seq.rope
    monkeypatch.setattr(
        seq, "rope", lambda data, theta=1e4, rotary_dim=None, yarn=None,
        mscale=None, **kw: rope(data, theta, rotary_dim, yarn, None))


@pytest.mark.parametrize("wrong", [_wrong_no_window, _wrong_window_edge,
                                   _wrong_no_gate, _wrong_no_factor])
def test_a_wrong_program_is_not_correct(wrong, monkeypatch):
    """Four programs that compute another model, each from the same
    weights in float32: at least one row of ``correct`` is over its
    committed limit, where the sound program is under every one."""
    from mxnet_tpu.ndarray import ndarray
    want = _sound()["want"]
    cell = _float32(harness.load_cell(CELL, rehearsal=True))
    monkeypatch.setattr(ndarray, "_JIT_CACHE", {})    # no earlier trace
    wrong(monkeypatch)
    over, rows = _over(cell, cell.driver.setup(cell, SEED), want)
    print(wrong.__name__, {k: v[0] for k, v in rows.items()})
    assert over, rows


def test_the_control_is_not_correct():
    cell, want = _sound()["cell"], _sound()["want"]
    rows = _rows(cell, training.reference(cell, SEED, "fp8"), want)
    print("fp8 control", {k: v[0] for k, v in rows.items()})
    assert [name for name, (value, limit) in rows.items() if value > limit]
    sound = _rows(cell, _sound()["got"], want)["first_step_diff"][0]
    assert rows["first_step_diff"][0] > 20 * sound


def test_weights_are_as_assumed():
    cell = harness.load_cell(CELL, rehearsal=True)
    sz = cell.sizes
    w = cell.model.make_weights(sz, 2 ** 31 + 5)
    for leaf in ("l1_ffn_norm_weight", "l0_attn_norm_weight",
                 "final_norm_weight"):
        assert (np.asarray(w[leaf]) == 1).all(), leaf
    std = float(np.std(np.asarray(w["embed_weight"])))
    assert abs(std / sz["initializer_range"] - 1) < 0.05
    # the gates' rows start like the rest of the projection: gates near a
    # half that differ by head
    gates = np.asarray(w["l1_qkv_weight"])[-6:]
    assert abs(float(gates.std()) / sz["initializer_range"] - 1) < 0.2
    # the plain start: a sublayer's last product is not scaled down (with
    # it scaled the held experts' loads ran away on the chip: `assumed`)
    for leaf in ("l1_w2", "l0_o_weight", "l2_o_weight", "l0_down_weight",
                 "l3_shared_down_weight"):
        out = float(np.std(np.asarray(w[leaf])))
        assert abs(out / sz["initializer_range"] - 1) < 0.1, leaf
    assert "rescale_layers" not in sz
    assert not [k for k in w if "router_bias" in k]
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
    line = _run(ARGV + ["--seed", "5000000019", "--trace", "1"], capsys)
    # (`correct` holds the chip's limits, set at the cell's own sizes in
    # bfloat16; in float32 they hold here too, above)
    assert line["failed"] == 0 and line["rehearsal"] is True
    m = line["metrics"]
    # shares of a roofline or of a peak, a program's name in the device
    # trace and the device's memory are device numbers: none from a
    # rehearsal on the CPU
    device_only = {"attn_roofline.train", "swa_roofline.train",
                   "moe_gmm_roofline.train", "step_mfu_device.train",
                   "step_program_ms.train", "peak_hbm.train"}
    for name in (set(NEW_METRICS) | set(SHARED_METRICS)) - device_only:
        assert name in m, name
    assert not device_only & set(m)
    # a 0 and not nothing off a TPU: the plain forms
    assert m["attn_kernel_sites.train"]["value"] == 0
    assert m["swa_kernel_sites.train"]["value"] == 0
    assert m["moe_gmm_kernel_sites.train"]["value"] == 0
    assert m["moe_overflow_pairs.train"]["value"] == 0
    assert 0 < m["swa_time_share.train"]["value"] < 100
    assert 0 < m["attn_time_share.train"]["value"] < 100
    assert 0 < m["moe_time_share.train"]["value"] < 100
    assert 0 < m["moe_buffer_fill.train"]["value"] <= 100
    assert m["expert_load_max_over_mean.train"]["value"] >= 1
    assert m["remat_saved_gb.train"]["value"] > 0
    assert 0 < m["scoped_time_share.train"]["value"] <= 100


def test_untraced_run_reports_the_end_to_end_metrics(capsys):
    line = _run(ARGV + ["--seed", str(2 ** 31 + 11), "--trace", "0"], capsys)
    assert set(line["metrics"]) == {"train_throughput", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
