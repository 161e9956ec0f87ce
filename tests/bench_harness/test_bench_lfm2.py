"""The cell ``lfm2-24b-a2b-train-8k``: its plain reference against the
system at ``rehearsal_sizes`` on the CPU (three Adam steps through a
dense conv layer, an attention expert layer and three conv expert
layers), every row of ``correct`` under the committed limits, the fp8
control and three wrong programs (the taps reversed, the gate ``C`` left
out, the head norms after the rotation) each over one, the
configuration's sizes against the published ``config.json``, the cost
functions against a count by hand, ``BENCHMARK.json``'s entries looked
up by name, never by position, and the metrics of a traced rehearsal
run. (The step compiled for a described v5e: ``tests/test_lfm2_v5e.py``.)"""
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

CELL = "lfm2-24b-a2b-train-8k"
CONFIG = "lfm2-24b-a2b"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"

# config.json of LiquidAI/LFM2-24B-A2B as the catalog beside the
# model-configs guide holds it (source_url in the .json)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention",
                                                      "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1,
           "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
           "num_experts": 8, "num_attention_heads": 4,
           "num_key_value_heads": 1, "vocab_size": 8192}
NEW_METRICS = {
    "sconv_time_share.train": ("lower", "%", "device_trace", "Kernels"),
    "sconv_roofline.train": ("higher", "%", "device_trace", "Kernels")}
SHARED_METRICS = (
    "device_idle.train", "step_device_ms.train", "step_program_ms.train",
    "peak_hbm.train", "step_mfu_device.train", "scoped_time_share.train",
    "opt_update_time_share.train", "head_time_share.train",
    "remat_saved_gb.train", "fresh_compiles.setup", "step_acquire_s.setup",
    "moe_time_share.train", "moe_gmm_roofline.train",
    "moe_gmm_kernel_sites.train", "moe_dispatch_time_share.train",
    "expert_load_max_over_mean.train", "moe_buffer_fill.train",
    "moe_overflow_pairs.train", "attn_time_share.train",
    "attn_roofline.train", "attn_kernel_sites.train")


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
    assert len(PUBLISHED["layer_types"]) == 40 \
        and PUBLISHED["layer_types"].count("full_attention") == 10
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
    for key in ("vocab_size", "num_attention_heads", "num_key_value_heads",
                "num_experts"):
        assert cfg[key] * 8 == PUBLISHED[key], key
    # the held layers in the published order: layer 0, then layers 2-5
    assert cfg["layer_types"] == [PUBLISHED["layer_types"][i]
                                  for i in (0, 2, 3, 4, 5)]
    sizes = cfg["sizes"]
    assert sizes["router_experts"] == 64 \
        and sizes["expert_ids"] == list(range(8))
    assert sizes["seq_len"] == 8192 and sizes["batch"] == 2
    assert sizes["head_dim"] * PUBLISHED["num_attention_heads"] \
        == sizes["hidden_size"]
    # one pool for the 8 held experts: whole tiles of 256 rows, no more
    # than 1.5 x the pairs at balance, which are the deployment's
    balanced = sizes["batch"] * sizes["seq_len"] * 4 * 8 / 64
    assert balanced == 8192 and sizes["moe_buffer_rows"] % 256 == 0
    assert balanced < sizes["moe_buffer_rows"] <= 1.5 * balanced
    # the rehearsal changes sizes, never the structure
    small = cfg["rehearsal_sizes"]
    assert set(small) == set(sizes)
    for key in ("num_hidden_layers", "num_dense_layers", "layer_types",
                "conv_L_cache", "conv_bias", "norm_eps", "head_norm_range",
                "norm_topk_prob", "norm_topk_eps", "use_expert_bias",
                "routed_scaling_factor", "conv_range"):
        assert small[key] == sizes[key], key
    # the rotation's base falls with the length, so that as large a share
    # of a head's pairs turns over 44 positions as over 8192 (5 of 8
    # against 21 of 32): else the head norms' place would hardly show
    assert small["rope_parameters"] == {"rope_theta": 400,
                                        "rope_type": "default"}
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] \
        == dep["tensor_parallel_attention_heads"] \
        == dep["vocabulary_parallel"] == 8
    assert "No code stands in for the 7 absent chips" in dep["held"]
    for key in ("published", "deployment", "assumed", "departures",
                "sizes", "rehearsal_sizes", "limits", "precision"):
        assert cfg[key], key
    said = " ".join(cfg["departures"])
    for what in ("untied", "wd 0", "learning_rate 1e-6", "NOT padded"):
        assert what in said, what
    assumed = " ".join(cfg["assumed"])
    for what in ("IN THAT ORDER", "LAST tap on the current token",
                 "rotate_half", "BEFORE the rotation", "+ 1e-6",
                 "no activation"):
        assert what in assumed, what
    for name in ("loss_rel", "first_grad_rel", "change_rel",
                 "first_step_diff"):
        limit = cfg["limits"]["step"][name]
        assert 0 < limit["limit"] < 1 and "my chip runs, PR 47" in limit["why"]


def test_benchmark_json_holds_the_cell_and_its_metrics_by_name():
    bench = harness.benchmark_json(proposed=False)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "step-ring",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "over their share" in cell["why"]
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
    share, roofline = (harness.load_json(os.path.join(
        BENCH, "layer_metrics", name + ".json")) for name in NEW_METRICS)
    assert share == {"reader": "trace_scope_busy",
                     "params": {"scopes": "^mx_sconv_"}}
    assert roofline == {"reader": "trace_scope", "params": {
        "what": "roofline", "scopes": "^mx_sconv_(gate|conv)$",
        "cost": "sconv_cost", "peak_flops": "bf16_flops",
        "peak_bytes_per_s": "hbm_bytes_per_s"}}
    # no metric of another cell's mechanism lists this one, and this
    # cell's are the only ones whose every listed cell is this one
    for name in ("ssd_time_share.train", "loop_time_share.train",
                 "gdn_roofline.train", "mhc_roofline.train",
                 "mla_latent_roofline.train", "conv_time_share.train"):
        assert CELL not in metrics[name]["workloads"], name


def test_costs_are_a_count_by_hand():
    cell = harness.load_cell(CELL)
    model, sz = cell.model, cell.sizes
    shapes = model.param_shapes(sz)
    count = lambda keep: sum(int(np.prod(s)) for k, s in shapes.items()  # noqa
                             if keep(k))
    conv = 3 * 2048 * 2048 + 2048 * 3 + 2048 * 2048
    assert conv == 16_783_360
    for layer in (0, 2, 3, 4):
        assert count(lambda k: k.startswith(f"l{layer}_") and k[3:] in (
            "in_weight", "conv_weight", "out_weight")) == conv
    attention = 524_288 + 262_144 + 524_288 + 128
    assert count(lambda k: k.startswith("l1_") and k[3:] in (
        "qkv_weight", "o_weight", "q_norm_weight", "k_norm_weight")) \
        == attention == 1_310_848
    assert count(lambda k: k.startswith("l0_") and k[3:] in (
        "gate_up_weight", "down_weight")) == 72_351_744
    assert count(lambda k: k.startswith("l3_") and k[3:] in (
        "router_weight", "w1", "w3", "w2")) == 8 * 9_437_184 + 131_072
    assert not [k for k in shapes if "shared" in k]
    trained = count(lambda k: not k.endswith("router_bias"))
    assert trained == 4 * conv + attention + 72_351_744 \
        + 4 * (8 * 9_437_184 + 131_072) + 22_528 + 2 * 16_777_216 \
        == 476_887_168
    assert model.pattern(sz) == "CG*FCFCFCF"
    macs = model.forward_macs(sz)
    assert macs["sconv.projections"] == 4 * (conv - 2048 * 3)
    assert macs["sconv.chain"] == 4 * 2048 * 5
    assert macs["attn.projections"] == attention - 128
    assert macs["attn.scores"] == 4 * 128 * 8193 / 2
    assert macs["dense.mlp"] == 72_351_744
    assert macs["experts.router"] == 4 * 131_072
    assert macs["experts.routed"] == 4 * 12_288 * 9_437_184 / 16_384
    assert macs["head"] == 8192 * 2048
    total = sum(macs.values())
    # ISSUE 47's shares of the forward: conv mixers 37 %, dense MLP 40 %,
    # head 9 %, attention 2 %, experts 10.5 % (15 % with the spare rows)
    assert 185e6 < total < 195e6
    assert 0.34 < macs["sconv.projections"] / total < 0.38
    assert 0.37 < macs["dense.mlp"] / total < 0.41
    assert 0.08 < macs["head"] / total < 0.10
    assert 0.14 < macs["experts.routed"] / total < 0.16
    assert (macs["attn.projections"] + macs["attn.scores"]) / total < 0.025
    assert model.items_per_step(sz) == 16_384
    assert model.flops_per_item(sz, "train") == 6 * total
    peaks = harness.peaks_for("TPU v5 lite")
    operations, moved = model.attn_cost(sz)
    assert operations == 6 * 16_384 * macs["attn.scores"]
    assert moved == 3 * 16_384 * (10 * 64 * 2 + 4 * 4)
    operations, moved = model.moe_gmm_cost(sz)
    assert operations == 6 * 16_384 * macs["experts.routed"]
    assert moved == 4 * 3 * 2 * (8 * 9_437_184 + 12_288 * (4096 + 3072))
    # the chain between the mixer's two products, by hand: forward 12 KB
    # read and 4 KB written a token, backward 16 KB read and 12 KB
    # written, and 28 operations a channel; four layers
    operations, moved = model.sconv_cost(sz)
    assert moved == 4 * (16_384 * (12_288 + 4_096 + 16_384 + 12_288)
                         + 3 * 2048 * 3 * 2)
    assert operations == 4 * 16_384 * 2048 * 28
    # bound by the memory: 3.6 ms a step at 819 GB/s
    assert moved / peaks["hbm_bytes_per_s"] \
        > 100 * operations / peaks["bf16_flops"]
    assert 0.0035 < moved / peaks["hbm_bytes_per_s"] < 0.0037


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    ref = text[text.index("# --- reference: begin"):
               text.index("# --- reference: end")]
    assert "import mxnet_tpu" not in ref and "from mxnet_tpu" not in ref
    for name in ("def short_conv", "def rotate", "def attention",
                 "def gated_mlp", "def router", "def moe_layer",
                 "def reference_loss", "def adam_step", "def balance_step"):
        assert name in ref, name
    # no activation in the mixer; the family has no shared expert
    mixer = ref[ref.index("def short_conv"):ref.index("def rotate")]
    assert "silu" not in mixer and "sigmoid" not in mixer
    assert "shared" not in ref[ref.index("def moe_layer"):
                               ref.index("def op_sublayer")] \
        .split('"""')[2]


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
    assert set(want["first_update"]) \
        == {k for k in shapes if not k.endswith("router_bias")} \
        <= set(got["first_update"])
    # the leaves the program trains are the reference's, size by size
    assert _sound()["leaves"] == {k: int(np.prod(shapes[k]))
                                  for k in want["first_update"]}
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    rows = _rows(cell, got, want)
    for name, (value, limit) in rows.items():
        assert value <= limit, (name, value, limit)
    assert rows["first_step_diff"][0] < 0.02
    # Adam's first update is the rate times the gradient's sign, in every
    # part of every kind of sublayer
    lr = cell.config["optimizer"]["learning_rate"]
    for leaf in ("head_weight", "l0_in_weight", "l2_conv_weight",
                 "l4_out_weight", "l1_qkv_weight", "l1_q_norm_weight",
                 "l1_o_weight", "l0_gate_up_weight", "l1_w1", "l3_w2",
                 "l4_router_weight", "l2_op_norm_weight"):
        moved = np.abs(want["first_update"][leaf])
        assert abs(np.median(moved[moved > 0]) / lr - 1) < 0.2, leaf


def test_the_timed_sizes_leaves_add_up_to_the_count_in_the_file():
    """The parameter count in ``source_note`` is the program's trained
    leaves' (shapes alone: no weight is made)."""
    cell = harness.load_cell(CELL)
    net = cell.model._net(cell.sizes)
    trained = sum(int(np.prod(p.shape))
                  for p in net.collect_params().values()
                  if p.grad_req != "null")
    assert trained == 476_887_168
    assert "476,887,168 trained parameters" in cell.config["source_note"]


def test_scope_table_is_the_program_s_and_names_the_new_parts():
    sound = _sound()
    assert sound["own"]
    for want in ("mx_sconv_proj", "mx_sconv_gate", "mx_sconv_conv",
                 "mx_attn_proj", "mx_attn_qk_norm", "mx_rope", "mx_attn_fwd",
                 "mx_gated_mlp", "mx_moe_score", "mx_moe_route",
                 "mx_moe_dispatch", "mx_moe_gmm_up", "mx_moe_gmm_down",
                 "mx_moe_combine", "mx_head/mx_dense", "mx_norm",
                 "mx_opt_update", "mx_embed", "mx_loss"):
        assert want in sound["scopes"], (want, sorted(sound["scopes"]))
    # the new scopes enclose nothing and stand inside nothing
    for path in sound["scopes"]:
        if "mx_sconv_" in path:
            assert "/" not in path, path
    assert not any(s.startswith(("mx_ssd", "mx_gdn", "mx_mla", "mx_mhc"))
                   or "mx_moe_shared" in s for s in sound["scopes"])
    gauges = sound["gauges"]
    assert len([k for k in gauges if k.startswith("moe::pairs_held::")]) == 4
    assert all(v == 0 for k, v in gauges.items()
               if k.startswith("moe::overflow_pairs::"))
    assert gauges["attn::kernel_sites"] == 0


def _over(cell, session, want):
    """The rows of ``correct`` that a program's first steps miss."""
    rows = _rows(cell, session["first"], want)
    cell.model.release_system()
    cell.driver.close(session)
    return {name: value for name, (value, limit) in rows.items()
            if not value <= limit}, rows


def _wrong_taps(monkeypatch):
    from mxnet_tpu.ops import seq
    conv = seq.causal_conv1d
    monkeypatch.setattr(seq, "causal_conv1d",
                        lambda x, w, bias: conv(x, w[:, ::-1], bias))


def _wrong_gate(monkeypatch):
    """``W_out c`` in place of ``W_out (C * c)``."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import get_op, seq

    def mixer(data, in_weight, conv_weight, out_weight, **kw):
        bcz = seq._mm(data, in_weight).astype(jnp.float32)
        width = bcz.shape[-1] // 3
        conv = seq.causal_conv1d(bcz[..., :width] * bcz[..., 2 * width:],
                                 conv_weight, None)
        return seq._mm(conv.astype(data.dtype), out_weight)

    monkeypatch.setattr(get_op("GatedShortConv"), "fn", mixer)


def _wrong_norms(monkeypatch):
    """The head norms after the rotation: the norm of a head's width
    hands on what it was given and the rotation applies it last."""
    from mxnet_tpu.ops import seq
    norm, rope, waiting = seq._rms_norm, seq.rope, []

    def late_norm(x, gamma, *args, **kw):
        if x.ndim != 4:               # a layer's norm, not a head's
            return norm(x, gamma, *args, **kw)
        waiting.append((gamma, args, kw))
        return x

    def rope_then_norm(data, *args, **kw):
        gamma, more, named = waiting.pop(0)
        return norm(rope(data, *args, **kw), gamma, *more, **named)

    monkeypatch.setattr(seq, "_rms_norm", late_norm)
    monkeypatch.setattr(seq, "rope", rope_then_norm)


@pytest.mark.parametrize("wrong", [_wrong_taps, _wrong_gate, _wrong_norms])
def test_a_wrong_program_is_not_correct(wrong, monkeypatch):
    """Three programs that compute another model, each from the same
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
    # (at the timed sizes first_step_diff is the number it fails on every
    # seed; at these it is the loss and the two worst-leaf numbers)
    assert [name for name, (value, limit) in rows.items() if value > limit]
    sound = _rows(cell, _sound()["got"], want)["first_step_diff"][0]
    assert rows["first_step_diff"][0] > 20 * sound


def test_weights_are_as_assumed():
    cell = harness.load_cell(CELL, rehearsal=True)
    sz = cell.sizes
    w = cell.model.make_weights(sz, 2 ** 31 + 5)
    for leaf in ("l1_ffn_norm_weight", "l0_op_norm_weight",
                 "final_norm_weight"):
        assert (np.asarray(w[leaf]) == 1).all(), leaf
    # the head norms' weights differ by element: with all ones the norm
    # and the rotation would commute
    for leaf in ("l1_q_norm_weight", "l1_k_norm_weight"):
        scale = np.asarray(w[leaf])
        assert 0.5 <= scale.min() < 0.8 and 1.2 < scale.max() <= 1.5, leaf
    taps = np.asarray(w["l2_conv_weight"])
    assert taps.shape == (sz["hidden_size"], 3)
    assert np.abs(taps).max() <= sz["conv_range"] < 3 ** -0.5 + 1e-4
    assert 0.9 < taps.std() * 3 ** 0.5 / sz["conv_range"] < 1.1
    std = float(np.std(np.asarray(w["embed_weight"])))
    assert abs(std / sz["initializer_range"] - 1) < 0.05
    for leaf in ("l1_w2", "l0_out_weight", "l1_o_weight", "l0_down_weight"):
        out = float(np.std(np.asarray(w[leaf])))
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
    line = _run(ARGV + ["--seed", "4700000019", "--trace", "1"], capsys)
    # (`correct` holds the chip's limits, set at the cell's own sizes in
    # bfloat16; in float32 they hold here too, above)
    assert line["failed"] == 0 and line["rehearsal"] is True
    m = line["metrics"]
    # shares of a roofline or of a peak, a program's name in the device
    # trace and the device's memory are device numbers: none from a
    # rehearsal on the CPU
    device_only = {"attn_roofline.train", "sconv_roofline.train",
                   "moe_gmm_roofline.train", "step_mfu_device.train",
                   "step_program_ms.train", "peak_hbm.train"}
    for name in (set(NEW_METRICS) | set(SHARED_METRICS)) - device_only:
        assert name in m, name
    assert not device_only & set(m)
    # a 0 and not nothing: the baseline the attention kernels' extension
    # to 64-wide heads moves
    assert m["attn_kernel_sites.train"]["value"] == 0
    assert m["moe_gmm_kernel_sites.train"]["value"] == 0     # off a TPU
    assert m["moe_overflow_pairs.train"]["value"] == 0
    assert 0 < m["sconv_time_share.train"]["value"] < 100
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
