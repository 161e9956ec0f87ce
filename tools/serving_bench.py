"""Serving frontier sweep: bucket sets × coalescing windows.

Usage: python tools/serving_bench.py "1,8,64:2000" "1,16,128:500" ...
Each spec is ``buckets:max_wait_us[:clients]`` — a comma-separated
bucket set, the DynamicBatcher coalescing window in µs, and optionally
the concurrent-client count (default 64). For each spec the sweep
drives single-image closed-loop clients through the batcher over a
frozen ResNet-50 Predictor and prints one frontier row: p50/p99
request latency, img/s, batch occupancy at the hot bucket, and the
efficiency vs the RAW compiled predict step at the largest bucket —
the table that picks the bucket set / wait window trade-off for a
latency SLO (mirrors tools/perf_sweep.py conventions; serving
internals: mxnet_tpu/serving/).

Since round 15 the sweep drives the autotuner's trial runner
(``mx.tune.TrialRunner`` over a spec knob, measurement =
``tune.workloads.measure_serving`` — the ONE closed-loop measurement
implementation, shared with ``mx.tune.autotune`` of a serving
workload), so this table and a tuner search can never disagree about
what a configuration measures.

``--decode`` switches the sweep to the autoregressive-decode frontier
(round 16): specs become ``slots,max_seq:max_wait_us[:clients]`` and
each row drives streaming clients through a DecodeBatcher over a pocket
transformer LM (``tune.workloads.measure_decode_serving`` — again the
ONE token-granularity measurement, shared with ``mx.tune.autotune`` of
a decode workload), printing tok/s, TTFT p50/p99 and inter-token
p50/p99 — the table that sizes KV-cache lanes and the first-fill window
for a token-latency SLO.

Off-TPU this runs the same code path compiled for CPU — slower, same
frontier shape. MXTPU_SERVING_* env vars set the defaults the sweep
overrides per spec (MXTPU_DECODE_* for --decode).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", "examples",
    "image_classification"))


def build_predictor(buckets, batch=64, small=False):
    import mxnet_tpu as mx
    if small:
        # CPU-proxy model (the --small flag): same serving machinery,
        # a step cheap enough to sweep interactively
        data = mx.sym.Variable("data")
        bn = mx.sym.BatchNorm(data, name="bn", fix_gamma=False)
        act = mx.sym.Activation(bn, act_type="relu", name="relu")
        conv = mx.sym.Convolution(act, kernel=(3, 3), pad=(1, 1),
                                  num_filter=32, no_bias=True,
                                  name="conv")
        fc = mx.sym.FullyConnected(mx.sym.Flatten(conv), num_hidden=64,
                                   name="fc")
        net = mx.sym.SoftmaxOutput(fc, name="softmax")
        feat = (16, 16, 16)
    else:
        from symbols import resnet as resnet_sym
        net = resnet_sym.get_symbol(1000, 50, "3,224,224", stem="s2d")
        feat = (3, 224, 224)
    mx.random.seed(0)
    mod = mx.mod.Module(context=mx.current_context(), symbol=net)
    mod.bind(data_shapes=[("data", (batch,) + feat)],
             label_shapes=[("softmax_label", (batch,))],
             for_training=False)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                   factor_type="in", magnitude=2))
    return mod.as_predictor(
        buckets=buckets,
        compute_dtype=None if small else "bfloat16"), feat


def parse_spec(spec):
    """``buckets:max_wait_us[:clients]`` -> (buckets, wait_us, clients)."""
    parts = spec.split(":")
    if len(parts) < 2:
        sys.exit(f"bad spec '{spec}': want buckets:max_wait_us"
                 "[:clients]")
    buckets = tuple(int(x) for x in parts[0].split(","))
    wait_us = int(parts[1])
    clients = int(parts[2]) if len(parts) > 2 else 64
    return buckets, wait_us, clients


def sweep(specs, small=False, per_client=8, on_trial=None):
    """Measure every spec through the tuner's trial runner; returns the
    completed trials in spec order (trial.metrics carries the frontier
    row, trial.objective is p99 ms)."""
    from mxnet_tpu import tune
    from mxnet_tpu.tune.workloads import measure_serving

    def measure(cfg, budget):
        buckets, wait_us, clients = parse_spec(cfg["spec"])
        pred, feat = build_predictor(buckets, batch=max(buckets),
                                     small=small)
        return measure_serving(pred, feat, wait_us, clients,
                               per_client=per_client)

    space = tune.SearchSpace(
        [tune.Knob("spec", tuple(specs), kind="param",
                   doc="buckets:max_wait_us[:clients]")],
        name="serving_bench")
    runner = tune.TrialRunner(space, measure, seed=0, max_trials=0,
                              base_budget=1, full_budget=1,
                              on_trial=on_trial, name="serving_bench")
    runner.search()
    by_spec = {t.config["spec"]: t for t in runner.trials}
    return [by_spec[s] for s in specs]


def build_decode_engine(slots, max_seq):
    from mxnet_tpu.serving.decode import TransformerLMSpec, \
        DecodePredictor, init_params
    spec = TransformerLMSpec(vocab_size=256, num_embed=64, num_heads=4,
                             num_layers=2, max_seq=max_seq,
                             name="benchlm")
    return DecodePredictor(spec, init_params(spec, seed=0),
                           slots=slots), spec


def parse_decode_spec(spec):
    """``slots,max_seq:max_wait_us[:clients]``."""
    parts = spec.split(":")
    if len(parts) < 2 or "," not in parts[0]:
        sys.exit(f"bad decode spec '{spec}': want "
                 "slots,max_seq:max_wait_us[:clients]")
    slots, max_seq = (int(x) for x in parts[0].split(","))
    wait_us = int(parts[1])
    clients = int(parts[2]) if len(parts) > 2 else 8
    return slots, max_seq, wait_us, clients


def decode_sweep(specs, per_client=4, max_new_tokens=16, on_trial=None):
    """The --decode frontier: every spec through the trial runner with
    the token-granularity closed-loop measurement."""
    import numpy as np
    from mxnet_tpu import tune
    from mxnet_tpu.tune.workloads import measure_decode_serving

    def measure(cfg, budget):
        slots, max_seq, wait_us, clients = \
            parse_decode_spec(cfg["spec"])
        eng, lmspec = build_decode_engine(slots, max_seq)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, lmspec.vocab_size,
                               size=4 + (i * 5) % (max_seq // 2)
                               ).astype(np.int32) for i in range(8)]
        return measure_decode_serving(
            eng, prompts, wait_us, clients, per_client=per_client,
            max_new_tokens=max_new_tokens)

    space = tune.SearchSpace(
        [tune.Knob("spec", tuple(specs), kind="param",
                   doc="slots,max_seq:max_wait_us[:clients]")],
        name="decode_bench")
    runner = tune.TrialRunner(space, measure, seed=0, max_trials=0,
                              base_budget=1, full_budget=1,
                              on_trial=on_trial, name="decode_bench")
    runner.search()
    by_spec = {t.config["spec"]: t for t in runner.trials}
    return [by_spec[s] for s in specs]


def main():
    args = [a for a in sys.argv[1:]
            if a not in ("--small", "--decode")]
    small = "--small" in sys.argv[1:]
    decode = "--decode" in sys.argv[1:]
    if decode:
        specs = args or ["4,64:2000", "4,64:0", "8,64:2000"]
        print(f"{'spec':>22}  {'tok/s':>9}  {'ttft p50':>9}"
              f"  {'ttft p99':>9}  {'itl p50':>8}  {'itl p99':>8}"
              f"  {'gens':>5}  retraces")

        def show_decode(t):
            if t.status == "failed":
                print(f"{t.config['spec']:>22}  FAILED: {t.reason}",
                      flush=True)
                return
            m = t.metrics
            print(f"{t.config['spec']:>22}  {m['tok_s']:9.1f}"
                  f"  {m['ttft_p50_ms']:9.2f}  {m['ttft_p99_ms']:9.2f}"
                  f"  {m['inter_token_p50_ms']:8.2f}"
                  f"  {m['inter_token_p99_ms']:8.2f}"
                  f"  {m['served_generations']:5d}"
                  f"  {m['retraces']:8d}", flush=True)

        decode_sweep(specs, on_trial=show_decode)
        return
    specs = args or ["1,8,64:2000", "1,8,64:500", "1,16,128:2000"]
    print(f"{'spec':>22}  {'img/s':>9}  {'p50 ms':>8}  {'p99 ms':>8}"
          f"  {'eff':>6}  {'bucket':>6}  {'occ':>5}  retraces")

    def show(t):
        if t.status == "failed":
            print(f"{t.config['spec']:>22}  FAILED: {t.reason}",
                  flush=True)
            return
        m = t.metrics
        print(f"{t.config['spec']:>22}  {m['rows_s']:9.1f}"
              f"  {m['p50_ms']:8.2f}"
              f"  {m['p99_ms']:8.2f}  {m['efficiency']:6.3f}"
              f"  {m['hot_bucket']:>6}  {m['occupancy'] or 0:5.2f}"
              f"  {m['retraces']:8d}", flush=True)

    sweep(specs, small=small, on_trial=show)


if __name__ == "__main__":
    main()
