"""Serving latency/throughput SLOs on the CPU proxy (timing-sensitive,
hence ``slow`` — tier-1 keeps the functional serving suite instead).

The acceptance bar for the dynamic batcher: with enough concurrent
clients to keep full buckets in flight, end-to-end throughput THROUGH
the queue/coalesce/pad/split machinery must reach >= 80% of the raw
compiled predict-step rate at the largest bucket — i.e. the batching
layer costs at most 20%.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.serving import loadgen

pytestmark = [pytest.mark.serving, pytest.mark.slow]

FEAT = (16, 16, 16)
TOP = 32


def _predictor():
    mx.random.seed(0)
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="bn", fix_gamma=False)
    act = mx.sym.Activation(bn, act_type="relu", name="relu")
    conv = mx.sym.Convolution(act, kernel=(3, 3), pad=(1, 1),
                              num_filter=32, no_bias=True, name="conv")
    fc = mx.sym.FullyConnected(mx.sym.Flatten(conv), num_hidden=64,
                               name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    mod = mx.mod.Module(context=mx.cpu(), symbol=net)
    mod.bind(data_shapes=[("data", (8,) + FEAT)],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    return mod.as_predictor(buckets=(1, 8, TOP))


def test_batcher_throughput_at_least_80pct_of_raw():
    pred = _predictor()
    pred.warmup()
    rng = np.random.RandomState(0)
    x_full = rng.rand(TOP, *FEAT).astype(np.float32)

    # raw compiled predict-step rate at the largest bucket
    raw_rps = loadgen.raw_predict_rate(pred, x_full, steps=20, warm=3)

    # closed-loop concurrent clients submitting bucket-row requests
    # through the batcher; enough clients to keep full buckets queued
    clients, per_client, req_rows = 16, 12, 8
    with serving.DynamicBatcher(pred, max_wait_us=2000,
                                max_queue=100_000, name="slo") as b:
        x_req = rng.rand(req_rows, *FEAT).astype(np.float32)
        b.predict(x_req)                      # prime the loop
        r = loadgen.closed_loop(b, x_req, clients, per_client,
                                timeout=120)
    batched_rps = r["rows_s"]
    efficiency = batched_rps / raw_rps
    assert efficiency >= 0.8, (
        f"dynamic batcher reached only {batched_rps:.0f} rows/s vs raw "
        f"{raw_rps:.0f} rows/s ({efficiency:.0%}; bar is 80%)")
