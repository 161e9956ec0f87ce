"""Inference throughput benchmark — the analog of the reference's
example/image-classification/benchmark_score.py (which produced the
scoring tables of incubator-mxnet/docs/faq/perf.md: ResNet-50 713 img/s on 1x P100 @ batch 32).

Scores the jitted symbolic forward on one TPU chip in bf16; batches are
device-resident and dispatch is async with one trailing sync, matching the
training bench's methodology.

Usage: python benchmark_score.py [--networks resnet-50,inception-v3,...]
                                 [--batch-sizes 1,32,128] [--dtype bfloat16]
Prints one JSON line per (network, batch).
"""
import argparse
import json
import time

import numpy as np

import mxnet_tpu as mx

from symbols import alexnet as _alexnet
from symbols import googlenet as _googlenet
from symbols import inception_bn as _incbn
from symbols import inception_v3 as _inc3
from symbols import mobilenet as _mobilenet
from symbols import resnet as _resnet
from symbols import resnext as _resnext
from symbols import vgg as _vgg


def get_network(name):
    """Returns (symbol, image_shape)."""
    if name == "alexnet":
        return _alexnet.get_symbol(1000), (3, 224, 224)
    if name == "googlenet":
        return _googlenet.get_symbol(1000), (3, 224, 224)
    if name == "inception-bn":
        return _incbn.get_symbol(1000), (3, 224, 224)
    if name == "mobilenet":
        return _mobilenet.get_symbol(1000), (3, 224, 224)
    if name.startswith("vgg-"):
        parts = name.split("-")
        if len(parts) == 2 and parts[1].isdigit():
            return _vgg.get_symbol(1000, int(parts[1])), (3, 224, 224)
        if len(parts) == 3 and parts[1].isdigit() and parts[2] == "bn":
            return _vgg.get_symbol(1000, int(parts[1]),
                                   batch_norm=True), (3, 224, 224)
        raise ValueError(f"unknown network {name}")
    if name == "inception-v3":
        return _inc3.get_symbol(1000), (3, 299, 299)
    if name.startswith("resnext-"):
        return _resnext.get_symbol(
            1000, int(name.split("-")[1])), (3, 224, 224)
    if name.startswith("resnet-"):
        num_layers = int(name.split("-")[1])
        return _resnet.get_symbol(1000, num_layers, "3,224,224"), \
            (3, 224, 224)
    raise ValueError(f"unknown network {name}")


def score(network, batch, dtype="bfloat16", steps=30):
    sym, image_shape = get_network(network)
    # score mode: strip the training head's label dependency
    mod = mx.mod.Module(symbol=sym, context=mx.current_context(),
                        label_names=("softmax_label",))
    data_shape = (batch,) + image_shape
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", (batch,))],
             for_training=False)
    mod.init_params(mx.init.Xavier())

    rng = np.random.RandomState(0)
    batches = [
        mx.io.DataBatch([mx.nd.array(
            rng.rand(*data_shape).astype(np.float32).astype(dtype))], [])
        for _ in range(4)
    ]
    # warmup/compile
    for b in batches[:2]:
        mod.forward(b, is_train=False)
    mod.get_outputs()[0].asnumpy()

    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        acc = None
        for i in range(steps):
            mod.forward(batches[i % 4], is_train=False)
            # chain every output into one scalar: the final wait then
            # provably covers ALL forwards, with a single 4-byte fetch
            # instead of a blocking fetch per step
            s = mod.get_outputs()[0].sum()
            acc = s if acc is None else acc + s
        acc.wait_to_read()
        best = min(best, time.perf_counter() - t0)
    img_s = batch * steps / best
    print(json.dumps({"network": network, "batch": batch,
                      "dtype": dtype, "img_s": round(img_s, 1),
                      "ms_per_batch": round(1000 * best / steps, 3)}),
          flush=True)
    return img_s


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--networks", type=str,
                    default="alexnet,resnet-50,resnet-152,inception-v3")
    ap.add_argument("--batch-sizes", type=str, default="32,128")
    ap.add_argument("--dtype", type=str, default="bfloat16")
    args = ap.parse_args()
    for net in args.networks.split(","):
        for b in args.batch_sizes.split(","):
            score(net, int(b), args.dtype)
