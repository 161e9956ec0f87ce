"""Subprocess helper for the compile-cache warm-start test
(test_compile_cache.py).

One full "service lifetime" against a shared MXTPU_COMPILE_CACHE_DIR:
train a small fused-step MLP a few batches, freeze it into a bucketed
serving Predictor, warm every bucket, serve one padded request — then
print a JSON summary of the compile registry plus content hashes of the
trained params and the served prediction.

The parent runs this twice with the same cache directory. Run 1 is the
cold start (every program freshly compiled and serialized); run 2 is
the restart the subsystem exists for: the SAME programs must AOT-load
with ZERO fresh XLA compiles, and the param/prediction hashes must be
bit-identical to run 1 — a cache hit may never change the math.

Usage: compile_cache_worker.py <out_json_path>
       (cache dir comes from the MXTPU_COMPILE_CACHE_DIR env)
"""
import hashlib
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, os.pardir))

import jax  # noqa: E402

# CPU drill: pin the platform BEFORE mxnet_tpu import, in code — a
# worker must stay off a chip its parent may hold, whatever the
# inherited environment says
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def build_sym():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(mx.sym.Flatten(data), num_hidden=32,
                              name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _sha(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def main():
    out_path = sys.argv[1]
    mx.random.seed(0)
    batch = 8
    mod = mx.mod.Module(build_sym(), context=mx.cpu())
    mod.bind([("data", (batch, 16))], [("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    assert mod._fused is not None, "worker must run the fused step path"

    rng = np.random.RandomState(0)
    for _ in range(4):
        b = mx.io.DataBatch(
            [mx.nd.array(rng.rand(batch, 16).astype(np.float32))],
            [mx.nd.array(rng.randint(0, 10, (batch,))
                         .astype(np.float32))])
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()

    arg_params, aux_params = mod.get_params()
    params_sha = _sha(*[arg_params[k].asnumpy()
                        for k in sorted(arg_params)])

    pred = mod.as_predictor(buckets=(1, 4))
    pred.warmup()
    # padded request (3 rows -> bucket 4): must not materialize any new
    # program beyond the warmed buckets
    out = pred.predict(rng.rand(3, 16).astype(np.float32))
    pred_sha = _sha(out)

    report = mx.compile_report()
    summary = {
        "fresh_compiles": report["totals"]["fresh_compiles"],
        "cache_hits": report["totals"]["cache_hits"],
        "cache_errors": report["totals"]["cache_errors"],
        "programs": report["totals"]["programs"],
        "digests": sorted(p["digest"] for p in report["programs"]),
        "predictor_retraces": pred.retraces,
        "params_sha": params_sha,
        "pred_sha": pred_sha,
    }
    with open(out_path, "w") as f:
        json.dump(summary, f)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
