"""Subprocess helper for the autotune warm-boot and kill-mid-search
tests (test_tune.py).

One "tuned service lifetime" against a shared MXTPU_TUNE_DIR +
MXTPU_COMPILE_CACHE_DIR: autotune the conv proxy workload (search on
the cold run, record warm-hit on the restart), apply the winner, then
train the proxy model one step at the tuned batch size through the
fused Module path — and print a JSON summary of the tune and compile
counters.

Run 1 is the cold search (trials measured, record + compile-cache
entries written). Run 2 is the restart the record store exists for:
the SAME process boot must perform ZERO search trials (warm record
hit) and ZERO fresh XLA compiles (compile-cache hit on the tuned-batch
step program) — "a tuned process boots tuned".

With MXTPU_FAULT_INJECT="tune_trial:trial=N:action=kill" armed, run 1
instead dies at the N-th trial-commit boundary; the parent then
asserts no record was written, the trial journal holds only complete
CRC-valid lines, and the resumed run reuses them.

Usage: tune_worker.py <out_json_path>
       (store dirs come from MXTPU_TUNE_DIR / MXTPU_COMPILE_CACHE_DIR;
        TUNE_WORKER_MAX_TRIALS bounds the search, default 5)
"""
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


def main():
    out_path = sys.argv[1]
    max_trials = int(os.environ.get("TUNE_WORKER_MAX_TRIALS", "5"))
    mx.random.seed(0)

    wl = mx.tune.workloads.conv_proxy(batch=4, batches=(4, 8))
    rec = mx.tune.autotune(wl, max_trials=max_trials, apply=True)
    params = rec.apply()
    batch = int(params.get("batch", 4))

    # boot the tuned service: one fused train step at the tuned batch
    # under the applied env knobs — through the compile registry, so a
    # restart must AOT-load it (zero fresh compiles)
    mod = mx.mod.Module(wl.symbol, context=mx.cpu())
    mod.bind([("data", (batch, 8, 8, 8))],
             [("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert mod._fused is not None, "worker must run the fused step path"
    rng = np.random.RandomState(0)
    b = mx.io.DataBatch(
        [mx.nd.array(rng.rand(batch, 8, 8, 8).astype(np.float32))],
        [mx.nd.array(rng.randint(0, 8, (batch,)).astype(np.float32))])
    mod.forward(b, is_train=True)
    mod.backward()
    mod.update()

    tr = mx.tune_report()
    cr = mx.compile_report()
    summary = {
        "digest": rec.digest,
        "default_value": rec.default_value,
        "best_value": rec.best_value,
        "best_config": rec.best_config,
        "tuned_batch": batch,
        "trials_run": tr["trials_run"],
        "trials_reused": tr["trials_reused"],
        "warm_hits": tr["warm_hits"],
        "records_written": tr["records_written"],
        "searches": tr["searches"],
        "fresh_compiles": cr["totals"]["fresh_compiles"],
        "cache_hits": cr["totals"]["cache_hits"],
        "cache_errors": cr["totals"]["cache_errors"],
    }
    with open(out_path, "w") as f:
        json.dump(summary, f)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
