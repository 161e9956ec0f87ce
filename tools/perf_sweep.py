"""Quick A/B throughput sweep of the fused Module step on the real chip.

Usage: python tools/perf_sweep.py "std:128" "s2d:128" "s2d:128:nofused" ...
Each spec is stem:batch[:fused|nofused] — the optional third field
forces the Pallas BN(+ReLU)->1x1-conv fusion pass on/off
(MXTPU_PALLAS_FUSION; default auto = on for TPU), so
``s2d:128 s2d:128:nofused`` is the fused-vs-unfused A/B. Prints img/s,
implied model-FLOPs MFU, the pass's rewritten-site count, and XLA cost
analysis' "bytes accessed" for the compiled step (the HBM-traffic
number the fusion exists to cut).
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

MODEL_FLOPS_PER_IMG = 3 * 4.089e9
PEAK = 197e12  # v5e bf16


def measure(stem, batch, steps=30):
    import jax
    import mxnet_tpu as mx
    from hlo_breakdown import build_model
    model = build_model(batch, stem=stem)
    rng = np.random.RandomState(0)
    n_host = 4
    batches = [mx.io.DataBatch(
        [mx.nd.array(rng.rand(batch, 3, 224, 224).astype(np.float32))],
        [mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.int32))])
        for _ in range(n_host)]

    def run(b):
        model.forward(b, is_train=True)
        model.backward()
        model.update()

    for b in batches:
        run(b)
    jax.block_until_ready(model._fused._pvals)
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(steps):
            run(batches[i % n_host])
        jax.block_until_ready(model._fused._pvals)
        dt = min(dt, time.perf_counter() - t0)
    step = dt / steps
    img_s = batch / step
    mfu = MODEL_FLOPS_PER_IMG * batch / step / PEAK
    rep = model._fused.fusion_report
    sites = len(rep["sites"]) if rep else 0
    gbytes = None
    try:
        fused = model._fused
        b0 = batches[0]
        feed = {fused.data_names[0]: b0.data[0].data,
                fused.label_names[0]: b0.label[0].data}
        by = float(fused.step_cost(feed).get("bytes accessed", 0.0))
        gbytes = by / 1e9 if by > 0 else None
    except Exception:
        pass
    return img_s, step, mfu, sites, gbytes


def main():
    from mxnet_tpu import config
    specs = sys.argv[1:] or ["std:128", "s2d:128"]
    for spec in specs:
        parts = spec.split(":")
        stem, batch = parts[0], int(parts[1])
        flag = os.environ.get("MXTPU_PALLAS_FUSION")  # keep as-is
        if len(parts) > 2:
            if parts[2] not in ("fused", "nofused"):
                sys.exit(f"bad spec '{spec}': third field must be "
                         "'fused' or 'nofused'")
            flag = "1" if parts[2] == "fused" else "0"
        with config.override("MXTPU_PALLAS_FUSION", flag):
            img_s, step, mfu, sites, gbytes = measure(stem, batch)
        gb = f"{gbytes:6.2f} GB/step" if gbytes else "   n/a"
        print(f"{spec:>18}: {img_s:8.1f} img/s  step={step*1e3:6.2f} ms"
              f"  mfu={mfu:.4f}  fused_sites={sites:3d}  bytes={gb}",
              flush=True)


if __name__ == "__main__":
    main()
