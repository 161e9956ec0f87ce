"""chip_smoke.py — does the system still start on the chip?

One process, no children, the entry points a user calls, at the full
width of ResNet-50: a few fused training steps (``Module`` +
``Module.fit``) with the pass flags at their defaults, the same steps
again with the Pallas rewrites forced on (on the chip the repo's own
bytes gate rejects them, so this is where the Mosaic kernels run inside
the real step), the trained model served through ``Predictor`` behind
``DynamicBatcher``, and the toy decode LM through ``DecodePredictor`` +
``DecodeBatcher``. With more than one chip visible, the forced training
phase runs again over all of them. Weights are random, from a seed.

It fails — non-zero exit, no result line — unless JAX's default backend
is ``tpu``, and on any failed assertion or exception in any phase; no
phase is wrapped in a handler. The last two lines of standard output
are one JSON object each: first ``{"phases": {...}, "mesh": ...}`` with
per-phase ``ok``, wall and set-up (trace + compile) seconds, then the
result line the driver parses, which has exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Seconds are reported, never compared: nothing here is warmed or repeated
enough to be a rate.

    python3 chip_smoke.py          # on a machine with a TPU

The phase functions take their size as an argument so that
tests/test_chip_smoke.py can run them on the CPU at a toy size.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

# the ROADMAP's ResNet-50 training cell: batch 128 per chip at 224x224,
# bf16 compute, space-to-depth stem, SGD + momentum + weight decay
FULL = {
    "layers": 50, "image": 224, "classes": 1000, "batch": 128,
    "stem": "s2d", "manual_steps": 6, "fit_batches": 3,
    "buckets": (1, 8, 32), "clients": 4, "requests_per_client": 8,
    # the toy decode LM of serving/decode/model.py
    "lm": {"vocab_size": 256, "num_embed": 128, "num_heads": 8,
           "num_layers": 4, "max_seq": 64},
    "slots": 8, "seq_buckets": (16, 32), "streams": 6, "new_tokens": 16,
    "min_pallas_sites": 1,
}

# lr is a runtime argument of the step, not part of the program: the
# program is the ROADMAP cell's, the value is small enough that a batch
# repeated a few times descends without bouncing (0.1 bounces)
_OPT = {"learning_rate": 0.005, "momentum": 0.9, "wd": 1e-4}


# ---------------------------------------------------------------------------
# preamble
# ---------------------------------------------------------------------------
def preamble():
    """Refuse anything but a TPU backend whose ``device_kind`` the HBM
    peak table knows; print what the run is standing on. Returns the device
    description of the result line."""
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's default backend is "
            f"'{jax.default_backend()}', not 'tpu' — this script proves "
            "the chip path and has nothing to say without one")
    import jaxlib
    import mxnet_tpu as mx
    from mxnet_tpu.telemetry import peak_hbm_bytes_s
    devs = jax.devices()
    dev = devs[0]
    if not peak_hbm_bytes_s(dev):
        raise SystemExit(
            f"chip_smoke: device_kind {dev.device_kind!r} is not in the "
            "HBM peak table (telemetry/timeline.py)")
    from importlib import metadata
    try:
        libtpu_v = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu_v = "not installed as a package"
    desc = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    print(f"chip_smoke: device {desc}")
    print(f"chip_smoke: jax {jax.__version__} jaxlib "
          f"{jaxlib.__version__} libtpu {libtpu_v}")
    print(f"chip_smoke: jax compile cache at "
          f"{jax.config.jax_compilation_cache_dir} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    assert mx.current_context().device_type == "tpu"
    return desc


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _resnet(size):
    sys.path.insert(0, os.path.join(_HERE, "examples",
                                    "image_classification"))
    from symbols import resnet as resnet_sym
    shape = f"3,{size['image']},{size['image']}"
    return resnet_sym.get_symbol(size["classes"], size["layers"], shape,
                                 stem=size.get("stem", "std"))


def _fused_buffers(mod):
    """Every device buffer the fused step owns: parameters, optimizer
    state, BatchNorm aux."""
    import jax
    f = mod._fused
    return [x for x in jax.tree_util.tree_leaves(
        (f._pvals, f._opt_state, f._flat_p, f._flat_state, f._aux_vals,
         f._flat_aux)) if hasattr(x, "devices")]


def _cross_entropy(probs, labels):
    p = probs[np.arange(len(labels)), labels.astype(int)]
    return float(-np.log(np.maximum(p, 1e-30)).mean())


def _pipeline_of(tag):
    """The newest pass-pipeline record for ``tag``; None when every pass
    was disabled (such a pipeline records nothing — the default off the
    chip, where ``auto`` means off)."""
    import mxnet_tpu as mx
    recs = [r for r in mx.pass_report()["pipelines"] if r["tag"] == tag]
    return recs[-1] if recs else None


def _check_passes(record, label):
    """No pass errored or went unmeasured; every bail-out names its site
    and its reason. Prints and returns sites applied / bailed per pass."""
    from mxnet_tpu.telemetry import registry as treg
    assert treg.counter("passes::unmeasured").get() == 0, \
        "a pass was applied without a bytes measurement"
    out = {}
    if record is None:
        print(f"chip_smoke: [{label}] every pass disabled")
        return out
    for e in record["passes"]:
        assert e["status"] != "error", e
        applied = len(e["sites"]) if e["status"] == "applied" else 0
        by_reason = {}
        for b in e["bailouts"]:
            assert b.get("reason"), f"bail-out without a reason: {b}"
            by_reason.setdefault(b["reason"], []).append(b.get("conv"))
        out[e["pass"]] = {"status": e["status"], "reason": e.get("reason"),
                          "applied": applied, "bailed": len(e["bailouts"])}
        print(f"chip_smoke: [{label}] pass {e['pass']}: {e['status']}"
              f"{' (' + str(e['reason']) + ')' if e.get('reason') else ''}"
              f", {applied} site(s) applied, {len(e['bailouts'])} bailed")
        for reason, names in by_reason.items():
            print(f"chip_smoke: [{label}]   {len(names)} bailed — {reason}:"
                  f" {', '.join(map(str, names[:8]))}"
                  f"{' ...' if len(names) > 8 else ''}")
    return out


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def _fresh_compiles():
    import mxnet_tpu as mx
    return mx.compile_report()["totals"]["fresh_compiles"]


def train_phase(contexts, size, label="train", forced=False, fit=True,
                reference=None):
    """Fused ResNet training on ``contexts`` (one context, or a list —
    then the batch is ``size['batch']`` per context over a data mesh):
    manual forward/backward/update steps on a repeated batch, then —
    with ``fit`` — ``update_metric`` in those steps and ``Module.fit``
    on a synthetic iterator.

    ``forced`` sets the two fusion-pass flags to ``1``: the rewrites
    apply without the bytes gate's verdict, which is how a user asks
    for them, and at least ``size['min_pallas_sites']`` Pallas sites
    must then be in the step. ``reference`` is the loss trajectory of
    another run on the same seed, batch and steps: this one must start
    where it starts and stay near it."""
    import contextlib
    import jax
    import mxnet_tpu as mx
    t_phase = time.perf_counter()
    ctxs = contexts if isinstance(contexts, (list, tuple)) else [contexts]
    platform = ctxs[0].jax_device.platform
    batch = size["batch"] * len(ctxs)
    img = (3, size["image"], size["image"])
    rng = np.random.RandomState(0)
    x = rng.rand(batch, *img).astype(np.float32)
    y = rng.randint(0, size["classes"], (batch,)).astype(np.float32)
    b = mx.io.DataBatch([mx.nd.array(x)], [mx.nd.array(y)])
    metric = mx.metric.Accuracy()
    mx.random.seed(0)
    mx.pass_report(reset=True)
    losses, setup_s = [], 0.0
    with contextlib.ExitStack() as flags:
        if forced:  # read when the optimizer binds the fused step
            for f in ("MXTPU_PALLAS_FUSION", "MXTPU_PASS_RESIDUAL_FUSION"):
                flags.enter_context(mx.config.override(f, "1"))
        t0 = time.perf_counter()
        mod = mx.mod.Module(context=contexts, symbol=_resnet(size),
                            fused=True, compute_dtype="bfloat16")
        mod.bind(data_shapes=[("data", (batch,) + img)],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
        # binds the fused step: the pass pipeline runs here, and its
        # bytes gate compiles the program before and after each pass
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params=dict(_OPT))
        setup_s += time.perf_counter() - t0
        for _ in range(size["manual_steps"]):
            t0, c0 = time.perf_counter(), _fresh_compiles()
            mod.forward(b, is_train=True)
            mod.backward()
            mod.update()
            if fit:
                mod.update_metric(metric, b.label)
            probs = mod.get_outputs()[0].asnumpy()  # waits for the step
            if _fresh_compiles() > c0:   # this step traced and compiled
                setup_s += time.perf_counter() - t0
            assert probs.shape == (batch, size["classes"]), probs.shape
            assert np.isfinite(probs).all(), "non-finite outputs"
            losses.append(_cross_entropy(probs, y))
    print(f"chip_smoke: [{label}] loss on a repeated batch: "
          + " ".join(f"{v:.4f}" for v in losses))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    if reference is not None:
        # step 1 is one forward pass on identical weights: only rounding
        # separates the two. Later steps train on, and bf16 training
        # spreads (3.7e-2 by step 4 at batch 16 on the CPU) — the bound
        # there catches a wrong kernel, not a different rounding
        dist = [abs(a - r) / r for a, r in zip(losses, reference)]
        print(f"chip_smoke: [{label}] relative distance from the "
              f"reference trajectory: "
              + " ".join(f"{d:.1e}" for d in dist))
        assert dist[0] < 2e-2 and max(dist) < 1e-1, (losses, reference)

    fused = mod._fused
    feed = {fused.data_names[0]: b.data[0].data,
            fused.label_names[0]: b.label[0].data}

    # -- where the state lives ------------------------------------------------
    bufs = _fused_buffers(mod)
    assert bufs
    for a in bufs:
        assert {d.platform for d in a.devices()} == {platform}, \
            (a.shape, a.devices())
    used = set().union(*(a.devices() for a in bufs))
    assert len(used) == len(ctxs), (used, ctxs)
    if len(ctxs) > 1:
        want = {c.jax_device for c in ctxs}
        staged = jax.device_put(feed[fused.data_names[0]],
                                fused.staging_sharding())
        assert staged.sharding.device_set == want, staged.sharding
        om = fused.optimizer_memory()
        assert om["zero"] and om["ndev"] == len(ctxs), om
        sharded = [s for st in fused._opt_state for s in st
                   if len(s.sharding.device_set) == len(ctxs)
                   and not s.sharding.is_fully_replicated]
        assert sharded, "no optimizer-state leaf is sharded over the mesh"
        assert om["per_device_bytes"] < om["logical_bytes"], om
        in_use = []
        for d in sorted(want, key=lambda d: d.id):
            stats = d.memory_stats() or {}
            in_use.append(int(stats.get("bytes_in_use", 0)))
        print(f"chip_smoke: [{label}] bytes_in_use per device: {in_use}; "
              f"optimizer state {om['per_device_bytes']} of "
              f"{om['logical_bytes']} bytes per device")
        if platform == "tpu":     # the CPU client keeps no such count
            # the same order, not the same: the first context also holds
            # the executor's arrays and the host-fed batch
            assert min(in_use) > 0, in_use
            assert max(in_use) < 10 * min(in_use), in_use

    # -- what the passes did, and what the compiler was given ----------------
    passes = _check_passes(_pipeline_of("fused_step"), label)
    sites = passes.get("pallas_fusion", {}).get("applied", 0)
    if forced:
        assert sites >= size["min_pallas_sites"], passes
    exe = fused.compiled_program(feed)
    assert exe is not None, "the step ran without a registered program"
    mosaic_calls = exe.as_text().count('custom_call_target="tpu_custom_call"')
    print(f"chip_smoke: [{label}] {sites} pallas_fusion site(s) applied, "
          f"{mosaic_calls} Mosaic custom call(s) in the compiled step")
    if platform != "tpu":       # interpreted kernels lower to plain HLO
        assert mosaic_calls == 0, mosaic_calls
    elif len(ctxs) == 1:        # one call per site, none fell back
        assert mosaic_calls == sites, (mosaic_calls, sites)
    else:
        # under the mesh XLA clones kernels (55 calls for 28 sites on a
        # 2x2 v5e — a recompute, not a fallback): none may be missing
        assert mosaic_calls >= sites, (mosaic_calls, sites)

    result = {"ok": True, "setup_s": round(setup_s, 2),
              "pallas_sites": sites, "mosaic_calls": mosaic_calls,
              "passes": passes, "losses": [round(v, 4) for v in losses]}
    if fit:
        _fit(mod, size, batch, img, metric, rng, label)
    result["wall_s"] = round(time.perf_counter() - t_phase, 2)
    return mod, result


def _fit(mod, size, batch, img, metric, rng, label):
    """The real loop: ``Module.fit`` on a synthetic iterator, continuing
    the module the manual steps trained (same program: the metric's
    counter is already in the step)."""
    import jax
    import mxnet_tpu as mx
    n = size["fit_batches"] * batch
    it = mx.io.NDArrayIter(
        rng.rand(n, *img).astype(np.float32),
        rng.randint(0, size["classes"], (n,)).astype(np.float32),
        batch_size=batch, label_name="softmax_label")
    seen = []
    t0 = time.perf_counter()
    mod.fit(it, eval_metric=metric, num_epoch=1, kvstore=None,
            optimizer="sgd", optimizer_params=dict(_OPT),
            batch_end_callback=lambda p: seen.append(p.nbatch))
    jax.block_until_ready(_fused_buffers(mod))
    fit_s = time.perf_counter() - t0
    assert len(seen) == size["fit_batches"], seen
    name, acc = metric.get()
    assert np.isfinite(acc), (name, acc)
    for a in _fused_buffers(mod):
        assert np.isfinite(np.asarray(a, dtype=np.float32)).all(), a.shape
    print(f"chip_smoke: [{label}] fit(): {len(seen)} batches in "
          f"{fit_s:.1f}s, {name}={acc:.4f}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def serve_phase(mod, size):
    """The trained module frozen into a bucketed bf16 ``Predictor``
    behind ``DynamicBatcher``: requests of 1-3 rows from a few threads,
    every one answered, each equal — within bf16 — to a direct
    ``Predictor`` call on the same rows."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    t_phase = time.perf_counter()
    mx.pass_report(reset=True)
    # set-up: the pass pipeline (its gate compiles the largest bucket
    # before and after each pass) and one program per bucket
    pred = mod.as_predictor(buckets=size["buckets"],
                            compute_dtype="bfloat16")
    pred.warmup()
    setup_s = time.perf_counter() - t_phase
    passes = _check_passes(_pipeline_of("predictor"), "serve")
    img = (3, size["image"], size["image"])
    rng = np.random.RandomState(1)
    reqs = [[rng.rand(1 + (c + i) % 3, *img).astype(np.float32)
             for i in range(size["requests_per_client"])]
            for c in range(size["clients"])]
    answers = [[None] * len(r) for r in reqs]
    errors = []

    def client(c):
        try:
            for i, xr in enumerate(reqs[c]):
                answers[c][i] = bat.predict(xr, timeout=300)
        except BaseException as e:      # re-raised on the main thread
            errors.append(e)

    with serving.DynamicBatcher(pred, max_wait_us=2000, max_queue=4096,
                                name="chip-smoke") as bat:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(size["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), "client hung"
        rep = bat.report()
    if errors:
        raise errors[0]
    worst = 0.0
    total = 0
    for c, row in enumerate(reqs):
        for i, xr in enumerate(row):
            got = np.asarray(answers[c][i])
            ref = np.asarray(pred.predict(xr))
            assert got.shape == ref.shape == (len(xr), size["classes"]), \
                (got.shape, ref.shape)
            assert np.isfinite(got).all()
            np.testing.assert_allclose(got.sum(axis=1), 1.0, atol=2e-2)
            # the batcher ran these rows in another bucket's program:
            # same math, bf16 rounding in another order
            err = float(np.linalg.norm(got - ref)
                        / max(np.linalg.norm(ref), 1e-30))
            worst = max(worst, err)
            total += 1
    assert total == size["clients"] * size["requests_per_client"]
    assert worst < 5e-2, f"batched vs direct relative error {worst}"
    assert rep["shed_requests"] == 0 and rep["deadline_missed"] == 0, rep
    print(f"chip_smoke: [serve] {total} requests answered through the "
          f"batcher, worst relative error vs direct predict {worst:.2e}, "
          f"{pred.retraces} bucket program(s) compiled")
    return {"ok": True, "wall_s": round(time.perf_counter() - t_phase, 2),
            "setup_s": round(setup_s, 2), "requests": total,
            "worst_rel_err": worst, "passes": passes}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def decode_phase(size):
    """``DecodePredictor`` + ``DecodeBatcher`` on the toy LM: concurrent
    token streams equal to solo greedy decode of the same prompts. The
    model is a toy; this proves only that the decode serving code
    executes on the device."""
    from mxnet_tpu.serving.decode import (
        TransformerLMSpec, DecodePredictor, DecodeBatcher, init_params)
    t_phase = time.perf_counter()
    spec = TransformerLMSpec(name="chipsmoke-lm", **size["lm"])
    eng = DecodePredictor(spec, init_params(spec, seed=0),
                          slots=size["slots"],
                          seq_buckets=size["seq_buckets"])
    t0 = time.perf_counter()
    eng.warmup()
    setup_s = time.perf_counter() - t0
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, spec.vocab_size, size=3 + (5 * i) % 11)
               .astype(np.int32) for i in range(size["streams"])]
    solo = [list(eng.generate(p, max_new_tokens=size["new_tokens"]))
            for p in prompts]
    with DecodeBatcher(eng, max_wait_us=2000, max_queue=256,
                       name="chip-smoke-decode") as bat:
        futs = [bat.submit(p, max_new_tokens=size["new_tokens"])
                for p in prompts]
        streams = [f.result(timeout=300) for f in futs]
    for i, (a, s) in enumerate(zip(streams, solo)):
        assert len(s) == size["new_tokens"], (i, s)
        assert all(0 <= t < spec.vocab_size for t in a), a
        assert list(a) == s, f"stream {i}: batched {a} != solo {s}"
    print(f"chip_smoke: [decode] {len(streams)} concurrent streams of "
          f"{size['new_tokens']} tokens equal solo greedy decode")
    return {"ok": True, "wall_s": round(time.perf_counter() - t_phase, 2),
            "setup_s": round(setup_s, 2), "streams": len(streams)}


# ---------------------------------------------------------------------------
def result_line(device):
    """The object of the last line of standard output. The driver accepts
    exactly ``ok`` and ``device`` = ``platform``/``kind``/``count``; what
    else the run has to say goes on the line before."""
    return {"ok": True,
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


def main():
    t_all = time.perf_counter()
    device = preamble()
    import mxnet_tpu as mx
    phases = {}
    mod, phases["train"] = train_phase(mx.tpu(0), FULL)
    # the same steps with the Pallas rewrites in the program: Mosaic's
    # kernels inside the real step, held against the run above
    kern, phases["kernels"] = train_phase(
        mx.tpu(0), FULL, label="kernels", forced=True, fit=False,
        reference=phases["train"]["losses"])
    del kern
    phases["serve"] = serve_phase(mod, FULL)
    del mod
    phases["decode"] = decode_phase(FULL)
    n = device["count"]
    if n > 1:
        # what the mesh adds is shard_map'd kernels, ZeRO-1 and the
        # collectives: forced, so that the kernels are in the program
        _, phases["mesh"] = train_phase([mx.tpu(i) for i in range(n)],
                                        FULL, label="mesh", forced=True)
        mesh = f"{n} devices"
    else:
        mesh = "not run: 1 device"
    print(f"chip_smoke: all phases ok in "
          f"{time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"phases": phases, "mesh": mesh}))
    # the result line: these keys and no others, last on stdout
    print(json.dumps(result_line(device)), flush=True)


if __name__ == "__main__":
    main()
