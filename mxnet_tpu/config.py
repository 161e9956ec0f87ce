"""Runtime configuration via environment variables.

TPU-native rebuild of the reference's env-var layer (reference:
dmlc::GetEnv call sites; canonical list docs/faq/env_var.md). Variables
keep the MXNET_ prefix so reference users' muscle memory carries over;
each is registered with a type, default, and description, and
``mxnet_tpu.config.show()`` prints the table (the reference documents them
only in docs).
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict

__all__ = ["get", "override", "register", "show", "variables"]


from .base import get_env as _get_env

_REGISTRY: Dict[str, tuple] = {}


def register(name: str, default, typ: Callable = str, doc: str = ""):
    """Register a configuration variable."""
    _REGISTRY[name] = (default, typ, doc)
    return name


def get(name: str, default=None):
    """Read a registered variable from the environment (typed), or the
    registered default — built on base.get_env so the truth table for
    booleans is uniform everywhere (reference: dmlc::GetEnv)."""
    if name in _REGISTRY:
        reg_default, typ, _ = _REGISTRY[name]
        eff_default = default if default is not None else reg_default
        return _get_env(name, eff_default, dtype=typ)
    return _get_env(name, default)


import contextlib


@contextlib.contextmanager
def override(name: str, value):
    """Temporarily force a configuration variable's environment value
    (None removes it). The one save/set/restore used by the tuner's
    trials, the serving predictor and the fusion tests — config state lives in the
    environment, so this is also the single place to change if that
    ever moves."""
    old = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = str(value)
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


def variables():
    """{name: (default, current, doc)} for every registered variable."""
    return {name: (d, get(name), doc)
            for name, (d, _t, doc) in sorted(_REGISTRY.items())}


def show():
    """Print the configuration table (reference: docs/faq/env_var.md)."""
    lines = [f"{'variable':<36}{'default':<18}{'current':<18}description"]
    for name, (default, current, doc) in variables().items():
        lines.append(f"{name:<36}{str(default):<18}{str(current):<18}{doc}")
    out = "\n".join(lines)
    print(out)
    return out


# -- the registered surface (reference: docs/faq/env_var.md) -----------------
register("MXNET_HOME", os.path.expanduser("~/.mxnet"), str,
         "Root for downloaded/converted data and embeddings "
         "(env_var.md:125 MXNET_GLUON_REPO analog)")
register("MXNET_TPU_MODEL_ZOO", os.path.expanduser("~/.mxnet_tpu/models"),
         str, "Local directory holding pretrained .params files")
register("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000, int,
         "Batched-allreduce chunking threshold in elements "
         "(env_var.md:74; kvstore_dist.h:58)")
register("MXNET_PROFILER_AUTOSTART", False, bool,
         "Start the profiler at import (env_var.md:105)")
register("MXNET_PROFILER_MODE", "symbolic", str,
         "Profiler mode hint (env_var.md:108)")
register("MXNET_CPU_WORKER_NTHREADS", 1, int,
         "DataLoader worker processes default (env_var.md:13)")
register("MXNET_ENGINE_TYPE", "XLA", str,
         "Engine identifier — informational; XLA async dispatch replaces "
         "ThreadedEngine/NaiveEngine (env_var.md:52)")
register("MXNET_EXEC_BULK_EXEC_TRAIN", True, bool,
         "Whole-step fusion — informational; jit fuses the full step "
         "(env_var.md:62)")
register("MXNET_USE_NATIVE_IO", True, bool,
         "Use the C++ RecordIO reader (native/libmxtpu_io.so, built on "
         "first use) instead of the pure-Python parser")
register("MXNET_BACKWARD_DO_MIRROR", False, bool,
         "Recompute activations in backward (jax.checkpoint) to trade "
         "FLOPs for memory (env_var.md:93)")
register("MXTPU_PALLAS_FUSION", "auto", str,
         "Graph-rewrite pass routing BN(+ReLU)->1x1-conv subgraphs "
         "through the Pallas fused kernel (symbol/fusion.py): 1/0 force "
         "on/off, auto = on for TPU backends, off elsewhere")
register("MXTPU_PASS_RESIDUAL_FUSION", "auto", str,
         "Graph-rewrite pass fusing BN(+ReLU)->conv chains of ANY "
         "geometry onto the analytic-fused-backward composite op "
         "(symbol/passes/residual_fusion.py): 1/0 force, auto = on for "
         "TPU backends")
register("MXTPU_PASS_BN_FOLD", "auto", str,
         "Inference-time constant-fold of Conv->BN into the conv "
         "weights/bias for eval-mode programs (Predictor / inference "
         "executor; symbol/passes/bn_fold.py): 1/0 force, auto = on "
         "for TPU backends")
register("MXTPU_PASS_BF16", "auto", str,
         "bf16 activation-traffic widening around convolutions with "
         "fp32 master params (symbol/passes/bf16_cast.py): 1/0 force, "
         "auto = on for TPU backends; skipped when the program already "
         "runs a sub-f32 compute_dtype")
register("MXTPU_PASS_GATE_BYTES", "auto", str,
         "Measured bytes-accessed gate of the pass manager "
         "(symbol/passes/manager.py): a pass that does not STRICTLY "
         "reduce XLA cost-analysis bytes on the program it rewrote is "
         "rejected at apply time. auto = gate auto-enabled passes, "
         "trust explicitly forced ones; 1 = gate everything; 0 = trust "
         "everything (no measurement compiles)")
register("MXTPU_SERVING_BUCKETS", "1,8,64", str,
         "Default batch buckets for serving.Predictor: requests pad to "
         "the nearest bucket so arbitrary sizes never retrace")
register("MXTPU_SERVING_MAX_WAIT_US", 2000, int,
         "DynamicBatcher coalescing window: how long the first queued "
         "request waits for company before its micro-batch launches")
register("MXTPU_SERVING_MAX_QUEUE", 256, int,
         "DynamicBatcher admission bound in queued ROWS; submits past "
         "it fail fast with serving.Overloaded (load shedding)")
register("MXTPU_DECODE_SLOTS", 4, int,
         "Decode batch width (serving/decode): number of concurrent "
         "generation slots in the continuous-batching decode program; "
         "KV-cache HBM scales linearly with it")
register("MXTPU_DECODE_SEQ_BUCKETS", "16,64", str,
         "Prompt-length buckets for the decode prefill program: prompts "
         "pad to the nearest bucket so arbitrary lengths never retrace "
         "(clipped to the model's max_seq)")
register("MXTPU_DECODE_MAX_WAIT_US", 2000, int,
         "DecodeBatcher first-fill window: when no generation is in "
         "flight, how long the first queued prompt waits for company "
         "before prefill launches (joins mid-flight are immediate)")
register("MXTPU_DECODE_MAX_QUEUE", 256, int,
         "DecodeBatcher admission bound in queued REQUESTS; submits "
         "past it fail fast with serving.Overloaded")
register("MXTPU_SPEC_K", 4, int,
         "Speculation depth for speculative decoding (serving/decode/"
         "spec.py): draft tokens proposed per lane per round; the "
         "target verifies k+1 fed tokens in ONE program and emits "
         "1..k+1 tokens. Verify width k+1 is compile-key material")
register("MXTPU_SPEC_DISABLE_BELOW", 0.125, float,
         "Acceptance-rate floor for speculative decoding: when the "
         "windowed draft-acceptance rate drops below this, the engine "
         "degrades to plain decode (speculation costs bytes it no "
         "longer repays) and re-probes after MXTPU_SPEC_PROBE_STEPS")
register("MXTPU_SPEC_PROBE_STEPS", 64, int,
         "How many plain-decode rounds a degraded speculative engine "
         "serves before probing speculation again")
register("MXTPU_SPEC_WINDOW", 32, int,
         "Sliding window (verify rounds) over which the speculative "
         "engine computes its acceptance rate / accepted-per-step "
         "gauges and the degrade decision")
register("MXTPU_FLEET_ROLE_PREFILL", 0, int,
         "Default prefill-role replica count for a TenantSpec that "
         "doesn't set prefill_replicas: >0 (with MXTPU_FLEET_ROLE_"
         "DECODE) runs the tenant disaggregated — prefill replicas "
         "fill KV lanes and hand them to decode replicas")
register("MXTPU_FLEET_ROLE_DECODE", 0, int,
         "Default decode-role replica count for a TenantSpec that "
         "doesn't set decode_replicas (see MXTPU_FLEET_ROLE_PREFILL)")
register("MXTPU_CKPT_KEEP", 3, int,
         "CheckpointManager retention: newest K valid checkpoints "
         "survive pruning (checkpoint.py)")
register("MXTPU_CKPT_ASYNC", False, bool,
         "CheckpointManager default: snapshot state synchronously but "
         "write checkpoint files on a background thread")
register("MXTPU_FT_GUARD", "auto", str,
         "Non-finite-step guard compiled into the fused train step: "
         "NaN/Inf gradients skip the update in-graph (params/optimizer "
         "state kept, counter bumped). 1/auto = on, 0 = off")
register("MXTPU_FT_MAX_CONSEC_SKIPS", 0, int,
         "Abort training (MXNetError) once this many CONSECUTIVE steps "
         "were guard-skipped (checked laggedly, no per-step sync); "
         "0 disables the abort")
register("MXTPU_FT_DIST_RETRIES", 3, int,
         "Retry count for dist init/barrier transport failures "
         "(exponential backoff, parallel/dist.py)")
register("MXTPU_FT_DIST_BACKOFF", 0.5, float,
         "Initial backoff seconds between dist retries (doubles per "
         "attempt)")
register("MXTPU_FT_DIST_DEADLINE", 120.0, float,
         "Total seconds budget across dist retries and the host-level "
         "fallback collective's blocking KV reads/barriers")
register("MXTPU_FLEET_PROBE_S", 0.25, float,
         "FleetRouter health-probe interval (serving/fleet.py): how "
         "often replica fault flags, straggler latency, and pending "
         "replacements are checked")
register("MXTPU_FLEET_MAX_FAILURES", 3, int,
         "Consecutive request failures before the FleetRouter marks a "
         "replica sick and drains it (a dead replica is drained on the "
         "first probe regardless)")
register("MXTPU_FLEET_STRAGGLER_FACTOR", 3.0, float,
         "FleetRouter auto-drain rule: a replica whose median request "
         "latency reaches this multiple of the median of replica "
         "medians is drained and replaced (the serving twin of "
         "tools/telemetry.py fleet's straggler flagging)")
register("MXTPU_FLEET_MAX_REDISPATCH", 2, int,
         "Max transparent re-dispatches of one request to another "
         "replica after a replica failure/drain before the error "
         "surfaces to the client")
register("MXTPU_FLEET_LAT_WINDOW", 64, int,
         "Per-replica latency samples the router keeps for the "
         "straggler rule (and the minimum is an eighth of it: no "
         "drain verdict off a cold replica's first requests)")
register("MXTPU_FLEET_SCALE_UP_THRESH", 0.5, float,
         "FleetAutoscaler scale-up trigger (serving/autoscale.py): "
         "queued rows above this fraction of the tenant group's total "
         "micro-batch capacity (healthy x max_batch) — or any recent "
         "shed — asks for one more replica, hysteresis permitting")
register("MXTPU_FLEET_SCALE_DOWN_THRESH", 0.05, float,
         "FleetAutoscaler scale-down trigger: sustained load below "
         "this fraction of capacity (and zero recent sheds) retires "
         "one replica via the polite DRAINING path")
register("MXTPU_FLEET_SCALE_COOLDOWN_S", 1.0, float,
         "Autoscaler hysteresis: minimum seconds between scale "
         "decisions for one tenant group (up or down), so a bursty "
         "queue cannot flap the fleet size")
register("MXTPU_FLEET_SCALE_INTERVAL_S", 0.25, float,
         "Autoscaler policy-thread tick interval (signals are read and "
         "one decision made per tick per tenant group)")
register("MXTPU_FLEET_MIN_REPLICAS", 1, int,
         "Autoscaler floor: a tenant group never shrinks below this "
         "many replicas (TenantSpec.min_replicas overrides per tenant)")
register("MXTPU_FLEET_MAX_REPLICAS", 4, int,
         "Autoscaler ceiling: a tenant group never grows past this "
         "many replicas — past it the degradation ladder engages "
         "(TenantSpec.max_replicas overrides per tenant)")
register("MXTPU_FLEET_TENANT_QUOTA", 16, int,
         "Base admission quota in in-flight requests per unit of "
         "tenant weight (serving/tenancy.py): a tenant may hold "
         "weight x this many requests in flight before its submits "
         "shed — the weighted-fair bound that keeps a batch tenant "
         "from starving a latency tenant")
register("MXTPU_FLEET_REDISPATCH_GRACE_S", 5.0, float,
         "How long an ADMITTED request with no deadline may park "
         "waiting for a healthy replica when re-dispatch finds none "
         "(replica condemned, replacement still STARTING) before the "
         "router gives up and sheds it — admitted requests ride out "
         "transient zero-capacity windows instead of dropping")
register("MXTPU_FLEET_DEGRADE_WAIT_FACTOR", 4.0, float,
         "Degradation-ladder rung 2: multiply every live batcher's "
         "max_wait_us by this factor while overloaded at max scale "
         "(bigger batches, higher latency, more throughput); restored "
         "on de-escalation")
register("MXTPU_FLEET_HEARTBEAT_S", 0.5, float,
         "Elastic-training heartbeat lease renewal interval "
         "(parallel/elastic.py): each rank republishes its lease in "
         "the coordination KV store this often")
register("MXTPU_FLEET_LEASE_S", 3.0, float,
         "Heartbeat lease TTL: a rank whose lease is older than this "
         "is declared lost and the survivors re-form at the new world "
         "size (must comfortably exceed MXTPU_FLEET_HEARTBEAT_S)")
register("MXTPU_DATA_PIPELINE", "auto", str,
         "Async host data pipeline (data/pipeline.py) wrapped around "
         "fit()'s train iterator: multi-worker decode, double-buffered "
         "device staging, checkpointable cursor. 1/auto = on, 0 = off; "
         "the batch stream is byte-identical either way")
register("MXTPU_DATA_WORKERS", 2, int,
         "Decode/augment worker threads per DataPipeline (the reference's "
         "preprocess_threads analog for the pipeline subsystem)")
register("MXTPU_DATA_QUEUE_DEPTH", 4, int,
         "Bounded depth (batches) of the pipeline's work/done queues — "
         "how far the source thread reads ahead of the workers")
register("MXTPU_DATA_STAGE_AHEAD", 2, int,
         "Staged-batch slots already device_put ahead of the consumer "
         "(2 = classic double buffering: next batch on device before "
         "the current step retires)")
register("MXTPU_FAULT_INJECT", "", str,
         "Deterministic fault-injection spec, 'site:k=v[:k=v];site2:...' "
         "(faultinject.py) — e.g. 'ckpt_write:byte=100:action=kill', "
         "'nan_grad:step=3'. Empty = no faults. Test-only")
register("MXTPU_COMPILE_CACHE_DIR", "", str,
         "Persistent compiled-program cache directory (compile/): "
         "fused train steps and Predictor buckets serialize their XLA "
         "executables here so a restart loads programs instead of "
         "recompiling. Empty = disabled")
register("MXTPU_COMPILE_CACHE", "auto", str,
         "Compile-cache master switch: 1/auto = on when CACHE_DIR is "
         "set, 0 = off (the compile registry / mx.compile_report() "
         "observability stays on either way)")
register("MXTPU_COMPILE_CACHE_MAX_BYTES", 0, int,
         "Compile-cache size budget for tools/compile_cache.py prune "
         "(oldest entries evicted first); 0 = unlimited")
register("MXTPU_COMPILE_CACHE_MAX_AGE_DAYS", 0.0, float,
         "Compile-cache retention age for tools/compile_cache.py prune; "
         "0 = keep forever")
register("MXTPU_TELEMETRY_DIR", "", str,
         "Durable telemetry export directory (telemetry/export.py): "
         "rotating JSONL event log + periodic report snapshots land "
         "here. Empty = in-memory telemetry only (registry/report stay "
         "on)")
register("MXTPU_TELEMETRY_ROTATE_BYTES", 4 * 1024 * 1024, int,
         "Event-log segment size: events-NNNNN.jsonl rotates to the "
         "next index past this many bytes")
register("MXTPU_TELEMETRY_EVENT_STEPS", 50, int,
         "Emit a train_step milestone event every N steps (step 1 "
         "always emits so short runs still produce a log)")
register("MXTPU_TELEMETRY_SNAPSHOT_STEPS", 500, int,
         "Export a full telemetry snapshot every N train steps "
         "(plus one at timeline close); 0 = close-time snapshot only")
register("MXTPU_TRACE_DIR", "", str,
         "Structured-trace export directory (telemetry/trace.py): host "
         "spans (serving request->batch->bucket, fit step->phase) land "
         "in a bounded ring and export as Chrome trace-event JSON "
         "(trace-<pid>-NNNNN.json, loadable in Perfetto / "
         "chrome://tracing). Set, tracing is on; empty, it is on only "
         "while a jax.profiler trace runs (spans then land in the ring "
         "and in the profiler's trace as mx:<cat>/<name>, no file)")
register("MXTPU_TRACE_RING", 16384, int,
         "Span capacity of the in-memory trace ring: the newest N "
         "completed spans are kept, older ones are overwritten "
         "(trace::dropped counts them) — tracing never allocates "
         "unboundedly on the hot path")
register("MXTPU_PALLAS_TILES", "", str,
         "Pallas fused-kernel output-tile override '<bm>,<bn>' "
         "(ops/pallas_fused.py): tried first by select_tiles/"
         "select_conv_tiles when it divides the shape and Mosaic "
         "accepts it. Values must be positive multiples of 8 within "
         "the built-in candidate bounds (bm<=1024, bn<=512) — invalid "
         "values raise MXNetError at selection time (a bad tile fails "
         "the tuner trial, not the process). Empty = built-in "
         "largest-legal selection")
register("MXTPU_TUNE_DIR", "", str,
         "TuningRecord store directory (tune/record.py). Empty = "
         "<MXTPU_COMPILE_CACHE_DIR>/tune when the compile cache is "
         "configured, else tuning-record persistence is off")
register("MXTPU_TUNE_CACHE", "auto", str,
         "Tuning-record persistence switch: 1/auto = on when a store "
         "directory resolves, 0 = search-only (no records written or "
         "read; mx.tune_report() observability stays on)")
register("MXTPU_TUNE_MAX_TRIALS", 0, int,
         "Trial-count ceiling per search: spaces larger than this are "
         "sampled (seeded, deterministic) instead of enumerated; "
         "0 = exhaustive enumeration")
register("MXTPU_TUNE_HBM_BUDGET", 0, int,
         "Peak-HBM headroom budget in bytes for the tuner's static "
         "pruning: batch-size candidates whose compiled train-step "
         "proxy reports memory_analysis peak above this are pruned "
         "without a measured trial; 0 = no HBM pruning")
register("MXTPU_PARTITION_RULES", "", str,
         "Regex -> PartitionSpec parameter layout rules for mesh binds "
         "(parallel/partition.py): ';'-separated 'regex=spec' clauses, "
         "spec a ','-list of mesh axis names with None/* placeholders "
         "or the word 'replicated'. First re.search match wins. The "
         "resolved rules are compile-key material. Empty = every "
         "parameter replicated (pure data parallelism)")
register("MXTPU_ZERO", "auto", str,
         "ZeRO-1 sharded weight update on mesh binds (module/fused.py, "
         "arXiv:2004.13336): each data-parallel replica owns 1/N of "
         "the optimizer state and updates only its shard; fresh params "
         "all-gather. Bit-identical to the replicated update. "
         "auto/1 = on when the optimizer is an elementwise key-free "
         "rule and the data axis has >1 device; 0 = replicated update")
register("MXTPU_PASS_INT8_PTQ", "auto", str,
         "Post-training int8 weight quantization pass for eval-mode "
         "programs (symbol/passes/int8_ptq.py): rewrites conv/dense "
         "weights to int8 with per-channel f32 scales from the ambient "
         "mx.quant calibration config. 1/0 force, auto = on for TPU "
         "backends; a no-op without an active QuantConfig (counted "
         "skip no_quant_config)")
register("MXTPU_QUANT_GRANULARITY", "per_channel", str,
         "Default quantization granularity for mx.quant.calibrate: "
         "per_channel (one scale per output channel, the accuracy "
         "posture) or per_tensor (one scale per weight tensor — fewer "
         "scale bytes, coarser clipping; the r15 quant workload "
         "searches both)")
register("MXTPU_QUANT_DENSE", "auto", str,
         "Let int8_ptq quantize FullyConnected weights too (1/0 force, "
         "auto = on for TPU backends). Off-TPU the XLA dot emitter "
         "does not fuse the int8->f32 dequant into the matmul, so "
         "int8 dense weights MOVE MORE BYTES than f32 — the measured "
         "gate rejects the rewrite; conv sites fuse everywhere and "
         "stay quantized regardless")
register("MXTPU_QUANT_ACC_TOL", 0.02, float,
         "Calibration accuracy guard (mx.quant.calibrate): a layer "
         "whose simulated-quant output error (relative L2 vs f32 over "
         "the calibration batches) exceeds this tolerance is DISABLED "
         "in the QuantConfig instead of shipped wrong; tools/quant.py "
         "verify gates end-to-end accuracy against the same number")
register("MXTPU_DECODE_KV_DTYPE", "float32", str,
         "KV-cache storage dtype for decode serving (serving/decode/): "
         "float32 or int8. int8 stores each cache row quantized with a "
         "per-(slot,position,head) f32 absmax scale and dequantizes at "
         "f32 compute in-program — ~0.31x the cache HBM at head_dim "
         "16, the decode step moves measurably fewer bytes, and "
         "continuous batching stays bit-identical to solo decode. "
         "Cache layout/dtype is compile-key material")


def _autostart_profiler():
    if get("MXNET_PROFILER_AUTOSTART"):
        from . import profiler
        profiler.set_config(filename=os.path.join(
            os.getcwd(), "profile.json"), aggregate_stats=True)
        profiler.set_state("run")


_autostart_profiler()
