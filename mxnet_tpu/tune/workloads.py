"""Tunable workloads: what a search measures, keyed like a program.

A :class:`Workload` binds a :class:`~.space.SearchSpace` to a concrete
measurement — it owns the canonical cache key (built through
``compile.program_key`` with kind ``"tune"``, so a tuning record is
keyed by the same material as the compiled programs it selects: symbol
digest, input shapes, optimizer, mesh, backend identity, plus the
space and objective), the static-pruning hook, and the ``measure``
function the trial runner drives.

Three measurement families, all reusing machinery that already exists:

- :class:`TrainStepWorkload` — objective ``step_bytes_per_row``: XLA
  cost-analysis bytes-accessed of the train-step proxy
  (``passes.measure_symbol_bytes`` — the same gate currency as r12)
  after running the pass pipeline under the trial's flag regime,
  normalized per batch row. Compile-time, deterministic, CPU-proxy
  friendly. Static pruning bounds the batch knob by peak-HBM headroom
  (``memory_analysis()`` of the compiled proxy vs.
  ``MXTPU_TUNE_HBM_BUDGET``).
- :class:`ServingWorkload` — objective ``p99_ms`` at a fixed
  closed-loop load (``serving/loadgen.py`` through a DynamicBatcher —
  the ONE closed-loop measurement implementation) over bucket-set ×
  ``max_wait_us`` knobs.
- :class:`DataPipelineWorkload` — objective ``wall_s_per_batch`` to
  drain N batches through a ``DataPipeline`` under the trial's
  ``MXTPU_DATA_WORKERS`` / ``MXTPU_DATA_STAGE_AHEAD``.

``conv_proxy()`` / ``sparse_proxy()`` are the built-in CPU-proxy
workloads (the conv family's BN→ReLU→1×1-conv tower and the sparse
family's two-tower embedding+conv recommender) shared by
``tools/tune.py`` and the tier-1 tests.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

from .space import SearchSpace, Knob, pass_knobs, batch_knob, \
    serving_knobs, data_knobs, decode_knobs, quant_knobs, spec_knobs

__all__ = ["Workload", "TrainStepWorkload", "ServingWorkload",
           "DecodeServingWorkload", "DataPipelineWorkload",
           "QuantWorkload", "SpecDecodeWorkload", "conv_proxy",
           "sparse_proxy", "decode_proxy", "quant_proxy",
           "spec_decode_proxy", "builtin_workload", "measure_serving",
           "measure_decode_serving", "BUILTIN_WORKLOADS"]


class Workload:
    """Base: a named, keyed, measurable search target."""

    name = "workload"
    objective = "objective"
    builtin: Optional[str] = None    # tools/tune.py rebuild tag

    def __init__(self, space: SearchSpace):
        self.space = space

    def key(self):
        """Canonical ProgramKey (kind "tune") — see module docstring."""
        from ..compile import program_key
        return program_key("tune", f"tune:{self.name}",
                           **self.key_material())

    def key_material(self) -> dict:
        return {"extra": {"space": self.space.describe(),
                          "objective": self.objective,
                          "builtin": self.builtin}}

    def static(self, cfg: Dict) -> Optional[str]:
        """Prune reason from compile-time analysis, or None."""
        return None

    def measure(self, cfg: Dict, budget: int) -> float:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# train step: bytes-accessed objective over pass flags / tiles / batch
# ---------------------------------------------------------------------------
class TrainStepWorkload(Workload):
    """See module docstring. ``feed_shapes`` are the data/label feed
    shapes WITHOUT the batch dimension resolved per trial when a
    ``batch`` knob is present — they are given at the default batch and
    rescaled along axis 0."""

    objective = "step_bytes_per_row"

    def __init__(self, name, symbol, feed_shapes: Dict[str, tuple],
                 space: SearchSpace, optimizer=None, mesh=None,
                 batch_axis: int = 0, hbm_budget: Optional[int] = None):
        super().__init__(space)
        self.name = name
        self.symbol = symbol
        self.feed_shapes = {n: tuple(s) for n, s in feed_shapes.items()}
        self.optimizer = optimizer
        self.mesh = mesh
        self.batch_axis = int(batch_axis)
        self.hbm_budget = hbm_budget
        self.default_batch = next(iter(self.feed_shapes.values())
                                  )[self.batch_axis]

    def key_material(self):
        from ..compile.key import symbol_digest
        m = super().key_material()
        m.update(symbol_sha=symbol_digest(self.symbol),
                 input_sigs=sorted(self.feed_shapes.items()),
                 optimizer=self.optimizer, mesh=self.mesh)
        return m

    # -- shape plumbing -------------------------------------------------------
    def _shapes(self, cfg) -> Dict[str, tuple]:
        """Full arg+aux shape map at the trial's batch size."""
        batch = int(cfg.get("batch", self.default_batch))
        kw = {}
        for n, s in self.feed_shapes.items():
            s = list(s)
            s[self.batch_axis] = batch
            kw[n] = tuple(s)
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**kw)
        shapes = dict(zip(self.symbol.list_arguments(), arg_shapes))
        shapes.update(zip(self.symbol.list_auxiliary_states(),
                          aux_shapes))
        return shapes

    def _pipeline(self, cfg):
        """The trial's rewritten graph (or the original when no pass
        fired) under the already-applied env regime."""
        from ..symbol import passes as P
        shapes = self._shapes(cfg)
        final, _rep = P.apply_pipeline(self.symbol, shapes, tag="tune",
                                       mode="train")
        return (final if final is not None else self.symbol), shapes

    # -- static pruning: peak-HBM headroom ------------------------------------
    def _budget_bytes(self):
        from .. import config as _config
        if self.hbm_budget is not None:
            return int(self.hbm_budget)
        return int(_config.get("MXTPU_TUNE_HBM_BUDGET", 0))

    def static(self, cfg):
        budget = self._budget_bytes()
        if not budget or "batch" not in cfg:
            return None
        if int(cfg["batch"]) == self.default_batch:
            return None           # the baseline is never pruned away
        peak = self.static_peak_bytes(cfg)
        if peak is not None and peak > budget:
            return (f"peak HBM {peak} > budget {budget} at "
                    f"batch={cfg['batch']}")
        return None

    def static_peak_bytes(self, cfg):
        """``memory_analysis()`` peak of the compiled train-step proxy
        at the trial's batch (None when the backend exposes none)."""
        try:
            import jax
            import numpy as np
            from ..executor import build_graph_fns
            from ..telemetry import memory as _tmem
            sym, shapes = self._pipeline(cfg)
            arg_names = sym.list_arguments()
            aux_names = sym.list_auxiliary_states()
            if any(n not in shapes for n in arg_names + aux_names):
                return None

            def sds(n):
                return jax.ShapeDtypeStruct(tuple(shapes[n]),
                                            np.float32)

            fwd, fwd_loss, _ = build_graph_fns(sym)

            def fn(arg_vals, aux_vals, key):
                return jax.grad(fwd_loss, argnums=0, has_aux=True)(
                    arg_vals, aux_vals, None, key)

            exe = jax.jit(fn).lower(
                tuple(sds(n) for n in arg_names),
                tuple(sds(n) for n in aux_names),
                jax.random.PRNGKey(0)).compile()
            mem = _tmem.analyze(exe)
            return mem.get("peak_bytes") or None
        except Exception:
            return None

    # -- the measured objective -----------------------------------------------
    def measure(self, cfg, budget):
        from ..base import MXNetError
        from ..symbol.passes import measure_symbol_bytes
        sym, shapes = self._pipeline(cfg)
        by = measure_symbol_bytes(sym, shapes, mode="train")
        if by is None:
            raise MXNetError(
                f"{self.name}: backend exposes no cost analysis — the "
                "bytes objective cannot be measured")
        batch = int(cfg.get("batch", self.default_batch))
        return {"objective": by / batch, "step_bytes": by,
                "batch": batch}


# ---------------------------------------------------------------------------
# serving: closed-loop p99 over bucket sets × coalescing windows
# ---------------------------------------------------------------------------
def measure_serving(predictor, feat, max_wait_us, clients, per_client=8,
                    timeout=600):
    """THE closed-loop serving measurement: single-row clients through
    a DynamicBatcher over ``predictor``, plus the RAW compiled predict
    rate at the top bucket for the efficiency column. What
    :class:`ServingWorkload` measures."""
    import numpy as np
    from .. import serving
    from ..serving import loadgen
    rng = np.random.RandomState(0)
    top = predictor.max_batch
    x_top = rng.rand(top, *feat).astype(np.float32)
    predictor.warmup()
    raw_rows_s = loadgen.raw_predict_rate(predictor, x_top, steps=8)
    with serving.DynamicBatcher(predictor, max_wait_us=max_wait_us,
                                max_queue=100_000,
                                name=f"tune{max_wait_us}") as bat:
        x1 = rng.rand(1, *feat).astype(np.float32)
        bat.predict(x1)
        r = loadgen.closed_loop(bat, x1, clients, per_client,
                                timeout=timeout)
        rep = bat.report()
    hot = max(rep["per_bucket"].items(),
              key=lambda kv: kv[1]["batches"] or 0)
    return {
        "objective": r["p99_ms"],
        "rows_s": r["rows_s"],
        "p50_ms": r["p50_ms"],
        "p99_ms": r["p99_ms"],
        "raw_rows_s": raw_rows_s,
        "efficiency": r["rows_s"] / raw_rows_s if raw_rows_s else None,
        "hot_bucket": hot[0],
        "occupancy": hot[1]["occupancy"],
        "retraces": predictor.retraces,
    }


class ServingWorkload(Workload):
    """Bucket-set × ``max_wait_us`` search for a Predictor behind a
    DynamicBatcher. ``make_predictor(buckets)`` builds the Predictor
    for one bucket set (the expensive, per-bucket-set half);
    measurement is :func:`measure_serving` at a fixed closed-loop load.
    ``budget`` scales the per-client request count."""

    objective = "p99_ms"

    def __init__(self, name, make_predictor, feat,
                 bucket_sets: Sequence[str], waits: Sequence[int],
                 space: Optional[SearchSpace] = None,
                 clients: int = 8, per_client: int = 4,
                 symbol=None):
        space = space or SearchSpace(serving_knobs(bucket_sets, waits),
                                     name=f"{name}-serving")
        super().__init__(space)
        self.name = name
        self.make_predictor = make_predictor
        self.feat = tuple(feat)
        self.clients = int(clients)
        self.per_client = int(per_client)
        self.symbol = symbol
        self._cache = {}

    def key_material(self):
        m = super().key_material()
        if self.symbol is not None:
            from ..compile.key import symbol_digest
            m["symbol_sha"] = symbol_digest(self.symbol)
        m["input_sigs"] = [("feat", self.feat),
                           ("clients", self.clients),
                           ("per_client", self.per_client)]
        return m

    def _predictor(self, buckets_spec):
        if buckets_spec not in self._cache:
            buckets = tuple(int(b) for b in
                            str(buckets_spec).split(","))
            self._cache[buckets_spec] = self.make_predictor(buckets)
        return self._cache[buckets_spec]

    def measure(self, cfg, budget):
        pred = self._predictor(cfg["buckets"])
        return measure_serving(pred, self.feat,
                               int(cfg["max_wait_us"]), self.clients,
                               per_client=self.per_client * max(1, budget))


# ---------------------------------------------------------------------------
# decode serving: token-SLO objective over slots × seq buckets × window
# ---------------------------------------------------------------------------
def measure_decode_serving(predictor, prompts, max_wait_us, clients,
                           per_client=2, max_new_tokens=6, timeout=600):
    """THE closed-loop decode measurement: streaming clients through a
    DecodeBatcher over ``predictor`` (``loadgen.token_closed_loop``,
    the one token-granularity driver). The objective folds both token SLOs into one end-to-end generation p99
    proxy: ``ttft_p99 + max_new_tokens * inter_token_p99``."""
    from ..serving import loadgen
    from ..serving.decode import DecodeBatcher
    predictor.warmup()
    with DecodeBatcher(predictor, max_wait_us=max_wait_us,
                       max_queue=100_000,
                       name=f"tune-decode{max_wait_us}") as bat:
        r = loadgen.token_closed_loop(
            bat, prompts, clients, per_client,
            max_new_tokens=max_new_tokens, timeout=timeout)
        rep = bat.report()
    ttft99 = r["ttft_p99_ms"] or 0.0
    itl99 = r["inter_token_p99_ms"] or 0.0
    return {
        "objective": ttft99 + max_new_tokens * itl99,
        "tok_s": r["tok_s"],
        "ttft_p50_ms": r["ttft_p50_ms"],
        "ttft_p99_ms": r["ttft_p99_ms"],
        "inter_token_p50_ms": r["inter_token_p50_ms"],
        "inter_token_p99_ms": r["inter_token_p99_ms"],
        "tokens": r["tokens"],
        "served_generations": rep["served_generations"],
        "retraces": predictor.retraces,
    }


class DecodeServingWorkload(Workload):
    """Slots × seq-bucket-set × first-fill-window search for a
    DecodePredictor behind a DecodeBatcher. ``make_engine(slots,
    seq_buckets)`` builds the engine for one (lanes, bucket-set) point —
    the expensive compile half, cached per point; measurement is
    :func:`measure_decode_serving` at a fixed streaming load (``budget``
    scales the per-client generation count)."""

    objective = "gen_p99_proxy_ms"

    def __init__(self, name, make_engine, prompts,
                 slot_counts: Sequence[int],
                 bucket_sets: Sequence[str], waits: Sequence[int],
                 space: Optional[SearchSpace] = None,
                 clients: int = 4, per_client: int = 2,
                 max_new_tokens: int = 6, spec=None):
        space = space or SearchSpace(
            decode_knobs(slot_counts, bucket_sets, waits),
            name=f"{name}-decode")
        super().__init__(space)
        self.name = name
        self.make_engine = make_engine
        self.prompts = list(prompts)
        self.clients = int(clients)
        self.per_client = int(per_client)
        self.max_new_tokens = int(max_new_tokens)
        self.spec = spec
        self._cache = {}

    def key_material(self):
        m = super().key_material()
        if self.spec is not None:
            m["extra"] = dict(m["extra"], **self.spec.key_material())
        m["input_sigs"] = [
            ("prompt_lens", tuple(int(p.shape[0]) for p in self.prompts)),
            ("clients", self.clients),
            ("per_client", self.per_client),
            ("max_new_tokens", self.max_new_tokens)]
        return m

    def _engine(self, slots, buckets_spec):
        key = (int(slots), str(buckets_spec))
        if key not in self._cache:
            buckets = tuple(int(b) for b in
                            str(buckets_spec).split(","))
            self._cache[key] = self.make_engine(int(slots), buckets)
        return self._cache[key]

    def measure(self, cfg, budget):
        eng = self._engine(cfg["slots"], cfg["seq_buckets"])
        return measure_decode_serving(
            eng, self.prompts, int(cfg["max_wait_us"]), self.clients,
            per_client=self.per_client * max(1, budget),
            max_new_tokens=self.max_new_tokens)


# ---------------------------------------------------------------------------
# speculative decode: bytes-per-ACCEPTED-token over k × draft size
# ---------------------------------------------------------------------------
class SpecDecodeWorkload(Workload):
    """Round-21 speculative-posture search: speculation depth ``k`` ×
    draft shrink factor × draft layer count. The expensive half per
    draft-size point is DISTILLATION (``spec.distill_draft`` — the
    draft is trained to imitate the target's greedy rollouts), cached
    per (shrink, layers) so every ``k`` trial at that size reuses it;
    the measurement streams a fixed prompt set through a speculative
    ``DecodeBatcher`` and reads the predictor's own accounting.

    The objective is ``spec_bytes_per_accepted_token`` — XLA
    cost-analysis bytes of one verify launch plus ``k`` draft steps,
    divided by the tokens the verify rounds actually emitted. It is the
    r12 gate currency normalized by the quantity speculation exists to
    maximize: a deep ``k`` with a bad draft measures WORSE than plain
    decode (wasted draft bytes), and so does a draft so large its own
    steps eat the amortization — only the measured trial sees where
    acceptance and draft cost balance."""

    objective = "spec_bytes_per_accepted_token"

    def __init__(self, name, spec, params, prompts,
                 space: Optional[SearchSpace] = None,
                 ks: Sequence[int] = (4, 2, 6),
                 shrinks: Sequence[int] = (2, 4),
                 draft_layers: Sequence[int] = (1,),
                 slots: int = 2, seq_buckets: Sequence[int] = (16,),
                 max_new_tokens: int = 12, distill_rollout: int = 40,
                 distill_epochs: int = 6):
        space = space or SearchSpace(
            spec_knobs(ks, shrinks, draft_layers), name=f"{name}-spec")
        super().__init__(space)
        self.name = name
        self.spec = spec
        self.params = dict(params)
        self.prompts = list(prompts)
        self.slots = int(slots)
        self.seq_buckets = tuple(int(b) for b in seq_buckets)
        self.max_new_tokens = int(max_new_tokens)
        self.distill_rollout = int(distill_rollout)
        self.distill_epochs = int(distill_epochs)
        self._target = None          # distillation rollout source
        self._drafts = {}            # (shrink, layers) -> (spec, params)

    def key_material(self):
        m = super().key_material()
        m["extra"] = dict(m["extra"], **self.spec.key_material())
        m["input_sigs"] = [
            ("prompt_lens", tuple(int(p.shape[0]) for p in self.prompts)),
            ("slots", self.slots), ("seq_buckets", self.seq_buckets),
            ("max_new_tokens", self.max_new_tokens),
            ("distill", (self.distill_rollout, self.distill_epochs))]
        return m

    def _draft(self, shrink, layers):
        key = (int(shrink), int(layers))
        if key not in self._drafts:
            from ..serving.decode import DecodePredictor
            from ..serving.decode.spec import make_draft_spec, \
                distill_draft
            if self._target is None:
                self._target = DecodePredictor(
                    self.spec, self.params, slots=1,
                    seq_buckets=self.seq_buckets,
                    name=f"{self.name}-distill-src")
            dspec = make_draft_spec(self.spec, num_layers=int(layers),
                                    shrink=int(shrink),
                                    name=f"{self.name}-d{shrink}x{layers}")
            dparams = distill_draft(self._target, dspec,
                                    rollout=self.distill_rollout,
                                    num_epoch=self.distill_epochs,
                                    seed=0)
            self._drafts[key] = (dspec, dparams)
        return self._drafts[key]

    def measure(self, cfg, budget):
        from ..base import MXNetError
        from ..serving.decode import DecodeBatcher
        from ..serving.decode.spec import SpecDecodePredictor
        dspec, dparams = self._draft(cfg["draft_shrink"],
                                     cfg["draft_layers"])
        pred = SpecDecodePredictor(
            self.spec, self.params, dspec, dparams,
            k=int(cfg["spec_k"]), slots=self.slots,
            seq_buckets=self.seq_buckets,
            name=f"{self.name}-k{cfg['spec_k']}")
        pred.warmup()
        with DecodeBatcher(pred, max_wait_us=0, max_queue=100_000,
                           name=f"tune-spec{cfg['spec_k']}") as bat:
            for _ in range(max(1, budget)):
                streams = [bat.submit(
                    p, max_new_tokens=self.max_new_tokens)
                    for p in self.prompts]
                for s in streams:
                    for _tok in s:
                        pass
        rep = pred.report()["spec"]
        bpt = pred.spec_bytes_per_accepted_token()
        if bpt is None:
            raise MXNetError(
                f"{self.name}: no verify rounds ran (or the backend "
                "exposes no cost analysis) — the bytes-per-accepted-"
                "token objective cannot be measured")
        plain = pred.decode_bytes_per_token()
        return {"objective": float(bpt),
                "plain_bytes_per_token": plain,
                "bytes_ratio_vs_plain":
                    float(bpt) / plain if plain else None,
                "accepted_per_step": rep["accepted_per_step"],
                "acceptance_rate": rep["acceptance_rate"],
                "rounds": rep["rounds"],
                "degrade_events": rep["degrade_events"],
                "retraces": pred.retraces}


# ---------------------------------------------------------------------------
# quantization posture: total-bytes objective over granularity × KV dtype
# ---------------------------------------------------------------------------
class QuantWorkload(Workload):
    """Round-19 quantization-posture search: weight-scale granularity ×
    decode KV-cache dtype (both env knobs — the runner applies them via
    ``config.override``, this workload only reads the ambient values).
    The objective is one bytes total in the r12 gate currency: the
    int8-PTQ-rewritten serving program's cost-analysis bytes
    (calibrated at the trial's granularity — a layer the accuracy guard
    disables stays fp32, so a granularity that trips the guard measures
    WORSE, never silently wrong) + the decode-step bytes + the KV-cache
    footprint of an engine built at the trial's KV dtype. A "win" here
    is the same measured claim the pass manager's gate enforces."""

    objective = "quant_bytes_total"

    def __init__(self, name, symbol, params, feed_shapes: Dict[str, tuple],
                 make_engine, space: Optional[SearchSpace] = None,
                 data_names: Optional[Sequence[str]] = None):
        space = space or SearchSpace(quant_knobs(), name=f"{name}-quant")
        super().__init__(space)
        self.name = name
        self.symbol = symbol
        self.params = dict(params)
        self.feed_shapes = {n: tuple(s) for n, s in feed_shapes.items()}
        self.make_engine = make_engine
        self.data_names = set(data_names or self.feed_shapes)
        self._engines = {}     # kv_dtype -> warmed engine (compile half)

    def key_material(self):
        from ..compile.key import symbol_digest
        m = super().key_material()
        m["symbol_sha"] = symbol_digest(self.symbol)
        m["input_sigs"] = sorted(self.feed_shapes.items())
        return m

    def _shapes(self) -> Dict[str, tuple]:
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(
            **self.feed_shapes)
        shapes = dict(zip(self.symbol.list_arguments(), arg_shapes))
        shapes.update(zip(self.symbol.list_auxiliary_states(),
                          aux_shapes))
        return shapes

    def _engine(self, kv_dtype):
        if kv_dtype not in self._engines:
            eng = self.make_engine(kv_dtype)
            eng.warmup()
            self._engines[kv_dtype] = eng
        return self._engines[kv_dtype]

    def measure(self, cfg, budget):
        from .. import config as _config
        from .. import quant as _q
        from ..base import MXNetError
        from ..symbol import passes as P
        gran = str(_config.get("MXTPU_QUANT_GRANULARITY", "per_channel"))
        kvd = str(_config.get("MXTPU_DECODE_KV_DTYPE", "float32"))
        qcfg = _q.calibrate((self.symbol, self.params), granularity=gran)
        shapes = self._shapes()
        # force the pass on: the trial IS the measurement, so the gate's
        # auto-posture double-measure is redundant work here (forced
        # flags are trusted under MXTPU_PASS_GATE_BYTES=auto)
        with _q.quant_scope(qcfg), \
                _config.override("MXTPU_PASS_INT8_PTQ", "1"):
            final, _rep = P.apply_pipeline(
                self.symbol, shapes, tag="tune", mode="serving",
                data_names=self.data_names)
            sym2 = final if final is not None else self.symbol
            serving = P.measure_symbol_bytes(
                sym2, shapes, mode="serving", data_names=self.data_names)
        if serving is None:
            raise MXNetError(
                f"{self.name}: backend exposes no cost analysis — the "
                "bytes objective cannot be measured")
        eng = self._engine(kvd)
        decode = float(eng.program_cost("decode").get(
            "bytes accessed", 0.0))
        kv = float(eng.kv_cache_bytes())
        return {"objective": float(serving) + decode + kv,
                "serving_bytes": float(serving),
                "decode_step_bytes": decode,
                "kv_cache_bytes": kv,
                "granularity": gran, "kv_dtype": kvd,
                "quant_layers_enabled": len(qcfg.enabled_layers())}


# ---------------------------------------------------------------------------
# data pipeline: drain-wall objective over worker/staging knobs
# ---------------------------------------------------------------------------
class DataPipelineWorkload(Workload):
    """``MXTPU_DATA_WORKERS`` × ``MXTPU_DATA_STAGE_AHEAD`` search:
    objective is the wall per batch to drain ``make_iter()`` through a
    DataPipeline (budget multiplies the drained-batch count). The env
    knobs are applied by the runner; the pipeline reads them at
    construction."""

    objective = "wall_s_per_batch"

    def __init__(self, name, make_iter, batches: int = 16,
                 space: Optional[SearchSpace] = None,
                 consume_s: float = 0.0):
        space = space or SearchSpace(data_knobs(), name=f"{name}-data")
        super().__init__(space)
        self.name = name
        self.make_iter = make_iter
        self.batches = int(batches)
        self.consume_s = float(consume_s)

    def key_material(self):
        m = super().key_material()
        m["input_sigs"] = [("batches", self.batches),
                           ("consume_s", self.consume_s)]
        return m

    def measure(self, cfg, budget):
        import time as _time
        from ..data import DataPipeline
        n = self.batches * max(1, budget)
        pipe = DataPipeline(self.make_iter())
        t0 = _time.time()
        got = 0
        try:
            for _ in pipe:
                got += 1
                if self.consume_s:
                    _time.sleep(self.consume_s)
                if got >= n:
                    break
        finally:
            pipe.close()
        wall = _time.time() - t0
        if not got:
            raise RuntimeError(f"{self.name}: iterator yielded nothing")
        return {"objective": wall / got, "batches": got,
                "stats": pipe.stats()}


# ---------------------------------------------------------------------------
# built-in CPU proxies (tools/tune.py / tests)
# ---------------------------------------------------------------------------
def _conv_symbol():
    """The conv family proxy: a BN→ReLU→1×1-conv tower (the exact
    subgraph the Pallas fusion pass targets) + classifier — ResNet-50's
    hot pattern at interactive CPU size."""
    from .. import symbol as sym
    data = sym.Variable("data")
    cur = data
    for i in range(2):
        bn = sym.BatchNorm(cur, name=f"bn{i}", fix_gamma=False)
        act = sym.Activation(bn, act_type="relu", name=f"relu{i}")
        cur = sym.Convolution(act, kernel=(1, 1), num_filter=16,
                              no_bias=True, name=f"conv{i}")
    fc = sym.FullyConnected(sym.Flatten(cur), num_hidden=8, name="fc")
    return sym.SoftmaxOutput(fc, name="softmax")


def _sparse_symbol(vocab=1000, dim=16):
    """The sparse family proxy: the two-tower recommender shape — an
    embedding lookup tower concatenated with a conv/BN dense tower (the
    r13 workload family; lookup-only graphs take the pass manager's
    ``embedding_graph`` skip, so the dense tower is what the pass knobs
    act on)."""
    from .. import symbol as sym
    img = sym.Variable("img")
    bn = sym.BatchNorm(img, name="bn1", fix_gamma=False)
    a = sym.Activation(bn, act_type="relu", name="relu1")
    conv = sym.Convolution(a, kernel=(1, 1), num_filter=16,
                           no_bias=True, name="conv1")
    ids = sym.Variable("ids")
    emb = sym.Embedding(data=ids, input_dim=vocab, output_dim=dim,
                        name="emb")
    cat = sym.Concat(sym.Flatten(conv), sym.Flatten(emb), dim=1)
    fc = sym.FullyConnected(cat, num_hidden=8, name="fc")
    return sym.SoftmaxOutput(fc, name="softmax")


def conv_proxy(batch: int = 8, batches=(8, 16, 32),
               hbm_budget: Optional[int] = None) -> TrainStepWorkload:
    """The conv-family built-in: pass-flag + tile + batch knobs over
    the BN→ReLU→1×1-conv proxy, bytes-per-row objective."""
    from .space import tile_knobs
    knobs = pass_knobs(("MXTPU_PALLAS_FUSION",
                        "MXTPU_PASS_RESIDUAL_FUSION",
                        "MXTPU_PASS_BF16")) + tile_knobs() + \
        [batch_knob(tuple(dict.fromkeys((batch,) + tuple(batches))),
                    default=batch)]
    wl = TrainStepWorkload(
        "conv_small", _conv_symbol(),
        {"data": (batch, 8, 8, 8), "softmax_label": (batch,)},
        SearchSpace(knobs, name="conv_small"), hbm_budget=hbm_budget)
    wl.builtin = "conv"
    return wl


def sparse_proxy(batch: int = 8, batches=(8, 16, 32),
                 hbm_budget: Optional[int] = None) -> TrainStepWorkload:
    """The sparse-family built-in: pass-flag + batch knobs over the
    two-tower embedding+conv proxy, bytes-per-row objective."""
    knobs = pass_knobs(("MXTPU_PALLAS_FUSION", "MXTPU_PASS_BF16")) + \
        [batch_knob(tuple(dict.fromkeys((batch,) + tuple(batches))),
                    default=batch)]
    wl = TrainStepWorkload(
        "sparse_two_tower", _sparse_symbol(),
        {"img": (batch, 8, 4, 4), "ids": (batch, 2),
         "softmax_label": (batch,)},
        SearchSpace(knobs, name="sparse_two_tower"),
        hbm_budget=hbm_budget)
    wl.builtin = "sparse"
    return wl


def decode_proxy(slot_counts=(2, 4), bucket_sets=("16", "16,32"),
                 waits=(2000, 0), clients: int = 4,
                 per_client: int = 2,
                 max_new_tokens: int = 6) -> DecodeServingWorkload:
    """The decode-family built-in: a pocket transformer LM
    (serving/decode/model.py at interactive CPU size) searched over
    KV-cache lanes × prefill buckets × first-fill window against the
    token-SLO objective."""
    import numpy as np
    from ..serving.decode import TransformerLMSpec, DecodePredictor, \
        init_params
    spec = TransformerLMSpec(vocab_size=64, num_embed=32, num_heads=2,
                             num_layers=2, max_seq=32, name="tunelm")
    params = init_params(spec, seed=0)

    def make_engine(slots, seq_buckets):
        return DecodePredictor(spec, params, slots=slots,
                               seq_buckets=seq_buckets)

    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, spec.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 3, 12, 7, 14)]
    wl = DecodeServingWorkload(
        "decode_lm", make_engine, prompts, slot_counts, bucket_sets,
        waits, clients=clients, per_client=per_client,
        max_new_tokens=max_new_tokens, spec=spec)
    wl.builtin = "decode"
    return wl


def quant_proxy(batch: int = 4, slots: int = 2,
                seq_buckets=(8,)) -> QuantWorkload:
    """The quant-family built-in: granularity × KV-dtype knobs over the
    conv proxy (deterministic seed-0 weights — the FC "fc" layer
    exercises the dense-off bailout on CPU backends) plus a pocket
    decode engine, total-bytes objective."""
    import numpy as np
    from ..serving.decode import TransformerLMSpec, DecodePredictor, \
        init_params
    sym = _conv_symbol()
    feed = {"data": (batch, 8, 8, 8), "softmax_label": (batch,)}
    arg_shapes, _, aux_shapes = sym.infer_shape(**feed)
    rng = np.random.RandomState(0)
    params = {}
    for n, s in list(zip(sym.list_arguments(), arg_shapes)) + \
            list(zip(sym.list_auxiliary_states(), aux_shapes)):
        if n not in feed:
            params[n] = rng.uniform(-0.5, 0.5, size=s).astype(np.float32)
    spec = TransformerLMSpec(vocab_size=64, num_embed=32, num_heads=2,
                             num_layers=2, max_seq=16, name="quantlm")
    lm_params = init_params(spec, seed=0)

    def make_engine(kv_dtype):
        return DecodePredictor(spec, lm_params, slots=slots,
                               seq_buckets=tuple(seq_buckets),
                               kv_dtype=kv_dtype)

    wl = QuantWorkload("quant_posture", sym, params, feed, make_engine)
    wl.builtin = "quant"
    return wl


def spec_decode_proxy(ks=(4, 2), shrinks=(2,), draft_layers=(1,),
                      slots: int = 2, seq_buckets=(16,),
                      max_new_tokens: int = 10) -> SpecDecodeWorkload:
    """The speculative-decode built-in: a pocket transformer target
    (deterministic seed-0 weights) with per-trial distilled drafts,
    searched over depth × draft size against the
    bytes-per-accepted-token objective. Distillation epochs are kept
    small — the proxy exists to exercise the search loop at
    interactive CPU cost, not to reach bench-grade acceptance."""
    import numpy as np
    from ..serving.decode import TransformerLMSpec, init_params
    spec = TransformerLMSpec(vocab_size=64, num_embed=32, num_heads=2,
                             num_layers=2, max_seq=48, name="speclm")
    params = init_params(spec, seed=0)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, spec.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 3, 12)]
    wl = SpecDecodeWorkload(
        "spec_decode_lm", spec, params, prompts, ks=ks, shrinks=shrinks,
        draft_layers=draft_layers, slots=slots, seq_buckets=seq_buckets,
        max_new_tokens=max_new_tokens, distill_rollout=24,
        distill_epochs=4)
    wl.builtin = "spec_decode"
    return wl


BUILTIN_WORKLOADS = {"conv": conv_proxy, "sparse": sparse_proxy,
                     "decode": decode_proxy, "quant": quant_proxy,
                     "spec_decode": spec_decode_proxy}


def builtin_workload(name: str, **kwargs) -> Workload:
    """Rebuild a built-in proxy workload by tag — how ``tools/tune.py
    verify`` re-measures a stored record's objective."""
    try:
        return BUILTIN_WORKLOADS[name](**kwargs)
    except KeyError:
        raise KeyError(f"unknown builtin workload {name!r}; known: "
                       f"{sorted(BUILTIN_WORKLOADS)}")
