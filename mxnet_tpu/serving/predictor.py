"""Predictor: a frozen, bucketed, compiled inference program.

The reference's C Predict API (c_predict_api.cc) freezes symbol+params
and binds one executor per input shape; BucketingModule shares params
across per-bucket executors. This class is both at once, TPU-native:
ONE jitted inference function whose XLA cache is keyed by the padded
batch bucket, parameters staged on device once (optionally cast to
bf16), the ``MXTPU_PALLAS_FUSION`` graph rewrite applied to the predict
program, and the request's (donated) input buffer the only per-call
host↔device traffic.

Bucketing: arbitrary request sizes pad up to the nearest configured
bucket, so the set of compiled programs is small and fixed — a mixed
stream of request sizes compiles each bucket exactly once
(``retraces`` counts actual traces; tests pin it). Oversized inputs
split into largest-bucket chunks.
"""
from __future__ import annotations

import threading

import numpy as np

from .. import config
from ..base import MXNetError
from . import _register_predictor

__all__ = ["Predictor", "default_buckets"]


def default_buckets():
    """Bucket set from MXTPU_SERVING_BUCKETS (ascending, deduped)."""
    raw = str(config.get("MXTPU_SERVING_BUCKETS", "1,8,64"))
    try:
        buckets = sorted({int(x) for x in raw.replace(" ", "").split(",")
                          if x})
    except ValueError:
        raise MXNetError(
            f"MXTPU_SERVING_BUCKETS={raw!r} is not a comma-separated "
            "integer list")
    if not buckets or buckets[0] < 1:
        raise MXNetError(
            f"MXTPU_SERVING_BUCKETS={raw!r} must name positive batch "
            "sizes")
    return tuple(buckets)


class Predictor:
    """Inference-only compiled program over a frozen symbol+params.

    Parameters
    ----------
    symbol : Symbol
        The model graph (output heads as trained; SoftmaxOutput & co
        evaluate in inference mode — no labels consumed).
    arg_params / aux_params : dict name -> NDArray (or array)
        Trained parameter/aux values; staged on device once.
    data_names : tuple of str
        Input argument names fed per request (everything else in
        ``list_arguments`` must be in the params or is zero-filled —
        e.g. a ``softmax_label`` head argument).
    data_shapes : dict name -> per-row feature shape (no batch dim)
        Required for every data name; buckets supply the batch dim.
    buckets : tuple of int, optional
        Ascending batch buckets (default: MXTPU_SERVING_BUCKETS).
    compute_dtype : str/dtype, optional
        e.g. "bfloat16": float32 params are cast ONCE at staging and
        inputs in-program; outputs return float32.
    apply_fusion : bool, optional
        Force the MXTPU_PALLAS_FUSION predict-program rewrite on/off
        (default: the flag's own resolution).
    """

    def __init__(self, symbol, arg_params, aux_params=None,
                 data_names=("data",), data_shapes=None, buckets=None,
                 compute_dtype=None, apply_fusion=None):
        import jax
        import jax.numpy as jnp

        self.symbol = symbol
        self.data_names = list(data_names)
        self.buckets = tuple(sorted(set(buckets))) if buckets \
            else default_buckets()
        if data_shapes is None:
            raise MXNetError(
                "Predictor needs data_shapes={name: per-row feature "
                "shape} — the batch dim comes from the buckets")
        self.data_shapes = {n: tuple(s) for n, s in data_shapes.items()}
        for n in self.data_names:
            if n not in self.data_shapes:
                raise MXNetError(f"data_shapes missing entry for '{n}'")
        self._cdt = jnp.dtype(compute_dtype) \
            if compute_dtype is not None else None

        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        aux_params = aux_params or {}
        self.param_names = [n for n in arg_names
                            if n not in self.data_names]
        self.output_names = symbol.list_outputs()

        # infer the full argument/output shape sets at TWO batch sizes:
        # comparing them identifies what actually TRACKS the batch —
        # which non-param args are label-head inputs to zero-fill per
        # bucket, and which outputs carry a batch axis to trim/split
        # (a coincidental leading dim equal to the bucket must not
        # count: a conv weight with num_filter == bucket is a missing
        # PARAM, and a fixed-shape aux output must never be sliced).
        # The largest-bucket shapes also feed the fusion pass's tile
        # bail-outs (batch-independent, so one bucket suffices).
        top = self.buckets[-1]

        def _infer(b):
            shape_kwargs = {n: (b,) + self.data_shapes[n]
                            for n in self.data_names}
            a, o, x = symbol.infer_shape(**shape_kwargs)
            return (dict(zip(arg_names, a)), list(o),
                    dict(zip(aux_names, x)))

        arg_shape_map, out_shapes, aux_shape_map = _infer(top)
        arg_alt, out_alt, _ = _infer(top + 1)

        def _tracks_batch(s_top, s_alt, b_top=top):
            return bool(s_top) and s_top[0] == b_top \
                and s_alt[0] == b_top + 1

        self.out_batched = [_tracks_batch(s, sa)
                            for s, sa in zip(out_shapes, out_alt)]

        # non-param, non-data args whose leading dim tracks the batch
        # (e.g. a softmax_label head argument, unused in inference) are
        # zero-filled per bucket; everything else must come from params
        self._zero_args = []
        missing = []
        for n in self.param_names:
            if n in arg_params:
                continue
            if _tracks_batch(arg_shape_map[n], arg_alt[n]):
                self._zero_args.append(n)
            else:
                missing.append(n)
        if missing:
            raise MXNetError(f"Predictor missing parameters {missing}")
        for n in aux_names:
            if n not in aux_params:
                raise MXNetError(f"Predictor missing aux state '{n}'")

        self._arg_shape_map = arg_shape_map
        self._aux_shape_map = aux_shape_map
        self._aux_names = aux_names
        self._pvals = {n: self._stage_value(arg_params[n],
                                            arg_shape_map[n], n)
                       for n in self.param_names
                       if n not in self._zero_args}

        # predict-program rewrite pipeline (symbol/passes/): the same
        # fusion rewrites the train step gets, plus the serving-only BN
        # constant-fold — eval-mode moving stats are constants, so
        # matched Conv→BN BatchNorms disappear from the compiled
        # predict program entirely. ``apply_fusion`` forces the pallas
        # pass on/off; the other passes follow their MXTPU_PASS_*
        # flags. Applicability uses the largest-bucket bound shapes.
        import contextlib
        run_sym = symbol
        self.fusion_report = None
        self.pass_report = None
        from ..symbol import passes as _passes
        shapes = dict(arg_shape_map)
        shapes.update(aux_shape_map)
        force = contextlib.nullcontext()
        if apply_fusion is not None:
            force = config.override("MXTPU_PALLAS_FUSION",
                                    "1" if apply_fusion else "0")
        with force:
            fused_sym, self.pass_report = _passes.apply_pipeline(
                symbol, {n: tuple(s) for n, s in shapes.items()},
                tag="predictor", mode="serving",
                compute_dtype=self._cdt,
                data_names=set(self.data_names) | set(self._zero_args))
        self.fusion_report = _passes.legacy_fusion_entry(
            self.pass_report)
        self._passes_material = _passes.pipeline_key_material(
            self.pass_report)
        if fused_sym is not None:
            run_sym = fused_sym

        from .. import compile as compile_mod
        from ..symbol.passes import hoist as _hoist
        run_arg_names = run_sym.list_arguments()
        run_aux_names = run_sym.list_auxiliary_states()
        self._arg_names = arg_names
        key = jax.random.PRNGKey(0)
        cdt = self._cdt
        zero_args = set(self._zero_args)
        # parameter-expression hoisting (symbol/passes/hoist.py): a
        # rewrite pass may leave weight-sized arithmetic in the graph
        # (the BN fold's w·s, a bf16 weight cast). Frozen params make
        # those subgraphs constants, so evaluate them ONCE here and
        # feed the results as precomputed program arguments — the
        # serving program reads the folded weight directly and the BN
        # (plus its four parameter vectors) vanishes from the compiled
        # program's byte traffic, not just its op count.
        hoist_keys, live_vars = _hoist.hoist_plan(
            run_sym, set(self.data_names) | zero_args)
        staged_aux = {n: self._stage_value(aux_params[n],
                                           aux_shape_map[n], n)
                      for n in aux_names}
        if hoist_keys:
            amap = dict(self._pvals)
            amap.update(staged_aux)
            self._hvals = tuple(
                jax.device_put(v)
                for v in _hoist.hoist_values(run_sym, hoist_keys, amap))
        else:
            self._hvals = ()
        hoist_ids = [(id(n), i) for n, i in hoist_keys]
        # parameters are explicit ARGUMENTS of the compiled program (in
        # the traced graph's arg order), not closure constants: baked-in
        # values would bloat every executable with the full weight set
        # and — worse — let a persistent-cache hit replay stale weights.
        # As arguments (hoisted values included: they recompute from the
        # current params at staging), the executable is
        # weight-independent and the program key only covers
        # shapes/dtypes.
        self._pval_names = [n for n in run_arg_names
                            if n in self._pvals and n in live_vars]
        self._pvals_t = tuple(self._pvals[n] for n in self._pval_names)
        pval_names = list(self._pval_names)
        live_aux_names = [n for n in run_aux_names if n in live_vars]
        self._avals = tuple(staged_aux[n] for n in live_aux_names)
        # restage() needs the staging plan after __init__: which symbol
        # actually runs, which param expressions were hoisted, and
        # which aux names the program consumes
        self._run_sym = run_sym
        self._hoist_keys = hoist_keys
        self._live_aux_names = live_aux_names

        def mx_predict(pvals_t, data_vals, avals, hvals):
            amap = dict(zip(pval_names, pvals_t))
            amap.update(zip(live_aux_names, avals))
            bsz = data_vals[0].shape[0]
            for n, v in zip(self.data_names, data_vals):
                if cdt is not None and v.dtype == jnp.float32:
                    v = v.astype(cdt)
                amap[n] = v
            for n in zero_args:
                s = (bsz,) + tuple(arg_shape_map[n][1:])
                amap[n] = jnp.zeros(s, jnp.float32)
            outs, _ = run_sym.eval_arrays_ex(
                amap, training=False, rng_key=key,
                preset=dict(zip(hoist_ids, hvals)))
            return tuple(o.astype(jnp.float32)
                         if cdt is not None and o.dtype == cdt else o
                         for o in outs)

        # donate the request buffers: they are fresh padded arrays each
        # call, so XLA may reuse them for outputs (donation_supported is
        # the compile subsystem's one home for the CPU-can't-donate
        # policy — the old per-Predictor workaround for the per-compile
        # backend warning)
        donate = {"donate_argnums": (1,)} \
            if compile_mod.donation_supported() else {}
        self._infer_jit = jax.jit(mx_predict, **donate)
        self._donate = bool(donate)
        self._programs = {}     # (bucket, dtypes) -> compiled program
        self._program_costs = {}  # (bucket, dtypes) -> XLA cost dict
        self._program_exes = {}   # (bucket, dtypes) -> raw executable
        self._program_memory = {}  # (bucket, dtypes) -> memory dict
        self._materialized = 0  # fresh traces taken BY this instance
        self._cache_loads = 0   # bucket programs AOT-loaded from disk
        self._faulted = False   # replica_drop fired: permanently dead
        self._lock = threading.Lock()
        # per-bucket counters: calls, rows served, pad rows wasted
        self._bucket_calls = {b: 0 for b in self.buckets}
        self._bucket_rows = {b: 0 for b in self.buckets}
        self._bucket_pad_rows = {b: 0 for b in self.buckets}
        _register_predictor(self)

    # -- construction helpers -------------------------------------------------
    @classmethod
    def from_module(cls, module, **kwargs):
        """Freeze a trained (bound+initialized) Module. Data feature
        shapes come from the module's bound data_shapes; params are
        synced from device."""
        arg_params, aux_params = module.get_params()
        kwargs.setdefault("data_names", list(module.data_names))
        kwargs.setdefault("data_shapes", {
            n: tuple(s[1:]) for n, s in module.data_shapes})
        return cls(module.symbol, arg_params, aux_params, **kwargs)

    # -- parameter staging ----------------------------------------------------
    def _stage_value(self, v, want_shape, name):
        """Shape-check one param/aux value and put it on device (cast
        to the compute dtype when configured) — the single staging rule
        __init__ and restage share."""
        import jax
        import jax.numpy as jnp
        a = np.asarray(getattr(v, "_data", getattr(v, "data", v)))
        if tuple(a.shape) != tuple(want_shape):
            raise MXNetError(
                f"Predictor param '{name}' has shape {a.shape}, "
                f"inferred {tuple(want_shape)}")
        x = jnp.asarray(a)
        if self._cdt is not None and x.dtype == jnp.float32:
            x = x.astype(self._cdt)
        return jax.device_put(x)

    def restage(self, arg_params, aux_params=None):
        """Swap in a new checkpoint's parameter values WITHOUT touching
        the compiled programs (the weight-hot-swap primitive,
        ``FleetRouter.swap_weights`` drives it replica-by-replica).

        Parameters are program *arguments* — the program key covers
        shapes/dtypes/passes, never values — so staging new values and
        recomputing the hoisted parameter expressions is the complete
        swap: zero retraces, and the next micro-batch computes exactly
        what a fresh Predictor on the new checkpoint would. Staging and
        hoist evaluation happen OUTSIDE the run lock; the final pointer
        swap takes it, so an in-flight micro-batch finishes on the old
        weights and the swap is atomic per micro-batch."""
        import jax
        aux_params = aux_params or {}
        missing = [n for n in self.param_names
                   if n not in self._zero_args and n not in arg_params]
        if missing:
            raise MXNetError(f"restage missing parameters {missing}")
        for n in self._aux_names:
            if n not in aux_params:
                raise MXNetError(f"restage missing aux state '{n}'")
        new_pvals = {n: self._stage_value(arg_params[n],
                                          self._arg_shape_map[n], n)
                     for n in self.param_names
                     if n not in self._zero_args}
        new_aux = {n: self._stage_value(aux_params[n],
                                        self._aux_shape_map[n], n)
                   for n in self._aux_names}
        if self._hoist_keys:
            from ..symbol.passes import hoist as _hoist
            amap = dict(new_pvals)
            amap.update(new_aux)
            new_hvals = tuple(
                jax.device_put(v) for v in _hoist.hoist_values(
                    self._run_sym, self._hoist_keys, amap))
        else:
            new_hvals = ()
        with self._lock:
            self._pvals = new_pvals
            self._pvals_t = tuple(new_pvals[n]
                                  for n in self._pval_names)
            self._avals = tuple(new_aux[n]
                                for n in self._live_aux_names)
            self._hvals = new_hvals

    # -- bucketing ------------------------------------------------------------
    @property
    def max_batch(self):
        return self.buckets[-1]

    def bucket_for(self, n):
        """Smallest bucket >= n, or the largest bucket (callers chunk)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    @property
    def retraces(self):
        """Number of XLA traces this predictor took (compile-registry
        accounting) — at most one per bucket after warmup, tests pin
        this; ZERO when every bucket program AOT-loaded from a warm
        ``MXTPU_COMPILE_CACHE_DIR``."""
        return self._materialized

    # -- compile registry / AOT cache (compile/ package) ----------------------
    def _program_key(self, bucket, dtypes):
        from .. import compile as compile_mod
        from .. import config as _config
        if not hasattr(self, "_symbol_sha"):
            self._symbol_sha = compile_mod.symbol_digest(self.symbol)
        sigs = tuple(
            (n, (bucket,) + tuple(self.data_shapes[n]), dt)
            for n, dt in zip(self.data_names, dtypes))
        fusion = {"flag": str(_config.get("MXTPU_PALLAS_FUSION")),
                  "sites": len(self.fusion_report["sites"])
                  if self.fusion_report else 0}
        extra = {
            "compute_dtype": str(self._cdt),
            "donate": self._donate,
            "zero_args": sorted(self._zero_args),
            "hoisted": len(self._hvals),
        }
        return compile_mod.program_key(
            "predictor", f"predictor:{self.symbol.name}:b{bucket}",
            symbol_sha=self._symbol_sha, input_sigs=sigs, fusion=fusion,
            passes=self._passes_material, extra=extra)

    def _acquire_program(self, bucket, args):
        """One compiled program per (bucket, request dtypes), acquired
        through the compile registry: a warm persistent cache turns
        warmup's per-bucket compile storm into file loads. The registry
        absorbs its own cache-entry failures; a trace or compile error
        surfaces."""
        from .. import compile as compile_mod
        dtypes = tuple(str(a.dtype) for a in args[1])
        key = self._program_key(bucket, dtypes)
        exe, source = compile_mod.load_or_compile(
            key, lambda: self._infer_jit.lower(*args))
        compile_mod.note_entry_point(
            key.name, key, compile_mod.arg_signature(args[1]))
        self._note_cost(bucket, dtypes, exe)
        if source == "cache":
            self._cache_loads += 1
            jit_fn = self._infer_jit

            def _reject():
                self._programs[(bucket, dtypes)] = jit_fn
                self._materialized += 1
            return compile_mod.guarded_loaded_program(
                exe, jit_fn, "predictor", on_reject=_reject)
        self._materialized += 1
        return exe

    def _note_cost(self, bucket, dtypes, exe):
        """Record XLA cost analysis of an acquired bucket program
        (bytes accessed is the serving-program currency too: the BN
        constant-fold exists to shrink it), and of its memory analysis
        (telemetry.memory — per-bucket HBM next to the cost record).
        Best-effort — some backends/AOT loads expose none."""
        self._program_exes[(bucket, dtypes)] = exe
        try:
            cost = exe.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            self._program_costs[(bucket, dtypes)] = dict(cost) \
                if cost else {}
        except Exception:
            self._program_costs[(bucket, dtypes)] = {}
        try:
            from ..telemetry import memory as _tmem
            self._program_memory[(bucket, dtypes)] = _tmem.analyze(exe)
        except Exception:
            self._program_memory[(bucket, dtypes)] = {}

    def program_cost(self, bucket=None):
        """XLA cost dict of one bucket's compiled program (largest
        bucket by default; {} when not yet materialized or
        unavailable). ``tests/test_passes.py`` pins the BN-folded
        serving program's bytes-accessed strictly below the unfolded
        one through here."""
        b = self.buckets[-1] if bucket is None else bucket
        for (bk, _dt), cost in self._program_costs.items():
            if bk == b and cost:
                return dict(cost)
        return {}

    def program_memory(self, bucket=None):
        """``memory_analysis()`` dict of one bucket's compiled program
        (largest bucket by default; {} when not yet materialized or the
        backend exposes none) — recorded at acquisition, same rule as
        :meth:`program_cost`: never a second compile."""
        b = self.buckets[-1] if bucket is None else bucket
        for (bk, _dt), mem in self._program_memory.items():
            if bk == b and mem:
                return dict(mem)
        return {}

    # -- execution ------------------------------------------------------------
    def _run_bucket(self, arrays, rows, bucket):
        """Pad name-ordered request arrays to ``bucket`` rows and run
        the compiled program. Returns trimmed numpy outputs."""
        import jax.numpy as jnp
        from .. import faultinject
        # ``replica_drop``: the serving-replica loss drill. ``call=N``
        # (or ``replica=<telemetry id>``) picks the victim micro-batch;
        # ``action=kill`` SIGKILLs the process, ``action=sleep:ms=N``
        # stretches the batch (the straggler-replica drill), and a
        # plain raise marks THIS replica permanently dead — an
        # in-process stand-in for a killed replica the FleetRouter must
        # drain and replace without dropping a request.
        if faultinject.fire("replica_drop", replica=self.telemetry_id):
            if (faultinject.active("replica_drop") or
                    {}).get("action") != "sleep":
                self._faulted = True
                raise faultinject.FaultInjected(
                    "replica_drop", replica=self.telemetry_id)
        if self._faulted:
            raise MXNetError(
                f"predictor {self.telemetry_id} is dead (replica_drop)")
        padded = []
        for a in arrays:
            if rows != bucket:
                pad = np.zeros((bucket - rows,) + a.shape[1:], a.dtype)
                a = np.concatenate([a, pad], axis=0)
            padded.append(jnp.asarray(a))
        from ..telemetry import trace as _trace
        with self._lock, _trace.span(
                f"serving:bucket{bucket}", cat="serving",
                args={"predictor": self.telemetry_id, "rows": rows,
                      "pad_rows": bucket - rows}):
            args = (self._pvals_t, tuple(padded), self._avals,
                    self._hvals)
            pkey = (bucket, tuple(str(a.dtype) for a in padded))
            fn = self._programs.get(pkey)
            if fn is None:
                fn = self._acquire_program(bucket, args)
                self._programs[pkey] = fn
            outs = fn(*args)
            self._bucket_calls[bucket] += 1
            self._bucket_rows[bucket] += rows
            self._bucket_pad_rows[bucket] += bucket - rows
        return [np.asarray(o)[:rows] if batched else np.asarray(o)
                for o, batched in zip(outs, self.out_batched)]

    def normalize_request(self, data):
        """Validate one request and return ``(arrays, rows)``: numpy
        arrays ordered by ``data_names``. The single input-contract
        check shared by ``predict`` and ``DynamicBatcher.submit`` —
        the two serving surfaces must reject identically."""
        if not isinstance(data, dict):
            data = {self.data_names[0]: data}
        arrays = []
        for n in self.data_names:
            if n not in data:
                raise MXNetError(f"request missing data input '{n}'")
            a = np.asarray(getattr(data[n], "_data", data[n]))
            if tuple(a.shape[1:]) != self.data_shapes[n]:
                raise MXNetError(
                    f"request input '{n}' rows have shape "
                    f"{tuple(a.shape[1:])}, expected "
                    f"{self.data_shapes[n]}")
            arrays.append(a)
        n_rows = arrays[0].shape[0]
        if n_rows < 1:
            raise MXNetError("got an empty (0-row) request")
        if any(a.shape[0] != n_rows for a in arrays):
            raise MXNetError("request inputs disagree on batch size")
        return arrays, n_rows

    def predict(self, data):
        """Run inference on one request. ``data``: array (single data
        input) or dict name -> array, any leading batch size; oversized
        requests chunk through the largest bucket. Returns one numpy
        array (single output) or a list — same shape contract as
        ``DynamicBatcher.predict``."""
        arrays, n_rows = self.normalize_request(data)
        chunks = []
        start = 0
        while start < n_rows:
            rows = min(n_rows - start, self.max_batch)
            bucket = self.bucket_for(rows)
            chunks.append(self._run_bucket(
                [a[start:start + rows] for a in arrays], rows, bucket))
            start += rows
        if len(chunks) == 1:
            outs = chunks[0]
        else:
            outs = [np.concatenate([c[i] for c in chunks], axis=0)
                    if batched else chunks[0][i]
                    for i, batched in enumerate(self.out_batched)]
        return outs[0] if len(outs) == 1 else outs

    def warmup(self):
        """Materialize every bucket program up front (serving must not
        pay a trace on a live request): AOT-loaded from the persistent
        compile cache when a valid entry exists (``compile::load``
        spans), freshly compiled otherwise (``compile::compile`` spans
        — warmup cost is visible in ``mx.profiler`` dumps either way).
        Returns the retrace (fresh trace) count — 0 on a warm cache."""
        for b in self.buckets:
            arrays = [np.zeros((b,) + self.data_shapes[n], np.float32)
                      for n in self.data_names]
            self._run_bucket(arrays, b, b)
        return self.retraces

    # -- observability --------------------------------------------------------
    def report(self, reset=False):
        with self._lock:
            out = {
                "id": self.telemetry_id,
                "buckets": list(self.buckets),
                "retraces": self._materialized,
                "compile_cache_loads": self._cache_loads,
                "faulted": self._faulted,
                "per_bucket": {
                    b: {"calls": self._bucket_calls[b],
                        "rows": self._bucket_rows[b],
                        "pad_rows": self._bucket_pad_rows[b]}
                    for b in self.buckets},
                "fused_sites": len(self.fusion_report["sites"])
                if self.fusion_report else 0,
                "pass_sites": {
                    e["pass"]: len(e["sites"])
                    for e in (self.pass_report or {}).get("passes", ())
                    if e["status"] == "applied"},
                "bytes_accessed": float(self.program_cost().get(
                    "bytes accessed", 0.0)) or None,
                "compute_dtype": str(self._cdt) if self._cdt else None,
            }
            if reset:
                for b in self.buckets:
                    self._bucket_calls[b] = 0
                    self._bucket_rows[b] = 0
                    self._bucket_pad_rows[b] = 0
        return out
