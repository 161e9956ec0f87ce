"""Fused training step for the symbolic Module path.

The reference's steady-state Module loop is: per-GPU executors run fwd/bwd
(DataParallelExecutorGroup, reference: python/mxnet/module/executor_group.py
:129), gradients reduce through KVStore push/pull, and a Python Updater
applies the optimizer per parameter (module.py:629-651). Here the ENTIRE
batch — forward, implicit-loss backward, cross-device gradient reduction,
optimizer update, BatchNorm aux fold — is ONE donated XLA program per
shape, sharing the graph functions with Executor (executor.build_graph_fns)
and the pure optimizer rules with the gluon TrainStep
(parallel.functional_opt). With a mesh, data/label inputs are sharded over
the 'data' axis and parameters replicated; GSPMD inserts the gradient
all-reduce exactly where the reference's KVStore did.

Small-parameter packing: a ResNet-scale model carries ~160 parameters and
~100 BatchNorm aux states, most of them tiny 1-D vectors. Handled as
individual XLA buffers they fragment the step into thousands of small
copies/converts. All 1-D float32 trainable parameters, their
optimizer states, and all 1-D float32 aux states are therefore packed into
single flat donated buffers; per-name values are static slices inside the
program and the optimizer update over the packed buffer is one fused op
(per-parameter lr_mult/wd_mult become per-element vectors — exact for
every elementwise rule; norm-based rules like LARS disable packing).
"""
from __future__ import annotations

import pickle
import weakref

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..executor import build_graph_fns
from ..parallel import functional_opt
from ..telemetry import trace as _trace

__all__ = ["FusedSymbolStep"]


class FusedSymbolStep:
    """One-XLA-program fwd+bwd+update for a bound Symbol.

    Owns the parameter / optimizer-state / aux buffers between calls
    (donated each step). The Module syncs them back into its executor
    lazily (``sync_to``) when eval/checkpoint paths need them.
    """

    def __init__(self, symbol, data_names, label_names, param_names,
                 aux_names, trainable, optimizer, mesh=None,
                 data_axis="data", compute_dtype=None,
                 partition_rules=None):
        self.symbol = symbol
        self.arg_names = symbol.list_arguments()
        self.aux_names = list(aux_names)
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.param_names = list(param_names)
        self.input_names = [n for n in self.arg_names
                            if n not in set(param_names)]
        self.trainable = dict(trainable)  # param name -> bool
        self.mesh = mesh
        self.data_axis = data_axis
        # regex -> PartitionSpec parameter layout rules (parallel/
        # partition.py): explicit arg wins, else MXTPU_PARTITION_RULES;
        # only consulted on mesh binds
        if partition_rules is None and mesh is not None:
            from ..parallel import partition as _partition
            partition_rules = _partition.env_rules()
        self.partition_rules = partition_rules or []
        # ZeRO-1 sharded update (arXiv:2004.13336): decided at start()
        self._zero = False
        self._zero_ndev = 1
        self._param_specs = None        # per-big-param PartitionSpec
        self._opt_state_specs = None    # per-big-param per-leaf spec
        self._flat_state_specs = None   # per-flat-leaf spec
        self._flat_total = 0            # _small_total padded to ndev
        # bf16 compute with fp32 master params/aux — the fused analog of
        # the optimizer's multi_precision path (reference: optimizer.py
        # create_state_multi_precision :247)
        self.compute_dtype = jnp.dtype(compute_dtype) \
            if compute_dtype is not None else None
        self.optimizer = optimizer
        self._fopt = functional_opt.from_optimizer(optimizer)
        # static per-parameter multipliers (Optimizer._get_lr/_get_wd
        # with idx2name semantics — reference: optimizer.py:411-432)
        self._lr_mults = [optimizer.lr_mult.get(n, 1.0)
                          for n in self.param_names]
        self._wd_eff = [optimizer.wd * optimizer.wd_mult.get(n, 1.0)
                        for n in self.param_names]
        _, self._fwd_loss, _ = build_graph_fns(symbol)
        self.fusion_report = None   # set by start() when the pass runs
        self.pass_report = None     # full pipeline report (passes/)
        self._passes_material = None  # pipeline fingerprint for keys
        # traced graph's variable order (passes may permute it); the
        # step program is fed in this order, buffers stay keyed by the
        # original names
        self._run_arg_names = self.arg_names
        self._run_aux_names = self.aux_names
        from .. import random as _random
        self._base_key = _random.next_key()
        # non-finite step guard (MXTPU_FT_GUARD): NaN/Inf gradients
        # where-select the OLD params/optimizer/aux/metric state inside
        # the compiled program — no retrace, no per-step host sync. The
        # device carries [total_skips, consecutive_skips] (int32[2], NOT
        # donated so lagged host reads stay valid); mx.fault_report()
        # syncs it on demand.
        from .. import config as _config
        self.guard_enabled = str(_config.get("MXTPU_FT_GUARD")).lower() \
            not in ("0", "false", "off")
        self._max_consec = int(_config.get("MXTPU_FT_MAX_CONSEC_SKIPS"))
        self._fault_state = None
        import collections
        self._skip_lag = collections.deque()
        # big params / per-param opt state (aligned with _big_names)
        self._pvals = None
        self._opt_state = None
        self._aux_vals = None          # big aux (aligned _aux_big_names)
        # packed small params / their flat opt state / packed aux
        self._flat_p = None
        self._flat_state = None
        self._flat_aux = None
        # in-step metric counter slots (attach_metric / metric_device.py)
        self._metric_sigs = []          # per-slot structural signature
        self._metric_rules = None       # per-slot (None, ln, pn, fn)
        self._metric_state = None       # per-slot device scalar
        self._metric_owner = []         # per-slot weakref to the metric
        self._metric_detach_epoch = 0   # bumped by detach_metrics
        self._t_dev = None
        self._step_jit = None
        self._program = None    # telemetry.trace's record of the step
        self._programs = {}     # feed signature -> compiled executable
        self._program_costs = {}  # feed signature -> XLA cost dict
        self._program_exes = {}   # feed signature -> raw executable
        self._program_memory = {}  # feed signature -> memory_analysis dict
        self._noted_cost = None   # (timeline weakref, sig) last noted
        self._jit_options = None
        self._lr_cache = None
        self.num_update = 0
        # partition decided at start() from actual value shapes
        self._big_names = None
        self._small_names = None
        self._aux_big_names = None
        self._aux_small_names = None
        # row-sparse embedding routing (sparse/): sites detected at
        # start() on the traced graph; [] = every gradient dense
        self._sparse_sites = []

    @property
    def started(self):
        return self._pvals is not None

    # -- state ----------------------------------------------------------------
    def _rep_sharding(self):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    def _partition(self, arg_dict, aux_dict):
        """Decide which params/aux pack into the flat buffers."""
        packable = (getattr(self._fopt, "elementwise", False)
                    and not self._fopt.needs_key)
        self._small_names, self._big_names = [], []
        for n in self.param_names:
            v = arg_dict[n]._data
            if (packable and v.ndim <= 1 and v.dtype == jnp.float32
                    and self.trainable.get(n, True)):
                self._small_names.append(n)
            else:
                self._big_names.append(n)
        self._aux_small_names, self._aux_big_names = [], []
        for n in self.aux_names:
            v = aux_dict[n]._data
            if v.ndim <= 1 and v.dtype == jnp.float32:
                self._aux_small_names.append(n)
            else:
                self._aux_big_names.append(n)
        # static slice tables
        self._small_off = {}
        off = 0
        for n in self._small_names:
            sz = int(np.prod(arg_dict[n]._data.shape)) \
                if arg_dict[n]._data.ndim else 1
            self._small_off[n] = (off, sz, tuple(arg_dict[n]._data.shape))
            off += sz
        self._small_total = off
        # ZeRO-1: the packed buffer pads to a multiple of the replica
        # count so every device owns an equal contiguous optimizer-state
        # shard. Padding is inert under every elementwise rule: p=0,
        # g=0 (no loss term reaches it), lr_mult=1, wd=0 keep the pad
        # exactly zero forever
        self._flat_total = off + ((-off) % self._zero_ndev
                                  if self._zero_ndev > 1 else 0)
        self._aux_off = {}
        off = 0
        for n in self._aux_small_names:
            sz = int(np.prod(aux_dict[n]._data.shape)) \
                if aux_dict[n]._data.ndim else 1
            self._aux_off[n] = (off, sz, tuple(aux_dict[n]._data.shape))
            off += sz
        self._aux_total = off
        # per-element lr/wd multiplier vectors for the packed update
        # (sized to the PADDED total: pad lr_mult=1 / wd=0)
        if self._small_total:
            lrm = np.ones(self._flat_total, np.float32)
            wdv = np.zeros(self._flat_total, np.float32)
            pidx = {n: i for i, n in enumerate(self.param_names)}
            for n, (o, sz, _) in self._small_off.items():
                lrm[o:o + sz] = self._lr_mults[pidx[n]]
                wdv[o:o + sz] = self._wd_eff[pidx[n]]
            self._flat_lrm = jnp.asarray(lrm)
            self._flat_wd = jnp.asarray(wdv)

    def start(self, arg_dict, aux_dict):
        """Capture initial parameter/aux values (copies — our buffers get
        donated, the executor's must stay live for eval paths)."""
        # Graph-rewrite pass pipeline (symbol/passes/): the whole-step
        # program traces the rewritten graph; self.symbol stays
        # authoritative for names. Deferred to start() because
        # applicability bail-outs need the bound array shapes. Mesh
        # (multi-chip) steps no longer skip silently: mesh-safe passes
        # run, the rest count into passes::skipped ("mesh_bind").
        from ..symbol import passes as _passes
        shapes = {n: tuple(d[n].shape)
                  for d in (arg_dict, aux_dict) for n in d}
        fused_sym, self.pass_report = _passes.apply_pipeline(
            self.symbol, shapes, tag="fused_step", mode="train",
            mesh=self.mesh, compute_dtype=self.compute_dtype,
            batch_names=set(self.data_names) | set(self.label_names),
            data_axis=self.data_axis)
        self.fusion_report = _passes.legacy_fusion_entry(
            self.pass_report)
        self._passes_material = _passes.pipeline_key_material(
            self.pass_report)
        if fused_sym is not None:
            _, self._fwd_loss, _ = build_graph_fns(fused_sym)
            self._run_arg_names = fused_sym.list_arguments()
            self._run_aux_names = fused_sym.list_auxiliary_states()
        # row-sparse embedding routing: SparseEmbedding nodes whose ids
        # are a direct feed and whose table is a trainable parameter get
        # rows-only gradients (perturbation trick in _build) + the lazy
        # row optimizer rule — the dense (vocab, dim) cotangent is never
        # materialized. Detection runs on the TRACED graph (node ids key
        # the eval preset). No lazy rule for this optimizer -> every
        # site falls back to the dense custom-VJP path, counted.
        run_sym = fused_sym if fused_sym is not None else self.symbol
        self._sparse_sites = []
        from ..sparse.embedding import find_sites as _find_sites
        from ..telemetry import registry as _treg
        tied = []
        all_sites = _find_sites(run_sym, self.param_names,
                                self.input_names, shapes,
                                fallbacks=tied)
        if tied:
            # tables with a non-site consumer (tied weights): routing
            # them row-sparse would drop the other consumer's gradient,
            # so they stay on the dense custom-VJP path, counted
            _treg.counter("sparse::dense_fallback").inc(len(tied))
        if all_sites and self._fopt.row_update is None:
            _treg.counter("sparse::dense_fallback").inc(len(all_sites))
        elif all_sites:
            self._sparse_sites = [
                s for s in all_sites
                if self.trainable.get(s.weight_name, True)]
            _treg.gauge("sparse::sites").set(len(self._sparse_sites))
        rep = self._rep_sharding()

        def _prep(v):
            v = jnp.array(v, copy=True)
            return jax.device_put(v, rep) if rep is not None else v

        # ZeRO-1 sharded update (MXTPU_ZERO, arXiv:2004.13336): each
        # replica owns 1/N of the optimizer state and updates only its
        # shard; GSPMD all-gathers the fresh params. Needs an
        # elementwise, key-free rule (a norm-based rule like LARS reads
        # the whole tensor) and >1 device on the data axis.
        from .. import config as _config
        ndev = int(self.mesh.shape.get(self.data_axis, 0)) \
            if self.mesh is not None else 0
        eligible = (ndev > 1
                    and getattr(self._fopt, "elementwise", False)
                    and not self._fopt.needs_key)
        zmode = str(_config.get("MXTPU_ZERO", "auto")).strip().lower()
        if zmode in ("0", "false", "off", "no"):
            self._zero = False
        else:
            self._zero = eligible
            if zmode in ("1", "true", "on", "yes") and not eligible \
                    and ndev > 1:
                import logging
                logging.getLogger("mxnet_tpu.module").warning(
                    "MXTPU_ZERO=1 but optimizer '%s' is not an "
                    "elementwise key-free rule; running the replicated "
                    "update", type(self.optimizer).__name__)
        self._zero_ndev = ndev if self._zero else 1
        self._partition(arg_dict, aux_dict)
        from jax.sharding import NamedSharding, PartitionSpec as P

        # regex partition rules decide each big param's layout (TP);
        # unruled params replicate. Rule-sharded params are excluded
        # from ZeRO (their optimizer state already follows the param's
        # partitioning below).
        rules = self.partition_rules if self.mesh is not None else []
        sparse_names = {s.weight_name for s in self._sparse_sites}
        self._param_specs = []
        for n in self._big_names:
            spec = P()
            if rules:
                from ..parallel import partition as _part
                v = arg_dict[n]._data
                spec = _part.spec_for(rules, n, ndim=v.ndim)
                _part.validate_specs(self.mesh, {n: spec},
                                     {n: tuple(v.shape)})
            self._param_specs.append(spec)
        # per-big-param ZeRO eligibility: trainable, dense-grad (sparse
        # tables take the lazy row update), replicated layout, and dim0
        # divisible by the replica count
        self._zero_big = []
        for n, spec in zip(self._big_names, self._param_specs):
            v = arg_dict[n]._data
            self._zero_big.append(bool(
                self._zero and self.trainable.get(n, True)
                and n not in sparse_names and tuple(spec) == ()
                and v.ndim >= 1 and v.shape[0] % ndev == 0
                and v.shape[0] >= ndev))

        def _put(v, spec):
            if self.mesh is None:
                return v
            return jax.device_put(v, NamedSharding(self.mesh, spec))

        self._pvals = tuple(
            _put(jnp.array(arg_dict[n]._data, copy=True), spec)
            for n, spec in zip(self._big_names, self._param_specs))
        self._aux_vals = tuple(_prep(aux_dict[n]._data)
                               for n in self._aux_big_names)

        def _leaf_spec(leaf, pshape, pspec, zero):
            shp = tuple(getattr(leaf, "shape", ()))
            if shp != tuple(pshape) or not shp:
                return P()      # scalar schedule leaves replicate
            if zero:
                return P(self.data_axis)   # ZeRO shard over dim0
            return pspec        # TP state follows the param layout

        opt_state, opt_specs = [], []
        for n, v, pspec, zb in zip(self._big_names, self._pvals,
                                   self._param_specs, self._zero_big):
            if not self.trainable.get(n, True):
                opt_state.append(())
                opt_specs.append(())
                continue
            leaves = self._fopt.init(v)
            specs = tuple(_leaf_spec(x, v.shape, pspec, zb)
                          for x in leaves)
            opt_state.append(tuple(_put(x, s)
                                   for x, s in zip(leaves, specs)))
            opt_specs.append(specs)
        self._opt_state = tuple(opt_state)
        self._opt_state_specs = tuple(opt_specs)
        self._flat_p = _prep(self._pack_params(arg_dict)) \
            if self._small_total else None
        self._flat_aux = _prep(self._pack_aux(aux_dict)) \
            if self._aux_total else None
        if self._small_total:
            leaves = self._fopt.init(self._flat_p)
            self._flat_state_specs = tuple(
                _leaf_spec(x, (self._flat_total,), P(), self._zero)
                for x in leaves)
            self._flat_state = tuple(
                _put(x, s) for x, s
                in zip(leaves, self._flat_state_specs))
        else:
            self._flat_state = ()
            self._flat_state_specs = ()
        if self.mesh is not None:
            from ..telemetry import registry as _treg2
            om = self.optimizer_memory()
            _treg2.gauge("mem::optimizer::logical_bytes").set(
                om["logical_bytes"])
            _treg2.gauge("mem::optimizer::per_device_bytes").set(
                om["per_device_bytes"])
        t0 = jnp.zeros((), jnp.uint32)
        self._t_dev = jax.device_put(t0, rep) if rep is not None else t0
        f0 = jnp.zeros((2,), jnp.int32)
        self._fault_state = jax.device_put(f0, rep) if rep is not None \
            else f0
        self._skip_lag.clear()
        from .. import fault as _fault
        _fault.register_guard(self)

    def _pack_params(self, arg_dict):
        vals = [np.asarray(arg_dict[n]._data).ravel()
                for n in self._small_names]
        pad = self._flat_total - self._small_total
        if pad:
            vals.append(np.zeros(pad, np.float32))
        return jnp.asarray(np.concatenate(vals).astype(np.float32))

    def _pack_aux(self, aux_dict):
        vals = [np.asarray(aux_dict[n]._data).ravel()
                for n in self._aux_small_names]
        return jnp.asarray(np.concatenate(vals).astype(np.float32))

    def _build(self):
        fwd_loss = self._fwd_loss
        fopt = self._fopt
        arg_names = self._run_arg_names   # traced graph's order
        big_pos = {n: i for i, n in enumerate(self._big_names)}
        small_off = self._small_off
        aux_big_pos = {n: i for i, n in enumerate(self._aux_big_names)}
        aux_off = self._aux_off
        input_pos = {n: i for i, n in enumerate(self.input_names)}
        trainable = [self.trainable.get(n, True) for n in self._big_names]
        pidx = {n: i for i, n in enumerate(self.param_names)}
        lr_mults = [self._lr_mults[pidx[n]] for n in self._big_names]
        wd_eff = [self._wd_eff[pidx[n]] for n in self._big_names]
        aux_names = self._run_aux_names   # traced graph's order
        has_flat = self._small_total > 0
        has_flat_aux = self._aux_total > 0
        flat_lrm = self._flat_lrm if has_flat else None
        flat_wd = self._flat_wd if has_flat else None
        # row-sparse embedding routing: tables backing a detected site
        # leave the differentiated param set — their gradient is taken
        # wrt a zero PERTURBATION of the gathered rows instead, then
        # deduplicated to unique rows (sparse/rowsparse.py). The dense
        # (vocab, dim) cotangent never exists in the program.
        from ..sparse.rowsparse import RowSparseRows, dedup_rows
        sites = [s for s in self._sparse_sites
                 if s.weight_name in big_pos]
        site_big_idx = [big_pos[s.weight_name] for s in sites]
        sparse_set = set(site_big_idx)
        dense_idx = [i for i in range(len(self._big_names))
                     if i not in sparse_set]
        dense_pos = {i: j for j, i in enumerate(dense_idx)}

        cdt = self.compute_dtype

        def _cast(v):
            if cdt is None or v.dtype != jnp.float32:
                return v
            with jax.named_scope("mx_cast"):
                return v.astype(cdt)

        metric_rules = self._metric_rules or []
        out_names = self.symbol.list_outputs()
        guard = self.guard_enabled

        # ZeRO-1 (arXiv:2004.13336): each replica updates a contiguous
        # 1/N shard of the eligible params with its LOCAL optimizer-
        # state shard; the param out_sharding (replicated) makes GSPMD
        # all-gather the fresh values — reduce-scatter(g) + local
        # update + all-gather(p), bit-identical to the replicated
        # update because every rule involved is elementwise (an
        # elementwise update of a slice IS the slice of the elementwise
        # update).
        mesh = self.mesh
        axis = self.data_axis
        ndev = self._zero_ndev
        zero_big = list(self._zero_big or ())
        zero_big += [False] * (len(self._big_names) - len(zero_big))
        zero_flat = self._zero and has_flat
        opt_specs = self._opt_state_specs or ()
        flat_specs = self._flat_state_specs or ()

        if zero_flat or any(zero_big):
            from jax.sharding import PartitionSpec as _P
            from jax import shard_map as _shard_map

            def _zero_update(p, g, s, s_specs, lr, t, lrm, wd):
                """One sharded optimizer step. ``lrm``/``wd`` are the
                per-element vectors of the packed buffer or plain
                python multipliers of a big param — both concrete, so
                closing over them is safe (lr/t are TRACERS and must
                ride in as shard_map arguments)."""
                rows = p.shape[0] // ndev
                vec = hasattr(lrm, "ndim")

                def body(p, g, lr, t, *sl):
                    i0 = jax.lax.axis_index(axis) * rows
                    pl = jax.lax.dynamic_slice_in_dim(p, i0, rows, 0)
                    gl = jax.lax.dynamic_slice_in_dim(g, i0, rows, 0)
                    if vec:
                        lr_l = lr * jax.lax.dynamic_slice_in_dim(
                            lrm, i0, rows, 0)
                        wd_l = jax.lax.dynamic_slice_in_dim(
                            wd, i0, rows, 0)
                    else:
                        lr_l, wd_l = lr * lrm, wd
                    np_, ns_ = fopt.update(pl, gl, tuple(sl), lr_l,
                                           t + 1, wd_l)
                    return (np_,) + tuple(ns_)

                res = _shard_map(
                    body, mesh=mesh,
                    in_specs=(_P(), _P(), _P(), _P()) + tuple(s_specs),
                    out_specs=(_P(axis),) + tuple(s_specs),
                    check_vma=False)(p, g, lr, t, *s)
                return res[0], tuple(res[1:])

        # base_key is a runtime ARGUMENT, not a closure constant: baked
        # into the executable it would make every process's programs
        # unique (next_key() differs per run) and the persistent compile
        # cache could never hit across restarts
        def mx_fused_step(pvals, opt_state, flat_p, flat_state, aux_vals,
                          flat_aux, mstate, fstate, feed_vals, t, lr,
                          base_key):
            key = jax.random.fold_in(base_key, t)

            # zero perturbations of each site's gathered rows: the
            # gradient wrt them IS the gradient wrt the gathered
            # activations, which dedup_rows turns into rows-only form
            perts = tuple(
                jnp.zeros(feed_vals[input_pos[s.ids_name]].shape
                          + (s.dim,), jnp.float32) for s in sites)

            def floss(pv_dense, fp, pert):
                def val(n):
                    if n in big_pos:
                        i = big_pos[n]
                        if i in dense_pos:
                            return _cast(pv_dense[dense_pos[i]])
                        # sparse table: reaches the loss only through
                        # the preset gather below — no dense cotangent
                        return _cast(pvals[i])
                    if n in small_off:
                        o, sz, shp = small_off[n]
                        return _cast(jax.lax.slice(fp, (o,), (o + sz,))
                                     .reshape(shp))
                    return _cast(feed_vals[input_pos[n]])

                arg_vals = tuple(val(n) for n in arg_names)

                def aux_val(n):
                    if n in aux_big_pos:
                        return _cast(aux_vals[aux_big_pos[n]])
                    o, sz, shp = aux_off[n]
                    return _cast(jax.lax.slice(flat_aux, (o,), (o + sz,))
                                 .reshape(shp))

                aux_in = tuple(aux_val(n) for n in aux_names)
                preset = None
                if sites:
                    preset = {}
                    for k, s in enumerate(sites):
                        w = pvals[site_big_idx[k]].astype(jnp.float32)
                        ids = feed_vals[input_pos[s.ids_name]] \
                            .astype(jnp.int32)
                        preset[(id(s.node), 0)] = _cast(
                            jnp.take(w, ids, axis=0) + pert[k])
                total, (outs, aux_up) = fwd_loss(arg_vals, aux_in, None,
                                                 key, preset=preset)
                return total, (outs, aux_up)

            pv_dense = tuple(pvals[i] for i in dense_idx)
            argnums = (0, 1, 2) if has_flat else (0, 2)
            grads, (outs, aux_up) = jax.grad(
                floss, argnums=argnums, has_aux=True)(
                    pv_dense, flat_p, perts)
            if has_flat:
                gd, grad_flat, gperts = grads
            else:
                gd, gperts = grads
                grad_flat = None
            grads_big = [None] * len(pvals)
            for j, i in enumerate(dense_idx):
                grads_big[i] = gd[j]
            if sites:
                # merge sites sharing one table, then ONE dedup per
                # table: unique sorted ids + segment-summed rows
                merged = {}
                for k, s in enumerate(sites):
                    ids = feed_vals[input_pos[s.ids_name]] \
                        .astype(jnp.int32).reshape(-1)
                    dg = gperts[k].reshape(ids.shape[0], s.dim) \
                        .astype(jnp.float32)
                    merged.setdefault(site_big_idx[k], []) \
                        .append((ids, dg, s.vocab))
                for i, parts in merged.items():
                    ids = jnp.concatenate([x[0] for x in parts])
                    dg = jnp.concatenate([x[1] for x in parts])
                    grads_big[i] = dedup_rows(ids, dg,
                                              num_rows=parts[0][2])
            def _apply():
                """The real update: optimizer step + BN aux fold +
                in-step metric advance."""
                new_p, new_s = [], []
                for i, (p, g, s, tr) in enumerate(
                        zip(pvals, grads_big, opt_state, trainable)):
                    if tr:
                        with jax.named_scope("mx_opt_update"):
                            if isinstance(g, RowSparseRows):
                                # lazy rows-only update: momentum/moments
                                # and weight decay advance on touch only
                                np_, ns_ = fopt.row_update(
                                    p, g.ids, g.rows, s, lr * lr_mults[i],
                                    t + 1, wd_eff[i])
                            elif zero_big[i]:
                                np_, ns_ = _zero_update(
                                    p, g, s, opt_specs[i], lr, t,
                                    lr_mults[i], wd_eff[i])
                            else:
                                pkey = jax.random.fold_in(
                                    jax.random.fold_in(key, 0x6F707469),
                                    i) if fopt.needs_key else None
                                np_, ns_ = fopt.update(
                                    p, g, s, lr * lr_mults[i],
                                    t + 1, wd_eff[i], key=pkey)
                            new_p.append(np_.astype(p.dtype))
                        new_s.append(ns_)
                    else:
                        new_p.append(p)
                        new_s.append(s)
                if has_flat:
                    with jax.named_scope("mx_opt_update"):
                        if zero_flat:
                            nf, nfs = _zero_update(
                                flat_p, grad_flat, flat_state, flat_specs,
                                lr, t, flat_lrm, flat_wd)
                        else:
                            nf, nfs = fopt.update(
                                flat_p, grad_flat, flat_state,
                                lr * flat_lrm, t + 1, flat_wd)
                        new_flat = nf.astype(jnp.float32)
                    new_flat_s = nfs
                else:
                    new_flat, new_flat_s = flat_p, flat_state
                new_aux_big = tuple(
                    aux_up.get(n, a).astype(a.dtype)
                    for n, a in zip(self._aux_big_names, aux_vals))
                if has_flat_aux:
                    pieces = []
                    for n in self._aux_small_names:
                        o, sz, shp = aux_off[n]
                        cur = jax.lax.slice(flat_aux, (o,), (o + sz,))
                        up = aux_up.get(n)
                        pieces.append(
                            up.reshape(sz).astype(jnp.float32)
                            if up is not None else cur)
                    new_flat_aux = jnp.concatenate(pieces) if pieces \
                        else flat_aux
                else:
                    new_flat_aux = flat_aux
                # in-step metric counters (metric_device.py): one device
                # scalar per attached metric, advanced inside THIS
                # program so update_metric never adds a dispatch or sync
                if metric_rules:
                    pred_map = dict(zip(out_names, outs))
                    label_map = {n: feed_vals[input_pos[n]]
                                 for n in self.input_names}
                    with jax.named_scope("mx_metric"):
                        new_m = tuple(
                            fn(s, [label_map[n] for n in lnames],
                               [pred_map[n] for n in pnames])
                            for (init, lnames, pnames, fn), s
                            in zip(metric_rules, mstate))
                else:
                    new_m = mstate
                return (tuple(new_p), tuple(new_s), new_flat, new_flat_s,
                        new_aux_big, new_flat_aux, new_m)

            if guard:
                # non-finite step guard: ONE scalar grad-norm across
                # every gradient (|g| sums propagate any NaN/Inf; an
                # fp32 overflow of the norm itself is a gradient
                # explosion — skipping is the right call there too).
                # lax.cond selects the pre-step state wholesale: params,
                # optimizer state, aux AND metric counters are
                # bit-identical after a skipped step, and the skip
                # branch costs nothing on clean steps (measured ~40%
                # cheaper than per-leaf where-selects on the CPU proxy).
                gnorm = jnp.float32(0)
                for g in list(grads_big) + \
                        ([grad_flat] if has_flat else []):
                    if isinstance(g, RowSparseRows):
                        g = g.rows      # sentinel rows are exact zeros
                    gnorm = gnorm + jnp.sum(jnp.abs(g),
                                            dtype=jnp.float32)
                finite = jnp.isfinite(gnorm)
                (new_p, new_s, new_flat, new_flat_s, new_aux_big,
                 new_flat_aux, new_m) = jax.lax.cond(
                    finite, _apply,
                    lambda: (tuple(pvals), tuple(opt_state), flat_p,
                             flat_state, tuple(aux_vals), flat_aux,
                             mstate))
                skipped = jnp.logical_not(finite).astype(jnp.int32)
                # [total skips, consecutive skips]
                fstate = jnp.stack([fstate[0] + skipped,
                                    (fstate[1] + 1) * skipped])
            else:
                (new_p, new_s, new_flat, new_flat_s, new_aux_big,
                 new_flat_aux, new_m) = _apply()
            return (new_p, new_s, new_flat, new_flat_s,
                    new_aux_big, new_flat_aux, new_m, fstate,
                    tuple(outs), t + 1)

        # fstate (arg 7) is deliberately NOT donated: the lagged
        # consecutive-skip abort check and fault_report() read old
        # fstate buffers after later steps have dispatched
        donate = (0, 1, 2, 3, 4, 5, 6, 9)
        # backend compiler options (reference analog: the MXNET_* perf env
        # layer, docs/faq/env_var.md): MXNET_TPU_XLA_OPTIONS="k=v,k2=v2"
        import os
        jit_kw = {}
        opts = os.environ.get("MXNET_TPU_XLA_OPTIONS")
        if opts:
            jit_kw["compiler_options"] = dict(
                kv.split("=", 1) for kv in opts.split(",") if "=" in kv)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(self.mesh, P())
            batched = NamedSharding(self.mesh, P(self.data_axis))
            shard_inputs = set(self.data_names) | set(self.label_names)
            feed_sh = tuple(batched if n in shard_inputs else rep
                            for n in self.input_names)
            # params follow their partition rule (replicated without
            # one); optimizer state follows the specs recorded at
            # start() — ZeRO shards P(data) over dim0, scalar schedule
            # leaves replicate. in == out keeps donation zero-copy.
            prep = tuple(NamedSharding(self.mesh, s)
                         for s in (self._param_specs
                                   or [P()] * len(self._big_names)))
            srep = tuple(
                tuple(NamedSharding(self.mesh, s) for s in specs)
                for specs in (self._opt_state_specs
                              or [()] * len(self._opt_state)))
            frep = rep if self._flat_p is not None else None
            fsrep = tuple(NamedSharding(self.mesh, s)
                          for s in (self._flat_state_specs or ()))
            farep = rep if self._flat_aux is not None else None
            arep = tuple(rep for _ in self._aux_big_names)
            mrep = tuple(rep for _ in (self._metric_state or ()))
            in_shardings = (prep, srep, frep, fsrep, arep, farep, mrep,
                            rep, feed_sh, rep, rep, rep)
            # pin state outputs to their input layout (keeps donation
            # zero-copy); leave graph outputs (None) to GSPMD
            out_shardings = (prep, srep, frep, fsrep, arep, farep, mrep,
                             rep, None, rep)
            self._step_jit = jax.jit(mx_fused_step, donate_argnums=donate,
                                     in_shardings=in_shardings,
                                     out_shardings=out_shardings,
                                     **jit_kw)
        else:
            self._step_jit = jax.jit(mx_fused_step, donate_argnums=donate,
                                     **jit_kw)
        self._jit_options = jit_kw.get("compiler_options")
        # compiled-program cache per feed signature: the jit above is
        # only ever LOWERED — actual executables are acquired through
        # the compile registry (AOT load-or-compile, compile/ package).
        # The recorded costs die with the programs: a rebuilt step (new
        # metric slots, new guard config) has a different bytes budget,
        # and cost_analysis()/the step gauges must never answer from
        # the old program's numbers
        self._programs = {}
        self._program_costs = {}
        self._program_exes = {}
        self._program_memory = {}
        self._noted_cost = None

    def staging_sharding(self):
        """Sharding for batch inputs (data + labels), for the host data
        pipeline's stager: batches staged with THIS sharding make
        step()'s own device_put a no-op, so the transfer fully overlaps
        the previous step instead of landing on the dispatch path.
        A single-device bind names the device its state lives on: a
        bare ``jax.device_put`` leaves an array that is committed to
        another backend (a host-resident batch under a TPU bind) where
        it is, and the compiled step then copies it inside every
        dispatch (PERF.md section 6, PR 26)."""
        if self.mesh is None:
            leaves = jax.tree_util.tree_leaves(self._state_args())
            return leaves[0].sharding if leaves else None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(self.data_axis))

    # -- run ------------------------------------------------------------------
    def _state_args(self):
        return (self._pvals, self._opt_state, self._flat_p,
                self._flat_state, self._aux_vals, self._flat_aux,
                self._metric_state or (), self._fault_state)

    # -- in-step metrics (metric_device.py) ------------------------------------
    def attach_metric(self, metric, sig, init, lnames, pnames, fn):
        """Claim an in-step counter slot for ``metric``: one device
        scalar advanced by ``fn`` inside the step program. A slot whose
        previous owner died (or is this very metric) and whose
        structural signature matches is REUSED — no retrace, counter
        reset to ``init``; otherwise a new slot appends and the step
        retraces once. Returns the slot index."""
        import weakref
        rep = self._rep_sharding()
        dinit = jax.device_put(init, rep) if rep is not None \
            else jnp.asarray(init)
        if self._metric_rules is None:
            self._metric_rules = []
            self._metric_state = ()
        for i, s in enumerate(self._metric_sigs):
            owner = self._metric_owner[i]
            o = owner() if owner is not None else None
            if s == sig and (o is None or o is metric):
                self._metric_owner[i] = weakref.ref(metric)
                self._metric_state = tuple(
                    dinit if j == i else v
                    for j, v in enumerate(self._metric_state))
                return i
        idx = len(self._metric_sigs)
        self._metric_sigs.append(sig)
        self._metric_rules.append((None, lnames, pnames, fn))
        self._metric_state = self._metric_state + (dinit,)
        self._metric_owner.append(weakref.ref(metric))
        self._step_jit = None              # retrace with the new slot
        return idx

    def live_metrics(self):
        """Currently-owned attached metric objects (for flush hooks)."""
        out = []
        for wr in self._metric_owner:
            m = wr() if wr is not None else None
            if m is not None:
                out.append(m)
        return out

    def detach_metrics(self):
        """Drop every in-step metric rule (executor reshape — shape
        templates and per-step instance counts would go stale).
        metric_device flushes live refs first."""
        if self._metric_rules:
            self._metric_sigs = []
            self._metric_rules = None
            self._metric_state = None
            self._metric_owner = []
            self._metric_detach_epoch += 1
            self._step_jit = None

    def release_metric_slot(self, idx):
        """Disown one slot (metric fell back to the sync path); the rule
        keeps running (retrace-free) until the slot is reused."""
        if idx < len(self._metric_owner):
            self._metric_owner[idx] = None

    def reset_metric_state(self, idx):
        if self._metric_state is None:
            return
        rep = self._rep_sharding()
        z = jnp.zeros_like(self._metric_state[idx])
        if rep is not None:
            z = jax.device_put(np.zeros(self._metric_state[idx].shape,
                                        self._metric_state[idx].dtype),
                               rep)
        self._metric_state = tuple(
            z if i == idx else s
            for i, s in enumerate(self._metric_state))

    def step(self, feed, lr):
        """Run one fused step. ``feed``: dict name -> jnp array for every
        input (data + label [+ states]); ``lr``: host scalar base learning
        rate (schedule already applied). Returns the graph outputs."""
        if self._step_jit is None:
            self._build()
        from .. import faultinject
        # deterministic straggler drill: 'slow_step:action=sleep:ms=N'
        # stretches every step by N ms — armed in ONE rank's environment
        # it is the injected skew the fleet telemetry aggregator
        # (tools/telemetry.py fleet) must flag
        faultinject.fire("slow_step", step=self.num_update)
        if self._sparse_sites:
            # the kill-mid-row-scatter drill: with action=kill the
            # process dies at the step boundary where the row update
            # would commit — the chaos suite proves resume restores
            # table + lazy optimizer state bit-for-bit from the last
            # checkpoint (a mid-program death can't tear donated
            # buffers; the step is atomic from the host's view)
            faultinject.fire("sparse_update", step=self.num_update)
            from .. import sparse as _sparse
            if _sparse.stats_enabled():
                _sparse.note_step_ids(self._sparse_sites, feed)
        if faultinject.fire("nan_grad", step=self.num_update):
            # poison the float data inputs: the SAME compiled program
            # produces NaN gradients, exercising the in-graph guard with
            # zero retrace (the guard is data-driven, not trace-driven)
            feed = dict(feed)
            for n in self.data_names:
                v = jnp.asarray(feed[n])
                if jnp.issubdtype(v.dtype, jnp.floating):
                    feed[n] = v * jnp.nan
        # step-time attribution (telemetry/timeline.py): the phases
        # below nest inside fit()'s outer device_step span, so their
        # time is attributed here and subtracted there — no double
        # counting, and the step costs two attribute reads when no
        # timeline is active
        from ..telemetry import timeline as _tlmod
        tl = _tlmod.current()
        feed_vals = []
        shard_inputs = set(self.data_names) | set(self.label_names)
        with tl.phase("h2d_stage") if tl else _tlmod.null_phase():
            for n in self.input_names:
                if n not in feed:
                    raise MXNetError(f"fused step missing input '{n}'")
                v = feed[n]
                if self.mesh is not None:
                    from jax.sharding import NamedSharding, \
                        PartitionSpec as P
                    spec = P(self.data_axis) if n in shard_inputs else P()
                    v = jax.device_put(v, NamedSharding(self.mesh, spec))
                feed_vals.append(v)
        if self._lr_cache is None or self._lr_cache[0] != lr:
            lr_dev = jnp.asarray(lr, jnp.float32)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                lr_dev = jax.device_put(
                    lr_dev, NamedSharding(self.mesh, P()))
            self._lr_cache = (lr, lr_dev)
        args = self._state_args() + (tuple(feed_vals), self._t_dev,
                                     self._lr_cache[1], self._base_key)
        sig = tuple((tuple(v.shape), str(v.dtype)) for v in feed_vals)
        prog = self._programs.get(sig)
        if prog is None:
            with tl.phase("compile") if tl else _tlmod.null_phase():
                prog = self._acquire_program(sig, args)
            self._programs[sig] = prog
        if tl is not None:
            # the cost only changes with the program — note it once per
            # (timeline, sig), not with per-step gauge writes under the
            # registry lock on the hottest loop
            noted = self._noted_cost
            if noted is None or noted[0]() is not tl or noted[1] != sig:
                cost = self._program_costs.get(sig)
                if cost:
                    tl.note_cost(flops=cost.get("flops"),
                                 bytes_accessed=cost.get("bytes accessed"))
                    self._noted_cost = (weakref.ref(tl), sig)
        with tl.phase("dispatch") if tl else _tlmod.null_phase():
            # on the host this is the enqueue of the compiled program,
            # inside fit()'s device_step; mesh scope so a plain-jit
            # fallback tracing HERE still
            # shard_maps the fused kernels (no-op when already compiled
            # or off-mesh)
            from ..ops.pallas_fused import mesh_scope
            with mesh_scope(self.mesh, self.data_axis):
                (self._pvals, self._opt_state, self._flat_p,
                 self._flat_state, self._aux_vals, self._flat_aux,
                 self._metric_state, self._fault_state, outs,
                 self._t_dev) = prog(*args)
        self.num_update += 1
        with tl.phase("metric_ft_sync") if tl else _tlmod.null_phase():
            self._check_abort()
        return outs

    # -- compile registry / AOT cache (compile/ package) ----------------------
    def _program_key(self, sig):
        """Canonical cache key for the step program at one feed
        signature. Everything that feeds the trace is material: graph,
        shapes, optimizer hyperparameters (baked as constants),
        mesh/sharding, fusion flag + site count, the FT guard, compute
        dtype, attached metric slots, and compiler options."""
        from .. import compile as compile_mod
        from .. import config as _config
        if not hasattr(self, "_symbol_sha"):
            self._symbol_sha = compile_mod.symbol_digest(self.symbol)
        fusion = {"flag": str(_config.get("MXTPU_PALLAS_FUSION")),
                  "sites": len(self.fusion_report["sites"])
                  if self.fusion_report else 0}
        extra = {
            "guard": bool(self.guard_enabled),
            "compute_dtype": str(self.compute_dtype),
            "data_axis": self.data_axis,
            "trainable": sorted((n, bool(v))
                                for n, v in self.trainable.items()),
            "metrics": repr(tuple(self._metric_sigs)),
            "compiler_options": self._jit_options,
            # sparse routing config: which sites carry row-sparse
            # gradients (and their vocab/dim) changes the traced
            # program — a dense-vs-sparse flip must never cache-hit
            "sparse": [s.describe() for s in self._sparse_sites],
            # sharded-update regime: a ZeRO step and a replicated step
            # are different programs over identical shapes
            "zero": int(self._zero_ndev) if self._zero else 0,
        }
        from ..parallel import partition as _part
        return compile_mod.program_key(
            "fused_step", f"fused_step:{self.symbol.name}",
            symbol_sha=self._symbol_sha, input_sigs=sig,
            optimizer=self.optimizer, mesh=self.mesh, fusion=fusion,
            passes=self._passes_material,
            partition=_part.rules_fingerprint(self.partition_rules),
            extra=extra)

    def _acquire_program(self, sig, args):
        """Route one compile through the registry: AOT-load from the
        persistent cache when a valid entry exists (zero fresh XLA
        compiles on a warm restart), else trace+compile inside a
        ``compile::compile`` span and serialize back. The registry
        absorbs its own cache-entry and serialization failures; an
        error raised here is the trace's or the compiler's, and it
        surfaces."""
        from .. import compile as compile_mod
        from ..ops.pallas_fused import mesh_scope

        def _lower():
            # the fused Pallas ops read the ambient mesh scope at trace
            # time to wrap themselves in shard_map (round 18)
            with mesh_scope(self.mesh, self.data_axis):
                return self._step_jit.lower(*args)

        key = self._program_key(sig)
        exe, source = compile_mod.load_or_compile(key, _lower)
        compile_mod.note_entry_point(key.name, key, sig)
        self._note_cost(sig, exe)
        self._note_program(source, exe, _trace.shapes_of(args))
        if source != "cache":
            return exe
        jit_fn = self._step_jit
        return compile_mod.guarded_loaded_program(
            exe, jit_fn, "fused step",
            on_reject=lambda: self._programs.__setitem__(sig, jit_fn))

    def _note_program(self, source, exe, shapes):
        """The one reference :meth:`scope_table` is built from when
        somebody asks: the executable, and the traced program. After a
        fresh compile the jit finds the trace it just took; an
        executable loaded from an AOT entry was never traced in this
        process, so that trace is taken only when the table is asked
        for, and only while this step is alive."""
        from ..ops.pallas_fused import mesh_scope

        def trace_of(step):
            with mesh_scope(step.mesh, step.data_axis):
                return step._step_jit.trace(*shapes)

        if source == "cache":
            ref = weakref.ref(self)
            traced = lambda: None if ref() is None else trace_of(ref())  # noqa: E731
        else:
            traced = trace_of(self)
        self._program = _trace.note_program("jit_mx_fused_step", traced,
                                            exe)

    def scope_table(self):
        """``{HLO instruction name: scope path}`` of the step program
        acquired last (``telemetry.trace.scope_table``), or None before
        the first step. Built when first asked for; a step costs nothing
        for it."""
        return None if self._program is None else self._program.table()

    def _note_cost(self, sig, exe):
        """Record XLA cost analysis of an already-compiled step program
        (bytes-accessed is THE optimization currency in the
        bandwidth-bound regime) — read off the executable we just
        acquired, never a second lower+compile. Feeds the
        ``step::bytes_accessed`` / ``flops`` / arithmetic-intensity
        gauges; the active StepTimeline derives roofline-fraction from
        the same numbers. Best-effort: some backends/AOT-loaded
        executables don't expose cost analysis."""
        self._program_exes[sig] = exe
        try:
            cost = exe.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            cost = dict(cost) if cost else {}
        except Exception:
            cost = {}
        self._program_costs[sig] = cost
        try:
            from ..telemetry import memory as _tmem
            self._program_memory[sig] = _tmem.analyze(exe)
        except Exception:
            self._program_memory[sig] = {}
        if not cost:
            return
        try:
            from ..telemetry.timeline import set_step_cost
            set_step_cost(flops=cost.get("flops"),
                          bytes_accessed=cost.get("bytes accessed"))
        except Exception:
            pass

    def _check_abort(self):
        """Lagged consecutive-skip abort (MXTPU_FT_MAX_CONSEC_SKIPS=K):
        the fstate ref from K steps ago is long materialized, so reading
        it never stalls the dispatch pipeline — detection latency is at
        most ~2K steps, and the step itself stays sync-free."""
        if self._max_consec <= 0 or not self.guard_enabled:
            return
        self._skip_lag.append(self._fault_state)
        if len(self._skip_lag) <= self._max_consec:
            return
        from ..telemetry import timeline as _tlmod
        with _tlmod.phase("device_read"):
            consec = int(np.asarray(self._skip_lag.popleft())[1])
        if consec >= self._max_consec:
            from .. import fault as _fault
            _fault.count("guard.aborts")
            raise MXNetError(
                f"aborting training: {consec} consecutive non-finite "
                f"steps were skipped by the gradient guard "
                f"(MXTPU_FT_MAX_CONSEC_SKIPS={self._max_consec}); the "
                "model state predates the first skipped step — inspect "
                "data/loss scale and resume from the last checkpoint")

    def reset_fault_state(self):
        """Zero the device skip counters (fault_report(reset=True))."""
        if self._fault_state is None:
            return
        rep = self._rep_sharding()
        z = jnp.zeros((2,), jnp.int32)
        self._fault_state = jax.device_put(z, rep) if rep is not None \
            else z
        self._skip_lag.clear()

    def lowered(self, feed):
        """Lower the step for the given feed dict (tools/bench introspection
        — keeps the jit signature private to this class)."""
        if self._step_jit is None:
            self._build()
        feed_vals = tuple(feed[n] for n in self.input_names)
        if self._lr_cache is None:
            self._lr_cache = (0.0, jnp.asarray(0.0, jnp.float32))
        from ..ops.pallas_fused import mesh_scope
        with mesh_scope(self.mesh, self.data_axis):
            return self._step_jit.lower(*self._state_args(), feed_vals,
                                        self._t_dev, self._lr_cache[1],
                                        self._base_key)

    def _feed_sig(self, feed):
        return tuple((tuple(feed[n].shape), str(feed[n].dtype))
                     for n in self.input_names)

    def step_cost(self, feed):
        """XLA cost analysis of the compiled step as a plain dict
        (keys like "flops", "bytes accessed"; {} when unavailable).
        The single unwrap point for the per-computation list some jax
        versions return; the tests that compare a rewritten step's
        bytes with the plain one's read costs through here
        (``benchmark/`` counts FLOPs from shapes, not from this). A
        program already acquired by :meth:`step` answers from the
        recorded cost (``_note_cost``) instead of paying a second
        lower+compile."""
        cached = self._program_costs.get(self._feed_sig(feed))
        if cached:
            return dict(cached)
        cost = self.lowered(feed).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost) if cost else {}

    def step_memory(self, feed):
        """``memory_analysis()`` of the compiled step as a plain dict
        (argument/output/temp/alias bytes + derived peak; {} when the
        backend has none) — same ``_note_cost`` rule as :meth:`step_cost`:
        a program already acquired answers from its record, never a
        second lower+compile."""
        cached = self._program_memory.get(self._feed_sig(feed))
        if cached:
            return dict(cached)
        from ..telemetry import memory as _tmem
        return _tmem.analyze(self.lowered(feed).compile())

    def compiled_program(self, feed):
        """The ALREADY-acquired executable for this feed signature, or
        None before :meth:`step` ran it. ``chip_smoke.py`` and the
        tests read HLO text and analyses off this instead of paying a
        second lower+compile."""
        return self._program_exes.get(self._feed_sig(feed))

    def optimizer_memory(self):
        """Optimizer-state footprint: ``logical_bytes`` (the state's
        global size) vs ``per_device_bytes`` (what ONE device actually
        holds — 1/N of every ZeRO-sharded leaf plus full copies of
        replicated ones). The ~1/N ratio is THE memory win of the
        sharded update (arXiv:2004.13336); memory_report()'s
        ``mem::optimizer::*`` gauges carry these numbers."""
        leaves = [x for st in (self._opt_state or ()) for x in st]
        leaves += [x for x in (self._flat_state or ())]
        logical = sum(int(x.size) * x.dtype.itemsize for x in leaves)
        out = {"logical_bytes": logical, "zero": bool(self._zero),
               "ndev": int(self._zero_ndev)}
        if self.mesh is None:
            out["per_device_bytes"] = logical
            return out
        dev0 = self.mesh.devices.flat[0]
        per_dev = 0
        for x in leaves:
            shards = getattr(x, "addressable_shards", None)
            if not shards:
                per_dev += int(x.size) * x.dtype.itemsize
                continue
            per_dev += sum(int(sh.data.size) * x.dtype.itemsize
                           for sh in shards if sh.device == dev0)
        out["per_device_bytes"] = per_dev
        return out

    def load_params(self, arg_dict, aux_dict):
        """Refresh parameter/aux buffers from executor arrays (set_params
        mid-run); optimizer state is kept, matching the eager Updater."""
        rep = self._rep_sharding()

        def _prep(v):
            v = jnp.array(v, copy=True)
            return jax.device_put(v, rep) if rep is not None else v

        def _put(v, spec):
            v = jnp.array(v, copy=True)
            if self.mesh is None:
                return v
            from jax.sharding import NamedSharding
            return jax.device_put(v, NamedSharding(self.mesh, spec))

        from jax.sharding import PartitionSpec as P
        specs = self._param_specs or [P()] * len(self._big_names)
        self._pvals = tuple(_put(arg_dict[n]._data, s)
                            for n, s in zip(self._big_names, specs))
        self._aux_vals = tuple(_prep(aux_dict[n]._data)
                               for n in self._aux_big_names)
        if self._small_total:
            self._flat_p = _prep(self._pack_params(arg_dict))
        if self._aux_total:
            self._flat_aux = _prep(self._pack_aux(aux_dict))

    # -- sync -----------------------------------------------------------------
    def sync_to(self, arg_dict, aux_dict):
        """Copy current parameter/aux buffers back into executor arrays.
        Copies, not references — our buffers are donated next step."""
        for n, v in zip(self._big_names, self._pvals):
            arg_dict[n]._data = jnp.array(v, copy=True)
        if self._small_total:
            flat = np.asarray(self._flat_p)
            for n in self._small_names:
                o, sz, shp = self._small_off[n]
                arg_dict[n]._data = jnp.asarray(
                    flat[o:o + sz].reshape(shp))
        for n, v in zip(self._aux_big_names, self._aux_vals):
            aux_dict[n]._data = jnp.array(v, copy=True)
        if self._aux_total:
            flat = np.asarray(self._flat_aux)
            for n in self._aux_small_names:
                o, sz, shp = self._aux_off[n]
                aux_dict[n]._data = jnp.asarray(
                    flat[o:o + sz].reshape(shp))

    # -- per-name views (packed-aware) ----------------------------------------
    def _param_state(self, n):
        """Optimizer state leaves for one parameter, as numpy arrays."""
        if n in self._big_names:
            return tuple(np.asarray(x)
                         for x in self._opt_state[
                             self._big_names.index(n)])
        o, sz, shp = self._small_off[n]
        # non-parameter-shaped leaves (e.g. nadam's scalar m_schedule) are
        # shared across the pack — emit them whole for every name
        return tuple(
            np.asarray(leaf)[o:o + sz].reshape(shp)
            if getattr(leaf, "ndim", 0) == 1 else np.asarray(leaf)
            for leaf in self._flat_state)

    # -- optimizer state io ----------------------------------------------------
    def get_states(self):
        """Serialized optimizer state (fused layout, self-describing)."""
        return pickle.dumps({
            "__mxnet_tpu_fused__": 1,
            "optimizer": type(self.optimizer).__name__.lower(),
            "num_update": self.num_update,
            "state": {n: self._param_state(n) for n in self.param_names},
        })

    def set_states(self, data):
        obj = pickle.loads(data) if isinstance(data, (bytes, bytearray)) \
            else data
        if not (isinstance(obj, dict) and obj.get("__mxnet_tpu_fused__")):
            raise MXNetError(
                "optimizer states were saved by the eager Updater path; "
                "the fused Module step cannot load them. Re-save from a "
                "fused run, or construct Module with fused=False to resume "
                "with the eager update loop.")
        if not self.started:
            raise MXNetError("call after bind/init (start() not run)")
        saved_opt = obj.get("optimizer")
        cur_opt = type(self.optimizer).__name__.lower()
        if saved_opt is not None and saved_opt != cur_opt:
            raise MXNetError(
                f"optimizer states were saved for '{saved_opt}' but the "
                f"module now runs '{cur_opt}'")
        self.num_update = obj["num_update"]
        rep = self._rep_sharding()
        t_dev = jnp.asarray(self.num_update, jnp.uint32)
        self._t_dev = jax.device_put(t_dev, rep) if rep is not None \
            else t_dev

        def _put(v, spec):
            # restore THIS world's recorded sharding: states in a
            # checkpoint are logical (gathered) arrays, and the mesh —
            # or its size — may have changed since they were saved
            # (elastic re-form resume, parallel/elastic.py)
            if self.mesh is None:
                return v
            from jax.sharding import NamedSharding
            return jax.device_put(v, NamedSharding(self.mesh, spec))

        from jax.sharding import PartitionSpec as P
        specs_by_big = self._opt_state_specs or \
            tuple(tuple(P() for _ in cur) for cur in self._opt_state)
        new_state = []
        for n, cur, specs in zip(self._big_names, self._opt_state,
                                 specs_by_big):
            saved = obj["state"].get(n)
            if saved is None:
                new_state.append(cur)
                continue
            if len(saved) != len(cur):
                raise MXNetError(
                    f"saved optimizer state for '{n}' has {len(saved)} "
                    f"leaves, expected {len(cur)} — optimizer mismatch?")
            new_state.append(tuple(
                _put(jnp.asarray(s,
                                 dtype=getattr(c, "dtype", jnp.float32)),
                     sp)
                for s, c, sp in zip(saved, cur, specs)))
        self._opt_state = tuple(new_state)
        if self._small_total and self._flat_state:
            leaves = [np.asarray(leaf).copy()
                      for leaf in self._flat_state]
            for n in self._small_names:
                saved = obj["state"].get(n)
                if saved is None:
                    continue
                if len(saved) != len(leaves):
                    raise MXNetError(
                        f"saved optimizer state for '{n}' has "
                        f"{len(saved)} leaves, expected {len(leaves)} — "
                        f"optimizer mismatch?")
                o, sz, _ = self._small_off[n]
                for j, sv in enumerate(saved):
                    if leaves[j].ndim == 1:
                        leaves[j][o:o + sz] = np.asarray(sv).ravel()
                    else:
                        # pack-shared leaf (scalar schedule): identical
                        # for every name, last write wins
                        leaves[j] = np.asarray(sv).reshape(
                            leaves[j].shape)
            fspecs = self._flat_state_specs or \
                tuple(P() for _ in leaves)
            self._flat_state = tuple(
                _put(jnp.asarray(x), sp)
                for x, sp in zip(leaves, fspecs))
