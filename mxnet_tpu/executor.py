"""Executor: bound symbolic computation.

TPU-native rebuild of ``mxnet.executor`` + the native GraphExecutor
(reference: python/mxnet/executor.py — forward :113, backward :154,
reshape :371; src/executor/graph_executor.cc).

Architectural mapping: the reference compiles the graph at bind time
(memory planning, op attachment, segment bulking) and pushes cached engine
ops per batch. Here bind builds ONE jitted forward function and ONE jitted
forward+backward function (via jax.vjp over the whole graph) — XLA is the
memory planner and scheduler; "bulking" is total.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from .base import MXNetError
from .ndarray.ndarray import NDArray, _wrap

__all__ = ["Executor"]

# output-layer ops whose backward is the gradient of an implicit loss
# (reference: src/operator/softmax_output.cc, regression_output.cc)
_IMPLICIT_LOSS = {}


def _register_implicit_losses():
    import jax
    import jax.numpy as jnp
    from .ops import nn as _nn

    def linreg_loss(data, label, grad_scale=1.0, **kw):
        return grad_scale * 0.5 * jnp.sum(
            jnp.square(data - label.reshape(data.shape)))

    def maereg_loss(data, label, grad_scale=1.0, **kw):
        return grad_scale * jnp.sum(jnp.abs(data - label.reshape(data.shape)))

    def logreg_loss(data, label, grad_scale=1.0, **kw):
        # grad = sigmoid(x) - y
        x = data
        y = label.reshape(data.shape)
        return grad_scale * jnp.sum(
            jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x))))

    def svm_loss(data, label, margin=1.0, regularization_coefficient=1.0,
                 use_linear=False, **kw):
        """One-vs-rest hinge loss (reference: src/operator/svm_output.cc
        L1_SVM/L2_SVM mshadow_op:31-67): the true-class score is pushed
        above +margin, every other score below -margin, each independently
        (NOT the Crammer-Singer relative-margin form)."""
        y = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(y, data.shape[-1], dtype=data.dtype)
        pos = jnp.maximum(0.0, margin - data) * onehot
        neg = jnp.maximum(0.0, margin + data) * (1.0 - onehot)
        viol = pos + neg
        per = jnp.sum(viol) if use_linear else jnp.sum(jnp.square(viol))
        return regularization_coefficient * per

    _IMPLICIT_LOSS.update({
        "SoftmaxOutput": _nn.softmax_output_loss,
        "Softmax": _nn.softmax_output_loss,
        "LinearRegressionOutput": linreg_loss,
        "MAERegressionOutput": maereg_loss,
        "LogisticRegressionOutput": logreg_loss,
        "SVMOutput": svm_loss,
    })


def collect_loss_specs(sym):
    """(output_index, head node, parsed attrs) for every implicit-loss
    head (SoftmaxOutput & co — reference: src/operator/softmax_output.cc).
    Shared by the jitted, segmented, and fused executors."""
    if not _IMPLICIT_LOSS:
        _register_implicit_losses()
    from .ops.registry import parse_attr
    specs = []
    for i, h in enumerate(sym._output_symbols()):
        node = h._node
        if node.op in _IMPLICIT_LOSS:
            attrs = {k: parse_attr(v) for k, v in node.attrs.items()
                     if not k.startswith("__")}
            specs.append((i, node, attrs))
    return specs


def total_implicit_loss(loss_specs, head_inputs, outs, head_grads):
    """Scalar training loss: each implicit head's loss over its INPUT
    values plus sum(out * head_grad) for explicit heads — the quantity
    whose gradient is the reference backward."""
    import jax
    import jax.numpy as jnp
    total = jnp.zeros((), jnp.float32)
    implicit = {i for i, _, _ in loss_specs}
    for (i, node, attrs), ins in zip(loss_specs, head_inputs):
        # the heads' gradient (p - y and its kin) carries this scope
        with jax.named_scope("mx_loss"):
            total = total + _IMPLICIT_LOSS[node.op](
                *ins, **attrs).astype(jnp.float32)
    for i, o in enumerate(outs):
        if i not in implicit and head_grads is not None and \
                head_grads[i] is not None:
            total = total + jnp.sum(o * head_grads[i])
    return total


def build_graph_fns(sym, device_map=None):
    """Pure forward / forward-with-implicit-loss functions for a symbol.

    Shared by Executor (separate fwd / fwd+grad jits) and the fused Module
    step (one fwd+bwd+update program). Returns ``(fwd, fwd_loss,
    loss_specs)`` where

        fwd(arg_vals, aux_vals, key, training) -> (outs, aux_updates)
        fwd_loss(arg_vals, aux_vals, head_grads, key)
            -> (scalar, (outs, aux_updates))

    ``fwd_loss``'s scalar is the sum of the graph's implicit losses
    (SoftmaxOutput & co — reference: src/operator/softmax_output.cc) plus
    ``sum(out * head_grad)`` for explicit heads, so its gradient wrt
    arg_vals is the reference backward.

    ``device_map`` routes each node to a group2ctx device (eager-only —
    see Symbol.eval_arrays_ex); functions built with it must NOT be
    jitted.
    """
    if not _IMPLICIT_LOSS:
        _register_implicit_losses()
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()

    def fwd(arg_vals, aux_vals, key, training):
        amap = dict(zip(arg_names, arg_vals))
        amap.update(zip(aux_names, aux_vals))
        outs, aux_updates = sym.eval_arrays_ex(amap, training=training,
                                               rng_key=key,
                                               device_map=device_map)
        return tuple(outs), aux_updates

    loss_specs = collect_loss_specs(sym)

    def fwd_loss(arg_vals, aux_vals, head_grads, key, preset=None):
        amap = dict(zip(arg_names, arg_vals))
        amap.update(zip(aux_names, aux_vals))
        outs, aux_updates = sym.eval_arrays_ex(amap, training=True,
                                               rng_key=key,
                                               device_map=device_map,
                                               preset=preset)
        # recompute each head's loss from the head node's *inputs* (XLA
        # CSE dedups against the forward eval). ``preset`` — values
        # seeded for specific nodes (the fused step's row-sparse
        # embedding routing) — must reach the recompute too, or the
        # seeded branch would fork from the loss actually trained on.
        head_inputs = []
        for i, node, attrs in loss_specs:
            ins = []
            for p, oi in node.inputs:
                sub = type(sym)(p, oi)
                ins.append(sub.eval_arrays(amap, training=True,
                                           rng_key=key,
                                           device_map=device_map,
                                           preset=preset)[0])
            head_inputs.append(ins)
        total = total_implicit_loss(loss_specs, head_inputs, outs,
                                    head_grads)
        return total, (tuple(outs), aux_updates)

    return fwd, fwd_loss, loss_specs


class Executor:
    """A bound computation graph (reference: executor.py:30).

    When ``_mesh`` is set (by Module for a multi-context bind), inputs named
    in ``_batch_args`` are placed batch-sharded over the mesh's 'data' axis
    and everything else replicated before each jitted call — GSPMD then
    partitions the whole program across the devices, the TPU equivalent of
    the reference's DataParallelExecutorGroup slicing
    (python/mxnet/module/executor_group.py:129, decide_slices :267)."""

    def __init__(self, symbol, ctx, arg_dict: Dict[str, NDArray],
                 args_grad: Optional[Dict[str, NDArray]], grad_req,
                 aux_dict: Dict[str, NDArray], group2ctx=None):
        if not _IMPLICIT_LOSS:
            _register_implicit_losses()
        self._symbol = symbol
        self._ctx = ctx
        self.arg_dict = dict(arg_dict)
        self.aux_dict = dict(aux_dict or {})
        self.grad_dict = dict(args_grad or {})
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in symbol.list_arguments()}
        else:
            self.grad_req = dict(grad_req)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()
        self.outputs: List[NDArray] = []
        self._monitor_callback = None
        self._monitor_all = False
        self._fwd_jit = None
        self._vjp_fn = None
        self._is_train = False
        self._mesh = None          # set by Module on multi-context bind
        self._batch_args = set()   # arg names sharded over the batch axis
        self._group2ctx = dict(group2ctx) if group2ctx else None
        self._device_map = None    # node -> device (group2ctx builds)
        self._fusion_report = None  # set by _build when the pass runs
        self._pass_report = None   # full pipeline report (passes/)
        # variable order of the graph the programs were TRACED from —
        # passes may permute it (BN folding re-roots the fold
        # arithmetic), so the jitted functions are fed in this order,
        # never the original symbol's
        self._run_arg_names = self.arg_names
        self._run_aux_names = self.aux_names

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    # -- compilation ----------------------------------------------------------
    def _build(self):
        import jax

        if self._group2ctx:
            # model parallelism by placement: the graph is partitioned at
            # ctx-group boundaries into per-device SEGMENTS, each jitted
            # as one XLA program pinned to its device (via committed
            # inputs), with device_put transfers between segments — the
            # compiled analog of the reference's per-device plan +
            # _CrossDeviceCopy (graph_executor.cc:406). The old fallback
            # dispatched every op eagerly. The Monitor capture pass
            # (eval_arrays_ex) still walks eagerly with device_map.
            import jax.numpy as jnp
            default_dev = self._ctx.jax_device if self._ctx is not None \
                else None
            dmap = self._symbol.build_device_map(self._group2ctx,
                                                 default_dev)
            self._device_map = dmap
            sym = self._symbol
            arg_names = self.arg_names
            aux_names = self.aux_names
            loss_specs = collect_loss_specs(sym)
            extra = [[(p, oi) for p, oi in node.inputs]
                     for _i, node, _a in loss_specs]
            flat_extra = [k for ins in extra for k in ins]
            plan = sym.build_segment_plan(dmap, extra_outputs=flat_extra)
            self._loss_specs = loss_specs
            self._segment_plan = plan
            n_outs = len(sym._output_symbols())

            def fwd(arg_vals, aux_vals, key, training):
                amap = dict(zip(arg_names, arg_vals))
                amap.update(zip(aux_names, aux_vals))
                vals, aux_updates = sym.eval_segmented(
                    plan, amap, training=training, rng_key=key)
                return tuple(vals[:n_outs]), aux_updates

            def fwd_loss(arg_vals, aux_vals, head_grads, key):
                amap = dict(zip(arg_names, arg_vals))
                amap.update(zip(aux_names, aux_vals))
                vals, aux_updates = sym.eval_segmented(
                    plan, amap, training=True, rng_key=key)
                outs = vals[:n_outs]
                # the head-input values ride along as extra plan outputs
                head_inputs = []
                p = n_outs
                for ins in extra:
                    head_inputs.append(vals[p:p + len(ins)])
                    p += len(ins)
                total = total_implicit_loss(loss_specs, head_inputs,
                                            outs, head_grads)
                return total, (tuple(outs), aux_updates)

            self._fwd_jit = fwd
            self._fwd_loss_grad = jax.grad(fwd_loss, argnums=0,
                                           has_aux=True)
            return

        # Graph-rewrite pass pipeline (symbol/passes/): the jitted
        # functions are built from a rewritten graph; self._symbol stays
        # the source of truth for names, serialization and the Monitor's
        # tapped eager pass. Bound array shapes decide applicability
        # bail-outs here. Mesh binds run the full mesh-safe pipeline
        # (round 18: the fused kernels shard_map under mesh_scope and
        # the gate measures per-device bytes); an unsafe pass counts
        # into passes::skipped with reason "mesh_bind:<pass>".
        sym = self._symbol
        infer_only = all(r == "null" for r in self.grad_req.values())
        from .symbol import passes as _passes
        shapes = {n: tuple(a.shape) for n, a in
                  list(self.arg_dict.items()) +
                  list(self.aux_dict.items())}
        # inference-only binds (grad_req all 'null' — predict/score
        # and serving executors) report under their own tag so
        # pass/fusion reports show the predict program is covered too,
        # and run in 'infer' mode so eval-only rewrites (BN folding)
        # may fire
        fused_sym, self._pass_report = _passes.apply_pipeline(
            self._symbol, shapes,
            tag="executor_infer" if infer_only else "executor",
            mode="infer" if infer_only else "train", mesh=self._mesh,
            batch_names=self._batch_args or None)
        self._fusion_report = _passes.legacy_fusion_entry(
            self._pass_report)
        if fused_sym is not None:
            sym = fused_sym
        self._run_arg_names = sym.list_arguments()
        self._run_aux_names = sym.list_auxiliary_states()
        # route the bind through the compile registry: programs are
        # keyed by (symbol JSON, bound shapes/dtypes, grad_req, mesh,
        # fusion flag) and SHARED between executors with identical keys
        # — two BucketingModule buckets binding identical shapes run
        # one compiled program, and re-switching buckets never
        # recompiles (compiles == unique program keys, pinned in
        # tests/test_bucketing_lm.py). JitProgram counts traces and
        # compile wall time into mx.compile_report().
        from . import compile as compile_mod
        from . import config as _config
        sigs = sorted(
            (n, tuple(a.shape), str(a.dtype))
            for n, a in list(self.arg_dict.items()) +
            list(self.aux_dict.items()))
        fusion_mat = {
            "flag": str(_config.get("MXTPU_PALLAS_FUSION")),
            "sites": len(self._fusion_report["sites"])
            if self._fusion_report else 0}
        kind = "executor_infer" if infer_only else "executor"
        base = f"executor:{self._symbol.name}"
        grad_req_mat = sorted(self.grad_req.items())
        symbol_sha = compile_mod.symbol_digest(self._symbol)

        def _key(prog):
            return compile_mod.program_key(
                kind, f"{base}:{prog}", symbol_sha=symbol_sha,
                input_sigs=sigs, mesh=self._mesh, fusion=fusion_mat,
                passes=_passes.pipeline_key_material(self._pass_report),
                extra={"prog": prog, "grad_req": grad_req_mat})

        key_fwd, key_grad = _key("fwd"), _key("grad")
        orig_sym = self._symbol

        def _builder():
            fwd_run, fwd_loss_run, loss_specs = build_graph_fns(sym)
            if infer_only and sym is not orig_sym:
                # eval-only rewrites (BN folding bakes moving-stats
                # semantics) are invalid under training=True; that
                # (rare, debug) specialization of an inference bind —
                # and its never-used grad program — trace the ORIGINAL
                # graph, remapping the run-order feed back to it
                fwd_orig, fwd_loss_orig, loss_specs = \
                    build_graph_fns(orig_sym)
                run_args, run_aux = (sym.list_arguments(),
                                     sym.list_auxiliary_states())
                orig_args = orig_sym.list_arguments()
                orig_aux = orig_sym.list_auxiliary_states()

                def _remap(vals, src, dst):
                    m = dict(zip(src, vals))
                    return tuple(m[n] for n in dst)

                def fwd(arg_vals, aux_vals, key, training):
                    if training:   # static arg: resolved at trace time
                        return fwd_orig(
                            _remap(arg_vals, run_args, orig_args),
                            _remap(aux_vals, run_aux, orig_aux),
                            key, True)
                    return fwd_run(arg_vals, aux_vals, key, False)

                def fwd_loss(arg_vals, aux_vals, head_grads, key):
                    return fwd_loss_orig(
                        _remap(arg_vals, run_args, orig_args),
                        _remap(aux_vals, run_aux, orig_aux),
                        head_grads, key)
            else:
                fwd, fwd_loss = fwd_run, fwd_loss_run
            return {
                "fwd": compile_mod.JitProgram(fwd, key_fwd,
                                              static_argnums=(3,)),
                "grad": compile_mod.JitProgram(
                    jax.grad(fwd_loss, argnums=0, has_aux=True),
                    key_grad),
                "loss_specs": loss_specs,
            }

        holder, _shared = compile_mod.shared_programs(key_fwd, _builder)
        self._progs_holder = holder   # strong ref keeps the share alive
        self._loss_specs = holder["loss_specs"]
        self._fwd_jit = holder["fwd"]
        self._fwd_loss_grad = holder["grad"]

    def _place(self, name, val):
        """Mesh placement for one argument value (no-op without a mesh)."""
        if self._mesh is None:
            return val
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P("data") if name in self._batch_args else P()
        return jax.device_put(val, NamedSharding(self._mesh, spec))

    def _trace_scope(self):
        """Mesh scope for jit entry points: the fused Pallas ops wrap
        themselves in shard_map when TRACED under an active mesh scope
        (ops/pallas_fused.py, round 18), and jit traces lazily at first
        call — so every call site enters the scope (no-op off-mesh)."""
        from .ops.pallas_fused import mesh_scope
        return mesh_scope(self._mesh)

    # -- execution ------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """(reference: executor.py:113)"""
        if kwargs:
            import jax.numpy as jnp
            for name, arr in kwargs.items():
                if name not in self.arg_dict:
                    raise MXNetError(f"Unknown argument {name}")
                # assign_array keeps group2ctx placement intact
                self.assign_array(
                    self.arg_dict[name],
                    arr if isinstance(arr, NDArray) else jnp.asarray(arr))
        if self._fwd_jit is None:
            self._build()
        self._is_train = is_train
        from . import random as _random
        # feed in the TRACED graph's variable order (_run_*: the pass
        # pipeline may permute it); values come from the name-keyed
        # dicts so the original symbol's lists stay the public surface
        arg_vals = tuple(self._place(n, self.arg_dict[n]._data)
                         for n in self._run_arg_names)
        aux_vals = tuple(self._place(n, self.aux_dict[n]._data)
                         for n in self._run_aux_names)
        cb_active = getattr(self._monitor_callback, "active",
                            None) if self._monitor_callback else None
        monitor_now = self._monitor_callback is not None and \
            (cb_active is None or cb_active())
        if monitor_now and self._monitor_all:
            # interpreted pass capturing every op output for the Monitor
            # (reference: GraphExecutor ExecuteMonCallback :1445); slower
            # than the jit path — monitoring is a debug mode there too,
            # and an interval-based Monitor only activates it on its
            # monitored batches (callback.active probe)
            amap = {n: v for n, v in zip(self._run_arg_names, arg_vals)}
            amap.update(zip(self._run_aux_names, aux_vals))
            internals = {}
            outs, aux_updates = self._symbol.eval_arrays_ex(
                amap, training=bool(is_train), rng_key=_random.next_key(),
                internals=internals, device_map=self._device_map)
            for name, o in internals.items():
                self._monitor_callback(name, _wrap(o))
        else:
            with self._trace_scope():
                outs, aux_updates = self._fwd_jit(arg_vals, aux_vals,
                                                  _random.next_key(),
                                                  bool(is_train))
        self.outputs = [_wrap(o) for o in outs]
        self._apply_aux_updates(aux_updates)
        if monitor_now and not self._monitor_all:
            for name, o in zip(self.output_names, self.outputs):
                self._monitor_callback(name, o)
        return self.outputs

    def _apply_aux_updates(self, aux_updates):
        """Fold BatchNorm running-stat updates into aux arrays (functional
        analog of the reference's in-place aux mutation)."""
        for name, val in (aux_updates or {}).items():
            if name in self.aux_dict:
                self.aux_dict[name]._data = val

    def backward(self, out_grads=None, is_train=True):
        """(reference: executor.py:154; grads accumulate per grad_req)"""
        if self._fwd_jit is None:
            self._build()
        import jax.numpy as jnp
        from . import random as _random
        arg_vals = tuple(self._place(n, self.arg_dict[n]._data)
                         for n in self._run_arg_names)
        aux_vals = tuple(self._place(n, self.aux_dict[n]._data)
                         for n in self._run_aux_names)
        if out_grads is None:
            head_grads = None
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            head_grads = tuple(
                g._data if isinstance(g, NDArray) else jnp.asarray(g)
                for g in out_grads)
        with self._trace_scope():
            grads, (outs, aux_updates) = self._fwd_loss_grad(
                arg_vals, aux_vals, head_grads, _random.next_key())
        self.outputs = [_wrap(o) for o in outs]
        self._apply_aux_updates(aux_updates)
        for name, g in zip(self._run_arg_names, grads):
            req = self.grad_req.get(name, "null")
            if req == "null" or name not in self.grad_dict:
                continue
            tgt = self.grad_dict[name]
            if req == "add":
                tgt._data = tgt._data + g
            else:
                tgt._data = g

    def set_monitor_callback(self, callback, monitor_all=False):
        """(reference: executor.py set_monitor_callback;
        GraphExecutor graph_executor.cc:121)"""
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    def assign_array(self, tgt, value):
        """Rebind an executor array's buffer, preserving its committed
        device under group2ctx placement (any other write path would
        silently migrate a placed weight to the default device)."""
        src = value._data if isinstance(value, NDArray) else value
        if self._group2ctx is not None:
            import jax
            src = jax.device_put(src, list(tgt._data.devices())[0])
        tgt._data = src

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """(reference: executor.py:326); device-preserving under
        group2ctx placement."""
        for name, array in arg_params.items():
            if name in self.arg_dict:
                self.assign_array(self.arg_dict[name], array)
            elif not allow_extra_params:
                raise ValueError(f"Found name \"{name}\" that is not in the "
                                 "arguments")
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    self.assign_array(self.aux_dict[name], array)
                elif not allow_extra_params:
                    raise ValueError(f"Found name \"{name}\" that is not in "
                                     "the auxiliary states")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor for new input shapes (reference:
        executor.py:371). XLA recompiles per shape — this is the
        BucketingModule mechanism."""
        import jax
        from . import ndarray as nd
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)

        def _alloc_like(old, s):
            # fresh buffer on the SAME device as the old array (group2ctx
            # placement survives bucketing reshapes)
            arr = nd.zeros(s, ctx=self._ctx)
            if self._group2ctx is not None and old is not None:
                arr._data = jax.device_put(
                    arr._data, list(old._data.devices())[0])
            return arr

        new_args = {}
        for name, s in zip(self.arg_names, arg_shapes):
            old = self.arg_dict[name]
            if tuple(old.shape) == tuple(s):
                new_args[name] = old
            else:
                new_args[name] = _alloc_like(old, s)
        new_grads = {}
        if self.grad_dict:
            for name, s in zip(self.arg_names, arg_shapes):
                if name in self.grad_dict:
                    new_grads[name] = _alloc_like(self.grad_dict[name], s)
        new_aux = {}
        for name, s in zip(self.aux_names, aux_shapes):
            old = self.aux_dict[name]
            new_aux[name] = old if tuple(old.shape) == tuple(s) \
                else _alloc_like(old, s)
        new_exec = Executor(self._symbol, self._ctx, new_args, new_grads,
                            self.grad_req, new_aux,
                            group2ctx=self._group2ctx)
        # keep the mesh placement across bucketing reshapes — dropping it
        # would silently un-shard a multi-context Module
        new_exec._mesh = self._mesh
        new_exec._batch_args = set(self._batch_args)
        # an installed Monitor survives the reshape (its callback would
        # otherwise silently stop capturing)
        new_exec._monitor_callback = self._monitor_callback
        new_exec._monitor_all = self._monitor_all
        if self._mesh is not None:
            ndev = self._mesh.devices.size
            for name, s in zip(self.arg_names, arg_shapes):
                if name in new_exec._batch_args and s and s[0] % ndev:
                    raise MXNetError(
                        f"reshaped batch dim of '{name}' ({s[0]}) is not "
                        f"divisible by the mesh size ({ndev})")
        return new_exec

    @property
    def output_dict(self):
        return dict(zip(self.output_names, self.outputs))
