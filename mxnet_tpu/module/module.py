"""Module: symbolic training, data-parallel over a device mesh.

TPU-native rebuild of ``mxnet.module.module`` (reference:
python/mxnet/module/module.py — bind :363, init_optimizer :472,
forward/backward/update :570-651).

Architectural mapping: the reference binds one executor per GPU via
DataParallelExecutorGroup (python/mxnet/module/executor_group.py:129,
decide_slices :267) and
reduces gradients through KVStore. Here there is ONE executor whose arrays
are sharded over a ``jax.sharding.Mesh`` built from the ctx list: the batch
is split over the mesh's 'data' axis (the decide_slices equivalent, even
slices only), parameters are replicated, and GSPMD inserts the gradient
all-reduce — the executor-group/KVStore machinery collapses into the
compiler. Requesting more contexts than there are distinct devices raises,
as does an uneven ``work_load_list`` — nothing is silently dropped.

In the steady state (init_optimizer with a local/None kvstore and
grad_req='write'), forward/backward/update collapse into ONE donated XLA
program per input shape (module/fused.py) covering fwd + implicit-loss bwd
+ optimizer update + BatchNorm aux fold — the TPU analog of the
reference's bulked engine pushes, with the Python Updater loop gone.
"""
from __future__ import annotations

import logging
import warnings

import numpy as np

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..base import MXNetError
from ..io import DataDesc
from ..model import load_checkpoint, save_checkpoint
from ..telemetry import trace as _trace
from .base_module import BaseModule, _check_input_names

__all__ = ["Module"]


def _norm_shapes(shapes):
    """Normalize [(name, shape)] / [DataDesc] to [(name, tuple)]."""
    out = []
    for s in shapes or []:
        if isinstance(s, DataDesc):
            out.append((s.name, tuple(s.shape)))
        else:
            out.append((s[0], tuple(s[1])))
    return out


class Module(BaseModule):
    """(reference: module.py:45)"""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None, fused=None, compute_dtype=None):
        super().__init__(logger=logger)
        if context is None:
            context = ctx_mod.current_context()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        if isinstance(group2ctxs, (list, tuple)):
            # reference shape: one dict per DP context
            # (module/executor_group.py group2ctxs); combining DP with
            # placement is not supported here — raise rather than drop
            # either axis
            if len(group2ctxs) > 1:
                raise NotImplementedError(
                    "group2ctxs with multiple entries (model parallelism "
                    "replicated across data-parallel contexts) is not "
                    "supported; use a single group2ctx dict")
            group2ctxs = group2ctxs[0] if group2ctxs else None
        if group2ctxs is not None and len(context) > 1:
            raise NotImplementedError(
                "group2ctxs cannot be combined with a multi-device ctx "
                "list; choose data parallelism OR placement")
        self._group2ctxs = group2ctxs
        if work_load_list is not None and len(set(work_load_list)) > 1:
            raise NotImplementedError(
                "uneven work_load_list is not supported: GSPMD shards the "
                "batch evenly over the mesh (reference decide_slices "
                "executor_group.py:267 allowed uneven slices)")
        self._fused_requested = fused
        self._fused = None
        self._fused_feed = None
        self._mesh = None
        self._compute_dtype = compute_dtype
        self._symbol = symbol
        self._data_names = list(data_names) if data_names is not None else []
        self._label_names = list(label_names) if label_names is not None \
            else []
        self._state_names = list(state_names) if state_names is not None \
            else []
        self._fixed_param_names = list(fixed_param_names) \
            if fixed_param_names is not None else []
        _check_input_names(symbol, self._data_names, "data", True)
        _check_input_names(symbol, self._label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param",
                           True)
        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names + \
            self._state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._compression_params = compression_params
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._grad_req = None

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """(reference: module.py:126)"""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = f"{prefix}-{epoch:04d}.states"
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """(reference: module.py:164)"""
        self._symbol.save(f"{prefix}-symbol.json")
        param_name = f"{prefix}-{epoch:04d}.params"
        self.save_params(param_name)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        if save_optimizer_states:
            state_name = f"{prefix}-{epoch:04d}.states"
            self.save_optimizer_states(state_name)

    # -- properties -----------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        if self._exec.outputs:
            return [(n, tuple(o.shape))
                    for n, o in zip(self._output_names, self._exec.outputs)]
        # before the first forward: infer from the bound input shapes
        # (reference semantics — output_shapes is valid right after bind)
        shapes = dict(self._data_shapes or [])
        shapes.update(dict(self._label_shapes or []))
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return list(zip(self._output_names,
                        (tuple(s) for s in out_shapes)))

    # -- params ---------------------------------------------------------------
    def get_params(self):
        """(reference: module.py:233)"""
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        """(reference: module.py:255)"""
        from .. import initializer as init_mod
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        if initializer is None and (arg_params is None or force_init is False):
            initializer = init_mod.Uniform(0.01)

        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(self._exec.arg_dict[name].shape)
                for name in self._param_names}
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(self._exec.aux_dict[name].shape)
                for name in self._aux_names}

        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache_arr = cache[name]
                if cache_arr is not arr:
                    if tuple(cache_arr.shape) != tuple(arr.shape):
                        raise RuntimeError(
                            f"Fail to load parameter {name} because of shape "
                            f"mismatch: {cache_arr.shape} vs {arr.shape}")
                    arr._data = cache_arr._data
            elif not allow_missing or initializer is not None:
                if initializer is not None:
                    from ..initializer import InitDesc
                    desc = InitDesc(name, attrs.get(name, None))
                    initializer(desc, arr)
            if cache is not None and name not in cache and not allow_missing:
                raise RuntimeError(f"{name} is not presented")

        for name, arr in sorted(self._arg_params.items()):
            _impl(name, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            _impl(name, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._copy_params_to_exec()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "set_params call ignored.", stacklevel=2)
            return
        for name, arr in (arg_params or {}).items():
            if name in self._arg_params:
                self._arg_params[name]._data = arr._data
        for name, arr in (aux_params or {}).items():
            if name in self._aux_params:
                self._aux_params[name]._data = arr._data
        self.params_initialized = True
        self._params_dirty = False
        self._copy_params_to_exec()

    def _copy_params_to_exec(self, refresh_fused=True):
        # Executor.assign_array preserves group2ctx placement
        for name in self._param_names:
            if name in self._arg_params:
                self._exec.assign_array(self._exec.arg_dict[name],
                                        self._arg_params[name])
        for name in self._aux_names:
            if name in self._aux_params:
                self._exec.assign_array(self._exec.aux_dict[name],
                                        self._aux_params[name])
        if refresh_fused and self._fused is not None and self._fused.started:
            # set_params/init_params mid-run: push the new values into the
            # fused buffers (optimizer state is kept, like the eager path)
            self._fused.load_params(self._exec.arg_dict, self._exec.aux_dict)
        if self._kvstore is not None and self._update_on_kvstore:
            # update-on-kvstore: the store holds the master weights that
            # every pull copies back over arg_dict, so set_params after
            # init_optimizer (auto-resume restores a checkpoint here)
            # must overwrite the master too — otherwise the first
            # push/pull silently reverts training to the stale init
            for i, name in enumerate(self._param_names):
                if name in self._arg_params:
                    self._kvstore.set(i, self._arg_params[name])

    def _sync_params_from_devices(self):
        """(reference: module.py:755)"""
        if self._fused is not None and self._fused.started:
            self._fused.sync_to(self._exec.arg_dict, self._exec.aux_dict)
        for name in self._param_names:
            self._arg_params[name]._data = self._exec.arg_dict[name]._data
        for name in self._aux_names:
            self._aux_params[name]._data = self._exec.aux_dict[name]._data
        self._params_dirty = False

    # -- bind -----------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """(reference: module.py:363)"""
        if force_rebind:
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        with _trace.span("bind", "setup"):
            self._bind(data_shapes, label_shapes, for_training,
                       inputs_need_grad, shared_module, grad_req)

    def _bind(self, data_shapes, label_shapes, for_training,
              inputs_need_grad, shared_module, grad_req):
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._data_shapes = _norm_shapes(data_shapes)
        self._label_shapes = _norm_shapes(label_shapes)
        shape_kwargs = dict(self._data_shapes + self._label_shapes)
        if not for_training:
            grad_req = "null"
        self._grad_req = grad_req
        shared_buffer = shared_module._exec.arg_dict \
            if shared_module is not None else None
        self._mesh = self._build_mesh()
        self._exec = self._symbol.simple_bind(
            ctx=self._context[0], grad_req=grad_req,
            shared_buffer=shared_buffer, group2ctx=self._group2ctxs,
            **shape_kwargs)
        if self._mesh is not None:
            self._exec._mesh = self._mesh
            self._exec._batch_args = set(
                n for n, _ in self._data_shapes + self._label_shapes)
        self.binded = True
        if self.params_initialized:
            # params were loaded before bind (Module.load path,
            # reference: module.py:441 set_params into fresh executors)
            self._copy_params_to_exec()
        if shared_module is not None and shared_module.params_initialized:
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            self.params_initialized = True
            self._copy_params_to_exec()

    def _build_mesh(self):
        """Multi-context bind -> a 1-D 'data' mesh over the ctx devices
        (the DataParallelExecutorGroup equivalent). Shard-or-raise: never
        silently train on context[0] alone."""
        if len(self._context) <= 1:
            return None
        from jax.sharding import Mesh
        devs = [c.jax_device for c in self._context]
        if len({d.id for d in devs}) != len(devs):
            raise MXNetError(
                f"Module got {len(self._context)} contexts "
                f"{self._context} but they map to only "
                f"{len({d.id for d in devs})} distinct device(s); "
                "multi-context training needs one real device per context")
        for name, shape in self._data_shapes + (self._label_shapes or []):
            if shape and shape[0] % len(devs) != 0:
                raise MXNetError(
                    f"batch dimension of '{name}' ({shape[0]}) is not "
                    f"divisible by the number of contexts ({len(devs)}); "
                    "GSPMD shards the batch evenly (reference "
                    "decide_slices allowed remainders)")
        return Mesh(np.array(devs), ("data",))

    # -- optimizer ------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """(reference: module.py:472; update decision model.py:58-95)"""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        with _trace.span("init_optimizer", "setup"):
            self._init_optimizer(kvstore, optimizer, optimizer_params)

    def _init_optimizer(self, kvstore, optimizer, optimizer_params):
        # normalize the summed batch gradient like the reference
        # (module.py:494-507: rescale_grad defaults to 1/batch_size,
        # scaled by num_workers for dist kvstore)
        batch_size = self._data_shapes[0][1][0] if self._data_shapes else 1
        from .. import kvstore as kvs
        kv_obj = None
        if kvstore:
            kv_obj = kvs.create(kvstore) if isinstance(kvstore, str) \
                else kvstore
            kv_type = getattr(kv_obj, "type", "")
            if "dist" in kv_type and "_sync" in kv_type:
                batch_size *= kv_obj.num_workers
        rescale_grad = 1.0 / max(batch_size, 1)
        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            optimizer_params.setdefault("rescale_grad", rescale_grad)
            optimizer = opt.create(optimizer, param_idx2name=idx2name,
                                   **optimizer_params)
        elif optimizer.rescale_grad != rescale_grad:
            self.logger.warning(
                "Optimizer created manually outside Module but "
                "rescale_grad is not normalized to 1.0/batch_size "
                "(%s vs. %s). Is this intended?",
                optimizer.rescale_grad, rescale_grad)
        self._optimizer = optimizer
        self._updater = opt.get_updater(optimizer)
        if kv_obj is not None:
            kv = kv_obj
            self._kvstore = kv
            self._update_on_kvstore = kv.is_distributed
            if self._update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            for i, name in enumerate(self._param_names):
                kv.init(i, self._arg_params[name])
        self.optimizer_initialized = True
        self._maybe_init_fused()
        if hasattr(self, "_preload_opt_states"):
            self.load_optimizer_states(self._preload_opt_states)
            del self._preload_opt_states

    def _maybe_init_fused(self):
        """Enable the fused fwd+bwd+update program when the configuration
        allows it (module/fused.py). ``fused=True`` forces (raise if
        impossible), ``fused=False`` opts out, None = auto."""
        if self._fused_requested is False:
            return
        blockers = []
        if self._update_on_kvstore:
            blockers.append("distributed kvstore updates")
        if self._grad_req != "write":
            blockers.append(f"grad_req={self._grad_req!r}")
        if self.inputs_need_grad:
            blockers.append("inputs_need_grad")
        if self._state_names:
            blockers.append("state_names")
        if self._group2ctxs:
            # placement runs the eager per-op path (executor._build
            # group2ctx branch); one jitted program would collapse the
            # devices back to one
            blockers.append("group2ctxs placement")
        if blockers:
            if self._fused_requested:
                raise MXNetError(
                    f"Module(fused=True) impossible with: {blockers}")
            return
        try:
            from .fused import FusedSymbolStep
            trainable = {
                n: (self._grad_dict_req(n) != "null"
                    and n not in self._fixed_param_names)
                for n in self._param_names}
            self._fused = FusedSymbolStep(
                self._symbol, self._data_names, self._label_names,
                self._param_names, self._aux_names, trainable,
                self._optimizer, mesh=self._mesh,
                compute_dtype=self._compute_dtype)
            self._fused.start(self._exec.arg_dict, self._exec.aux_dict)
        except ValueError as e:
            # optimizer class without a functional rule
            if self._fused_requested:
                raise
            self._fused = None
            self.logger.warning(
                "fused Module step unavailable (%s); falling back to the "
                "eager per-parameter update loop", e)

    def _degrade_fused(self, what):
        """Leave the fused regime for an off-script call. Loud once
        training has begun — optimizer state cannot be handed back to the
        eager Updater mid-run without changing semantics."""
        if self._fused is None:
            return
        if self._fused.num_update > 0:
            raise MXNetError(
                f"{what} is incompatible with the fused update path once "
                "training has begun; construct Module(..., fused=False)")
        self.logger.warning(
            "%s disables the fused update path; using the eager loop", what)
        self._fused = None

    # -- compute --------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        """(reference: module.py:570). In the fused regime a training
        forward only stashes the batch; the whole fwd+bwd+update runs as
        one XLA program in update()."""
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        feed = {}
        for (name, _), arr in zip(self._data_shapes, data_batch.data):
            feed[name] = arr
        if self._label_shapes and data_batch.label:
            for (name, _), arr in zip(self._label_shapes, data_batch.label):
                feed[name] = arr
        # shape change (bucketing-style) → reshape executor
        for name, arr in feed.items():
            if tuple(self._exec.arg_dict[name].shape) != tuple(arr.shape):
                new_shapes = {n: tuple(a.shape) for n, a in feed.items()}
                self._exec = self._exec.reshape(**new_shapes)
                if self._fused is not None and \
                        getattr(self._fused, "_metric_rules", None):
                    # in-step metric templates/instance counts are
                    # per-shape: fold what's counted, re-attach lazily
                    from .. import metric_device
                    metric_device.flush_and_detach(self._fused)
                break
        self._fused_outs_live = False
        if is_train and self._fused is not None:
            import jax.numpy as jnp
            self._fused_feed = {
                n: (a._data if isinstance(a, nd.NDArray)
                    else jnp.asarray(a)) for n, a in feed.items()}
            self._exec.outputs = []  # stale until update() or get_outputs()
            mon = getattr(self, "_monitor", None)
            if mon is not None and getattr(mon, "activated", False):
                # monitored batch: extra tapped fwd+bwd at pre-update
                # params (observation only — the training step still
                # runs fused)
                if self._params_dirty:
                    self._sync_params_from_devices()
                self._exec.forward(is_train=True, **feed)
                self._exec.backward()
            return
        if self._fused is not None and self._params_dirty:
            # eval/predict between fused steps: executor arrays are stale
            self._sync_params_from_devices()
        self._exec.forward(is_train=is_train, **feed)

    def backward(self, out_grads=None):
        """(reference: module.py:627)"""
        assert self.binded and self.params_initialized
        if self._fused is not None and out_grads is not None:
            self._degrade_fused("backward(out_grads=...)")
        if self._fused is not None and self._fused_feed is not None:
            return  # implicit-loss backward happens inside the fused step
        if self._fused is None and self._fused_feed is not None:
            # just degraded with a batch pending: materialize the forward
            self._exec.forward(is_train=True, **self._fused_feed)
            self._fused_feed = None
        self._exec.backward(out_grads=out_grads)

    def update(self):
        """(reference: module.py:629-651)"""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if self._fused is not None:
            if self._fused_feed is None:
                raise MXNetError(
                    "update() without a pending training forward; call "
                    "forward(batch, is_train=True) first (fused path)")
            opt = self._optimizer
            nu = self._fused.num_update + 1
            lr = opt.lr_scheduler(nu) if opt.lr_scheduler is not None \
                else opt.lr
            outs = self._fused.step(self._fused_feed, lr)
            self._fused_feed = None
            opt.num_update = self._fused.num_update
            from ..ndarray.ndarray import _wrap
            self._exec.outputs = [_wrap(o) for o in outs]
            self._fused_outs_live = True
            mon = getattr(self, "_monitor", None)
            if mon is not None and getattr(mon, "activated", False):
                # Monitor.toc reads the eager executor's arg arrays after
                # update (reference: monitor.py toc) — give it the
                # POST-step weights, not the stale pre-step copies
                self._sync_params_from_devices()
            return
        if self._kvstore is not None and self._update_on_kvstore:
            for i, name in enumerate(self._param_names):
                if self._grad_dict_req(name) == "null":
                    continue
                self._kvstore.push(i, self._exec.grad_dict[name],
                                   priority=-i)
                self._kvstore.pull(i, self._exec.arg_dict[name],
                                   priority=-i)
            return
        for i, name in enumerate(self._param_names):
            if self._grad_dict_req(name) == "null" or \
                    name in self._fixed_param_names:
                continue
            self._updater(i, self._exec.grad_dict[name],
                          self._exec.arg_dict[name])

    def _grad_dict_req(self, name):
        req = self._exec.grad_req
        return req.get(name, "null") if isinstance(req, dict) else req

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused is not None and self._fused_feed is not None and \
                not self._exec.outputs:
            # outputs requested between forward() and update(): run the
            # plain forward on current (synced) params
            if self._params_dirty:
                self._sync_params_from_devices()
            self._exec.forward(is_train=True, **self._fused_feed)
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        """(reference: module.py:736). get_outputs() materializes the
        forward when called between a fused forward() and update().

        On the fused path the update is NON-BLOCKING for supported
        metrics: counters accumulate on device along the step's async
        dependency chain and sync only when the metric is read
        (Speedometer interval / epoch log) — metric_device.py."""
        label_dict = dict(zip(self._label_names, labels or []))
        if self._fused is not None and self._exec.outputs and \
                getattr(self, "_fused_outs_live", False):
            # only when these outputs came from a fused TRAIN step —
            # in-step counters advance once per step, so eval/eager
            # forwards must take the synchronous path
            from .. import metric_device
            if metric_device.inline_update(
                    self._fused, eval_metric, label_dict,
                    dict(zip(self._output_names, self._exec.outputs))):
                return
        eval_metric.update_dict(
            label_dict,
            dict(zip(self._output_names, self.get_outputs())))

    def install_monitor(self, mon):
        """Attach a Monitor WITHOUT leaving the fused regime: batches
        inside the monitor interval additionally run the tapped
        interpreted forward on the eager executor (pre-update params,
        the same activations the reference's callback sees —
        monitor.py:33 is interval-based there too); every other batch
        stays on the compiled fused step."""
        assert self.binded
        self._monitor = mon
        mon.install(self._exec)

    # -- optimizer state io ----------------------------------------------------
    def save_optimizer_states(self, fname):
        """(reference: module.py:759)"""
        assert self.optimizer_initialized
        from ..base import atomic_write
        if self._fused is not None:
            with atomic_write(fname) as fout:
                fout.write(self._fused.get_states())
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with atomic_write(fname) as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        """(reference: module.py:777)"""
        assert self.optimizer_initialized
        if self._fused is not None:
            with open(fname, "rb") as f:
                self._fused.set_states(f.read())
        elif self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())

    def as_predictor(self, buckets=None, compute_dtype=None, **kwargs):
        """Freeze this trained module into a ``serving.Predictor`` —
        inference-only jitted program per batch bucket, params staged
        once, fusion pass applied (serving/predictor.py). The module
        keeps training; the predictor owns copies."""
        from ..serving import Predictor
        return Predictor.from_module(self, buckets=buckets,
                                     compute_dtype=compute_dtype,
                                     **kwargs)

    def reshape(self, data_shapes, label_shapes=None):
        """(reference: module.py:448)"""
        assert self.binded
        self._data_shapes = _norm_shapes(data_shapes)
        self._label_shapes = _norm_shapes(label_shapes)
        kwargs = dict(self._data_shapes + self._label_shapes)
        self._exec = self._exec.reshape(**kwargs)
        self._copy_params_to_exec(refresh_fused=False)
