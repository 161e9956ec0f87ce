"""BaseModule: the high-level training interface.

TPU-native rebuild of ``mxnet.module.base_module`` (reference:
python/mxnet/module/base_module.py — fit :376, score :194,
forward_backward :189, predict :238).
"""
from __future__ import annotations

import logging
import os
import time
import warnings

import numpy as np

from .. import metric as metric_mod
from .. import io as io_mod
from ..base import MXNetError, as_list as _as_list
from ..model import BatchEndParam

__all__ = ["BaseModule"]


def _check_input_names(symbol, names, typename, throw):
    """(reference: base_module.py:33)"""
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith("_weight")
                      and not arg.endswith("_bias")
                      and not arg.endswith("_gamma")
                      and not arg.endswith("_beta")]
        msg = (f"\033[91mYou created Module with Module(..., "
               f"{typename}_names={names}) but input with name '{name}' is "
               f"not found in symbol.list_arguments(). Did you mean one of:"
               f"\n\t{candidates}\033[0m")
        if throw:
            raise ValueError(msg)
        warnings.warn(msg)


class BaseModule:
    """(reference: base_module.py:63)"""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- abstract API ---------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def bind(self, *args, **kwargs):
        raise NotImplementedError

    def init_params(self, *args, **kwargs):
        raise NotImplementedError

    def init_optimizer(self, *args, **kwargs):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    # -- derived convenience ---------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    def forward_backward(self, data_batch):
        """(reference: base_module.py:189)"""
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        """Evaluate on eval_data (reference: base_module.py:194)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric,
                                       locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """(reference: base_module.py:277)"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad]
                       for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False,
                sparse_row_id_fn=None):
        """(reference: base_module.py:310)"""
        from .. import ndarray as nd
        assert self.binded and self.params_initialized
        if isinstance(eval_data, (np.ndarray,)) or hasattr(eval_data, "_data"):
            eval_data = io_mod.NDArrayIter(eval_data)
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad]
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches, as num of outputs is not the " \
                    "same in mini-batches. Maybe bucketing is used?"
            output_list2 = [
                nd.concat(*[out[i] for out in output_list], dim=0)
                for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None,
            checkpoint_manager=None, auto_resume=False):
        """The full training loop (reference: base_module.py:376).

        ``checkpoint_manager`` (a ``mx.checkpoint.CheckpointManager`` or a
        directory path) saves the FULL training state — params, optimizer
        state, epoch cursor, RNG stream, metric values — atomically at
        every epoch end; ``auto_resume=True`` restores the newest *valid*
        checkpoint before training, skipping every completed epoch (a
        corrupt/torn newest checkpoint falls back to the previous one).
        """
        from .. import initializer as init_mod
        assert num_epoch is not None, "please specify number of epochs"
        if initializer is None:
            initializer = init_mod.Uniform(0.01)
        if checkpoint_manager is None and auto_resume:
            raise ValueError(
                "fit(auto_resume=True) needs checkpoint_manager= (a "
                "CheckpointManager or a checkpoint directory path)")
        if isinstance(checkpoint_manager, (str, bytes, os.PathLike)):
            from ..checkpoint import CheckpointManager
            checkpoint_manager = CheckpointManager(checkpoint_manager)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label, for_training=True,
                  force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        # async host data pipeline (MXTPU_DATA_PIPELINE, auto-on):
        # read-ahead decode + double-buffered device staging around the
        # train iterator; the batch stream is byte-identical to the
        # unwrapped iterator (data/pipeline.py). The wrapper also gives
        # any iterator the checkpointable-cursor protocol at the
        # pipeline level.
        from ..data import maybe_wrap_for_fit
        train_data, _owned_pipe = maybe_wrap_for_fit(train_data, self)

        if checkpoint_manager is not None and auto_resume:
            resumed = checkpoint_manager.restore(self)
            if resumed is not None:
                begin_epoch = max(begin_epoch, resumed.epoch)
                self.logger.info(
                    "Auto-resume from checkpoint '%s': continuing at "
                    "epoch %d", resumed.path, begin_epoch)
                ds = resumed.data_state
                if ds is not None and \
                        callable(getattr(train_data, "set_state", None)):
                    # restore the DATA position too: the saved cursor is
                    # the end-of-epoch state from before the crash, so
                    # replay the epoch-end reset() the killed run never
                    # ran — the next epoch's stream matches an
                    # uninterrupted job exactly
                    try:
                        train_data.set_state(ds)
                        train_data.reset()
                        self.logger.info(
                            "Auto-resume restored the data cursor "
                            "(epoch %s, batch %s)", ds.get("epoch"),
                            ds.get("batch"))
                    except (ValueError, NotImplementedError) as e:
                        # cursor saved for a different iterator regime
                        # (e.g. MXTPU_DATA_PIPELINE toggled between
                        # save and resume): params still resume; the
                        # data stream restarts from a fresh epoch —
                        # loudly, never silently mis-applied
                        self.logger.warning(
                            "Auto-resume could not restore the data "
                            "cursor (%s); the input stream restarts "
                            "from a fresh epoch", e)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        try:
            self._fit_loop(train_data, eval_data, eval_metric,
                           validation_metric, epoch_end_callback,
                           batch_end_callback, eval_end_callback,
                           eval_batch_end_callback, monitor,
                           sparse_row_id_fn, begin_epoch, num_epoch,
                           checkpoint_manager)
        finally:
            if _owned_pipe is not None:
                # fit created the pipeline: join its threads even when
                # training dies mid-epoch (Ctrl-C, fault drills) so the
                # process never hangs on a full queue
                _owned_pipe.close()

        if checkpoint_manager is not None:
            # drain an in-flight async save before returning: the caller
            # may exit immediately, and a daemon writer killed mid-write
            # would leave the final checkpoint torn; this also re-raises
            # any background save failure instead of swallowing it
            checkpoint_manager.wait()

    def _fit_loop(self, train_data, eval_data, eval_metric,
                  validation_metric, epoch_end_callback, batch_end_callback,
                  eval_end_callback, eval_batch_end_callback, monitor,
                  sparse_row_id_fn, begin_epoch, num_epoch,
                  checkpoint_manager):
        """The per-epoch training loop body of :meth:`fit` (split out so
        fit's pipeline/checkpoint lifecycle wraps it in one place).

        A :class:`~mxnet_tpu.telemetry.StepTimeline` spans the loop:
        every step's wall time is attributed across data-wait /
        H2D-staging / compile / device-step / metric+FT-sync /
        callbacks phases (the fused step attributes its inner phases
        into the same timeline; nesting subtracts, so nothing
        double-counts), each a span of ``telemetry/trace.py``, and —
        with ``MXTPU_TELEMETRY_DIR`` set — step milestones, epoch ends,
        and periodic report snapshots land in the durable event log.
        """
        from ..telemetry import StepTimeline, export as _texp
        sym_name = getattr(self._symbol, "name", None) or "module"
        tl = StepTimeline(name=f"fit:{sym_name}").activate()
        if tl.trace_id is not None:
            # propagate the run's trace to the data pipeline: its
            # source/decode/stage spans (recorded on pipeline threads)
            # join this fit's trace tree in the Chrome-trace export
            setter = getattr(train_data, "set_trace", None)
            if callable(setter):
                setter(tl.trace_id, tl.root_span_id)
        try:
            self.__fit_epochs(train_data, eval_data, eval_metric,
                              validation_metric, epoch_end_callback,
                              batch_end_callback, eval_end_callback,
                              eval_batch_end_callback, monitor,
                              sparse_row_id_fn, begin_epoch, num_epoch,
                              checkpoint_manager, tl, _texp)
        finally:
            tl.close()

    def __fit_epochs(self, train_data, eval_data, eval_metric,
                     validation_metric, epoch_end_callback,
                     batch_end_callback, eval_end_callback,
                     eval_batch_end_callback, monitor, sparse_row_id_fn,
                     begin_epoch, num_epoch, checkpoint_manager, tl,
                     _texp):
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nbatch = 0
            data_iter = iter(train_data)
            end_of_batch = False
            # open the first step's wall clock before the epoch-start
            # fetch: the initial data wait (iterator re-init, pipeline
            # warm-up) is attributed to the epoch's first step — the
            # loop's step_start below is a no-op while the step is open
            tl.step_start()
            with tl.phase("data_wait"):
                next_data_batch = next(data_iter)
            while not end_of_batch:
                data_batch = next_data_batch
                tl.step_start()
                if monitor is not None:
                    monitor.tic()
                # the outer span: the fused step's inner h2d_stage /
                # compile / dispatch phases nest inside and claim
                # their share; the eager path books it all here
                with tl.phase("device_step"):
                    self.forward_backward(data_batch)
                    self.update()
                try:
                    with tl.phase("data_wait"):
                        next_data_batch = next(data_iter)
                    self.prepare(next_data_batch,
                                 sparse_row_id_fn=sparse_row_id_fn)
                except StopIteration:
                    end_of_batch = True
                with tl.phase("metric_ft_sync"):
                    self.update_metric(eval_metric, data_batch.label)
                if monitor is not None:
                    monitor.toc_print()
                if batch_end_callback is not None:
                    batch_end_params = BatchEndParam(
                        epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                        locals=locals())
                    with tl.phase("callbacks"):
                        for callback in _as_list(batch_end_callback):
                            callback(batch_end_params)
                tl.step_end(epoch=epoch)
                nbatch += 1

            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            toc = time.time()
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))
            if _texp.enabled():
                _texp.emit_event(
                    "epoch", name=tl.name, epoch=epoch, nbatch=nbatch,
                    time_s=round(toc - tic, 4),
                    metrics={n: float(v) for n, v
                             in eval_metric.get_name_value()})

            # the reference pulls params to host and re-broadcasts every
            # epoch (base_module.py:617) to consolidate multi-device aux;
            # with the single fused device state that roundtrip is a
            # functional no-op and costs a full parameter down+up
            # transfer, so it only runs when a callback consumes the
            # host params (checkpointing). Eval paths sync lazily
            # (module.forward: _params_dirty).
            if epoch_end_callback is not None:
                arg_params_, aux_params_ = self.get_params()
                self.set_params(arg_params_, aux_params_)
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_params_, aux_params_)

            if checkpoint_manager is not None:
                # tag epoch+1 == the next epoch to run: auto_resume picks
                # it up as begin_epoch, so completed epochs never rerun.
                # The train iterator's cursor rides along so resume also
                # restores the DATA position (shuffle order, epoch,
                # batch ordinal) — data/pipeline.py protocol
                ds_fn = getattr(train_data, "get_state", None)
                try:
                    data_state = ds_fn() if callable(ds_fn) else None
                except Exception:
                    data_state = None
                checkpoint_manager.save_module(self, epoch + 1,
                                               nbatch=nbatch,
                                               eval_metric=eval_metric,
                                               data_state=data_state)

            if eval_data is not None:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                     name, val)
            train_data.reset()

    # -- misc ------------------------------------------------------------------
    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        """(reference: base_module.py:613)"""
        from .. import ndarray as nd
        arg_params, aux_params = self.get_params()
        save_dict = {f"arg:{k}": v for k, v in arg_params.items()}
        save_dict.update({f"aux:{k}": v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        """(reference: base_module.py:628)"""
        from .. import ndarray as nd
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError(f"Invalid param file {fname}")
        self.set_params(arg_params, aux_params)

    def install_monitor(self, mon):
        raise NotImplementedError

    def prepare(self, data_batch, sparse_row_id_fn=None):
        """(reference: base_module.py:356)"""

    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError

