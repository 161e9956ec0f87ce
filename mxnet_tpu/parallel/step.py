"""Fused, sharded training step.

This is the TPU-native replacement for the reference's steady-state hot loop
(SURVEY.md §3.2): GraphExecutor::RunOps pushing cached per-op engine
operations + KVStore push/pull per layer. Here the ENTIRE training step —
forward, backward, gradient reduction across the mesh, optimizer update, and
BatchNorm running-stat fold — is one XLA computation: compiled once, fully
fused, with parameter/optimizer buffers donated (zero-copy in-place update)
and cross-chip gradient reductions (psum) inserted by GSPMD exactly where
the dataflow needs them, overlapping backward compute the way the
reference's priority-ordered engine pushes did (trainer.py:190 priority=-i).
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry as _telemetry
from ..ops import attn_kernel as _attn_kernel
from ..ops import gmm_kernel as _gmm_kernel
from ..ops import moe_rows_kernel as _moe_rows_kernel
from ..ops import gdn_kernel as _gdn_kernel
from ..ops import gdn_conv_kernel as _gdn_conv_kernel
from ..ops import mhc_kernel as _mhc_kernel
from ..ops import seq as _seq
from ..ndarray.ndarray import NDArray, _wrap
from ..ops.seq import exit_weighted_ce, softmax_ce_rows
from ..telemetry import trace as _trace

__all__ = ["TrainStep", "softmax_ce_loss", "l2_loss", "exit_weighted_loss"]


def softmax_ce_loss(logits, labels):
    """Mean softmax cross entropy with integer labels (the train_imagenet
    objective; reference op: SoftmaxOutput src/operator/softmax_output.cc).

    ``logits`` is ``(rows, classes)``, ``labels`` is ``(rows,)`` with every
    value in ``[0, classes)``; the loss is computed in float32. The label's
    log-probability is picked by a compare against an iota and a masked row
    sum, not by a gather: a gather wants the logits row-major and
    materialised, a reduction fuses into a pass that already reads them
    where the projection left them. A label outside ``[0, classes)`` picks
    nothing: that row's loss is the log-sum-exp of its logits less their
    maximum, and its gradient the softmax (a gather gave NaN past the end).
    Scope ``mx_loss``, opened by the named losses themselves: a loss the
    caller brings names its own parts (``exit_weighted_loss``'s are
    ``mx_exit_head`` and ``mx_exit_gate``), and a scope around it would
    enclose them.
    """
    with jax.named_scope("mx_loss"):
        return jnp.mean(softmax_ce_rows(logits, labels))


def l2_loss(pred, target):
    with jax.named_scope("mx_loss"):
        return 0.5 * jnp.mean(
            jnp.square(pred - target.reshape(pred.shape)))


def exit_weighted_loss(beta=0.0):
    """The loss of a net that runs its stack several times and may leave
    after any pass (``PatternLM(loops=, exit_gate=True)``), over all of
    the net's outputs: the expected next-token cross entropy under the
    exit distribution, less ``beta`` times that distribution's entropy
    (``ops.seq.exit_weighted_ce``). Every pass's logits are computed,
    dropped and computed again in the backward pass, one pass at a
    time."""
    def loss(outs, labels):
        hidden, gate_logits, head_weight = outs
        return exit_weighted_ce(hidden, gate_logits, head_weight, labels,
                                beta)
    return loss


_LOSSES = {"softmax_ce": softmax_ce_loss, "l2": l2_loss}


def _remat_staged(staged):
    """Wrap the staged forward in jax.checkpoint. The inner function
    records ``_write_params`` on itself AT TRACE TIME (block.py:484), so
    the wrapper keeps a reference for the BatchNorm fold to read."""
    wrapped = jax.checkpoint(staged)
    wrapped._inner = staged
    return wrapped


def _remat_by_unit(staged):
    """Trace the staged forward with the net's recomputation units on
    (``gluon.block.remat_units``), and publish what the units keep for
    the backward pass beside their inputs: the gauges
    ``remat::saved_bytes::<unit>`` and ``remat::units``, of the step
    traced last."""
    from ..gluon.block import remat_units

    def wrapped(pvals, args, key):
        with remat_units() as units:
            out = staged(pvals, args, key)
        _telemetry.remove("remat::")
        for prefix, nbytes in units.saved.items():
            _telemetry.gauge(f"remat::saved_bytes::{prefix}").set(nbytes)
        _telemetry.gauge("remat::units").set(len(units.saved))
        return out

    wrapped._inner = staged
    return wrapped


class TrainStep:
    """One-XLA-computation training step for a HybridBlock.

    Usage::

        step = TrainStep(net, loss="softmax_ce", optimizer="sgd",
                         optimizer_params={"momentum": 0.9}, mesh=mesh)
        loss = step(x, y)          # NDArray/ndarray in, scalar out

    With a mesh, the batch is sharded over the 'data' axis and parameters
    are replicated (data parallelism); pass ``param_spec_fn`` for
    tensor-parallel parameter layouts.
    """

    def __init__(self, net, loss="softmax_ce", optimizer="sgd",
                 optimizer_params=None, mesh: Optional[Mesh] = None,
                 data_axis="data", compute_dtype=None, lr=0.01,
                 lr_schedule: Optional[Callable[[int], float]] = None,
                 param_spec_fn=None, partition_rules=None, preprocess=None,
                 remat=None):
        """``preprocess``: optional on-device fn applied to the data batch
        inside the compiled step (e.g. uint8 decode -> normalize). Keeps the
        host->device transfer small — the TPU analog of the reference doing
        mean-subtract inside the C++ iterator (iter_normalize.h).

        ``remat``: recompute activations during backward (jax.checkpoint),
        trading FLOPs for HBM — the reference's gradient mirroring
        (MXNET_BACKWARD_DO_MIRROR, graph_executor.cc mirror fn). Default
        comes from that env var via mxnet_tpu.config. ``remat="layer"``
        recomputes by unit: every block of the net marked ``_remat_unit``
        keeps its inputs and what is dear to compute twice (matrix
        products' outputs, a choice's or a sort's result, a scan's
        output: ``ops.remat``) and recomputes the rest of its insides,
        so the peak holds one unit's activations, not the net's
        (wrapping the whole forward saves nothing at the peak).

        The step takes the net's own parameter buffers, not a copy of
        them (4 bytes a parameter) and, since every call donates them,
        points the net's Parameters at the new ones after each call: the
        net is always current.

        Parameters with ``grad_req="null"`` get no gradient, no optimizer
        state and no cast to ``compute_dtype``: they are state the forward
        reads and may write (BatchNorm's statistics, a router's correction
        bias, counters)."""
        self.net = net
        self.preprocess = preprocess
        self.loss_fn = _LOSSES[loss] if isinstance(loss, str) else loss
        self._loss_named = isinstance(loss, str)
        optimizer_params = dict(optimizer_params or {})
        self.lr = optimizer_params.pop("learning_rate", lr)
        self.lr_schedule = lr_schedule
        self.wd = optimizer_params.pop("wd", 0.0)
        # any registered optimizer runs inside the fused step — the pure
        # rules live in functional_opt (the traced analog of optimizer.py)
        from . import functional_opt
        self._fopt = functional_opt.create(optimizer, **optimizer_params)
        self._opt_init = self._fopt.init
        self.mesh = mesh
        self.data_axis = data_axis
        self.compute_dtype = compute_dtype
        self._num_update = 0

        if remat is None:
            from .. import config as _config
            remat = _config.get("MXNET_BACKWARD_DO_MIRROR")
        self.remat = remat if remat == "layer" else bool(remat)

        self.param_list = net._get_param_list()
        self._trainable = [p.grad_req != "null" for p in self.param_list]
        # staged forward in training mode: fn(pvals, args, key)->(outs,writes)
        _, self._staged = net._build_jit(training=True)
        if self.remat == "layer":
            self._staged = _remat_by_unit(self._staged)
        elif self.remat:
            self._staged = _remat_staged(self._staged)
        self._pvals = None
        self._opt_state = None
        self._step_jit = None
        self._program = None     # telemetry.trace's record of the step
        self._trace_id = None    # every call's span is on one trace
        # declarative alternative to param_spec_fn: regex -> PartitionSpec
        # rules (parallel/partition.py). Explicit param_spec_fn wins; with
        # neither, rules come from MXTPU_PARTITION_RULES.
        if param_spec_fn is None and mesh is not None:
            from . import partition as _partition
            rules = (_partition.parse_rules(partition_rules)
                     if isinstance(partition_rules, str)
                     else partition_rules)
            if rules is None:
                rules = _partition.env_rules()
            if rules:
                def param_spec_fn(p, _rules=tuple(rules)):
                    shape = getattr(p, "shape", None)
                    ndim = len(shape) if shape else None
                    return _partition.spec_for(_rules, p.name, ndim=ndim)
        self._param_spec_fn = param_spec_fn

    # -- state ----------------------------------------------------------------
    def _init_state(self):
        import jax.numpy as jnp
        # the net's own buffers: every call donates them, and _call points
        # the net's Parameters at what the call returns
        pvals = tuple(p.data()._data for p in self.param_list)
        opt_state = tuple(
            self._opt_init(v) if t else ()
            for v, t in zip(pvals, self._trainable))
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            if self._param_spec_fn is not None:
                shard = [NamedSharding(self.mesh,
                                       self._param_spec_fn(p) or P())
                         for p in self.param_list]
            else:
                shard = [rep] * len(pvals)
            pvals = tuple(jax.device_put(v, s)
                          for v, s in zip(pvals, shard))
            # state leaves only inherit the param's sharding when they have
            # the param's shape; scalar leaves (e.g. adam's step counter t)
            # are replicated — a non-empty spec on a rank-0 array is invalid
            opt_state = tuple(
                tuple(jax.device_put(
                          x, s if getattr(x, "shape", None) == v.shape
                          else rep)
                      if hasattr(x, "shape") else x
                      for x in st)
                for st, s, v in zip(opt_state, shard, pvals))
        self._pvals = pvals
        self._opt_state = opt_state
        t0 = jnp.zeros((), jnp.uint32)
        if self.mesh is not None:
            t0 = jax.device_put(t0, NamedSharding(self.mesh, P()))
        self._t_dev = t0
        self._lr_cache = None

    def _build_step(self):
        staged = self._staged
        loss_fn = self.loss_fn
        first_output = self._loss_named
        fopt = self._fopt
        trainable = self._trainable
        compute_dtype = self.compute_dtype
        param_objs = self.param_list
        wd_base = self.wd
        # per-parameter multipliers are static (gluon Parameter.lr_mult /
        # wd_mult — reference: gluon/parameter.py), baked into the trace
        lr_mults = [getattr(p, "lr_mult", 1.0) for p in param_objs]
        wd_mults = [getattr(p, "wd_mult", 1.0) for p in param_objs]

        preprocess = self.preprocess

        # RNG: one base key captured at build; per-step keys are folded in
        # from the update counter INSIDE the compiled step — an eager
        # jax.random.split per step would cost a host->device dispatch
        # round trip (expensive when the chip is reached over a network)
        from .. import random as _random_mod
        base_key = _random_mod.next_key()

        def mx_train_step(pvals, opt_state, x, y, t, lr):
            # of the step traced last, like the units' gauges: the lowering
            # that follows counts the sites it gives the kernels
            _telemetry.gauge(_attn_kernel.GAUGE).set(0)
            _telemetry.gauge(_attn_kernel.FUSED_BWD_GAUGE).set(0)
            _telemetry.gauge(_attn_kernel.WINDOW_GAUGE).set(0)
            _telemetry.gauge(_gmm_kernel.GAUGE).set(0)
            _telemetry.gauge(_moe_rows_kernel.GAUGE).set(0)
            _telemetry.gauge(_gdn_kernel.GAUGE).set(0)
            _telemetry.gauge(_gdn_conv_kernel.GAUGE).set(0)
            _telemetry.gauge(_seq.MHC_GAUGE).set(0)
            _telemetry.gauge(_mhc_kernel.GAUGE).set(0)
            key = jax.random.fold_in(base_key, t)
            if preprocess is not None:
                x = preprocess(x)

            def fwd(pv):
                pv_c = pv
                if compute_dtype is not None:
                    # the masters' copies in the compute dtype, and
                    # backward the gradients' way back to float32
                    with jax.named_scope("mx_cast"):
                        pv_c = tuple(
                            v.astype(compute_dtype)
                            if v.dtype == jnp.float32 and tr else v
                            for v, tr in zip(pv, trainable))
                        x_c = x.astype(compute_dtype) \
                            if x.dtype == jnp.float32 else x
                else:
                    x_c = x
                outs, writes = staged(pv_c, (x_c,), key)
                if first_output or len(outs) == 1:
                    outs = outs[0]
                return loss_fn(outs, y), writes

            (loss, writes), grads = jax.value_and_grad(
                fwd, has_aux=True)(pvals)
            # optimizer update on trainable params only. A fusion carries
            # one name: a weight gradient that XLA fuses with its update
            # reads as one of the two (on a v5e as the product: PERF.md)
            new_p, new_s = [], []
            for i, (p, g, s, tr) in enumerate(
                    zip(pvals, grads, opt_state, trainable)):
                if tr:
                    # salt the optimizer stream: fold_in(key, i) for small i
                    # coincides with split(key)[i], which is exactly what the
                    # staged forward's dropout chain consumes
                    pkey = jax.random.fold_in(
                        jax.random.fold_in(key, 0x6F707469), i) \
                        if fopt.needs_key else None
                    with jax.named_scope("mx_opt_update"):
                        np_, ns_ = fopt.update(p, g, s, lr * lr_mults[i],
                                               t + 1, wd_base * wd_mults[i],
                                               key=pkey)
                        new_p.append(np_.astype(p.dtype))
                    new_s.append(ns_)
                else:
                    new_p.append(p)
                    new_s.append(s)
            # fold BatchNorm running-stat writes (identified at trace time)
            write_params = getattr(
                getattr(staged, "_inner", staged), "_write_params", [])
            if write_params:
                idx = {id(p): i for i, p in enumerate(param_objs)}
                for wp, wv in zip(write_params, writes):
                    i = idx.get(id(wp))
                    if i is not None:
                        new_p[i] = wv.astype(new_p[i].dtype)
            # the update counter lives ON DEVICE and advances inside the
            # step: feeding it from the host would cost one tiny transfer
            # every step
            return tuple(new_p), tuple(new_s), t + 1, loss

        donate = (0, 1, 4)
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            batch1 = NamedSharding(self.mesh, P(self.data_axis))
            # param shardings mirror _init_state
            if self._param_spec_fn is not None:
                pshard = tuple(NamedSharding(self.mesh,
                                             self._param_spec_fn(p) or P())
                               for p in self.param_list)
            else:
                pshard = tuple(rep for _ in self.param_list)
            sshard = tuple(
                tuple(ps if getattr(leaf, "shape", None)
                      == getattr(pv, "shape", None) else rep
                      for leaf in st) if st else ()
                for ps, st, pv in zip(pshard, self._opt_state, self._pvals))
            in_shardings = (pshard, sshard, batch1, batch1, rep, rep)
            # pin outputs to the same layout: without this GSPMD may pick a
            # different sharding for the updated params, forcing a reshard
            # of every parameter on every step's input boundary
            out_shardings = (pshard, sshard, rep, rep)
            self._step_jit = jax.jit(mx_train_step, donate_argnums=donate,
                                     in_shardings=in_shardings,
                                     out_shardings=out_shardings)
        else:
            self._step_jit = jax.jit(mx_train_step, donate_argnums=donate)

    # -- public ---------------------------------------------------------------
    def __call__(self, x, y):
        # tracing is asked for once a call; the call and what it holds
        # are spans of telemetry/trace.py, every call on one trace
        on = _trace.enabled()
        if on and self._trace_id is None:
            self._trace_id = _trace.new_trace_id()
        with _trace.span("step", "step", trace=self._trace_id, on=on,
                         args={"step": self._num_update + 1}):
            return self._call(x, y, on)

    def _call(self, x, y, on):
        first = self._step_jit is None
        if first:
            # set-up's parts by name, beside Module's setup/bind and
            # setup/init_optimizer (aggregates prof::setup::*)
            with _trace.span("compile", "step", on=on):
                if self._pvals is None:
                    with _trace.span("materialize", "setup", on=on):
                        self._materialize(x)
                    with _trace.span("init_state", "setup", on=on):
                        self._init_state()
                with _trace.span("build_step", "setup", on=on):
                    self._build_step()
        xa = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        ya = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        if self.mesh is not None:
            with _trace.span("h2d_stage", "step", on=on):
                batch = NamedSharding(self.mesh, P(self.data_axis))
                xa = jax.device_put(xa, batch)
                ya = jax.device_put(ya, batch)
        lr = self.lr if self.lr_schedule is None \
            else self.lr_schedule(self._num_update)
        # cache the lr device scalar (it changes rarely; shipping a fresh
        # host scalar per step costs a transfer round trip)
        if self._lr_cache is None or self._lr_cache[0] != lr:
            self._lr_cache = (lr, jnp.asarray(lr, jnp.float32))
        args = (self._pvals, self._opt_state, xa, ya, self._t_dev,
                self._lr_cache[1])
        if first:
            # the first call traces and compiles, or loads from JAX's
            # persistent cache: a plain jax.jit, so the compile registry
            # is told of the acquisition, it does not make it; what is
            # inside it, JAX's trace, lowering and compile or load, is
            # compile_report()["jax"]'s row mx_train_step
            from ..compile import registry as _creg
            shapes = _trace.shapes_of(args)     # the call donates args
            with _trace.span("compile", "step", on=on):
                with _creg.jit_acquire("mx_train_step", "train_step", args):
                    out = self._step_jit(*args)
                # the one reference scope_table() is built from when
                # somebody asks: the call's own trace, found again (no
                # second trace; JAX reports the lookup as a trace of
                # microseconds)
                self._program = _trace.note_program(
                    "jit_mx_train_step", self._step_jit.trace(*shapes))
        else:
            # on the host an enqueue; once the runtime's limit of
            # executions in flight is reached it blocks for a device step
            with _trace.span("dispatch", "step", on=on):
                out = self._step_jit(*args)
        self._pvals, self._opt_state, self._t_dev, loss = out
        for p, v in zip(self.param_list, self._pvals):
            p._data._data = v
        self._num_update += 1
        return _wrap(loss)

    def _materialize(self, x):
        """Ensure deferred params are materialized (one eager forward
        if needed)."""
        try:
            for p in self.param_list:
                p._check_and_get()
        except Exception:
            from .. import autograd as _ag
            xa = x._data if isinstance(x, NDArray) else jnp.asarray(x)
            xa1 = xa[:1]
            if self.preprocess is not None:
                # the eager materialization forward must see the same
                # dtype/layout the compiled step computes on
                xa1 = self.preprocess(xa1)
            with _ag.train_mode():
                self.net.forward(_wrap(xa1))
            self.param_list = self.net._get_param_list()
            self._trainable = [p.grad_req != "null"
                               for p in self.param_list]

    def scope_table(self):
        """``{HLO instruction name: scope path}`` of this step's compiled
        program (``telemetry.trace.scope_table``), or None before the
        first call. Built when first asked for; a step costs nothing for
        it."""
        return None if self._program is None else self._program.table()

    def sync_params(self):
        """Nothing to do: the net's Parameters hold the step's buffers
        after every call. Kept for its callers."""

    @property
    def num_update(self):
        return self._num_update
