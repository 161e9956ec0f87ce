"""Gluon Block / HybridBlock.

TPU-native rebuild of ``mxnet.gluon.block`` (reference:
python/mxnet/gluon/block.py — Block :123, HybridBlock :376, SymbolBlock :599,
hybridize :332, cache build ``_build_cache`` :436).

Architectural mapping: the reference's ``hybridize()`` traces the network into
a ``CachedOp`` (an NNVM graph JIT that still dispatches per-op to the engine,
src/imperative/cached_op.cc:342). Here ``hybridize()`` stages the whole
forward into ONE ``jax.jit`` computation — XLA fuses the graph, so the TPU
version is strictly stronger (kernel fusion, not just dispatch removal).
Training state (BatchNorm running stats) and RNG (Dropout) are threaded
functionally through the jitted computation and applied after each call.
"""
from __future__ import annotations

import copy
import re
import threading
from collections import OrderedDict

import numpy as np

from .. import autograd
from .. import ndarray as nd_module
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray, _wrap
from .parameter import (DeferredInitializationError, Parameter, ParameterDict)

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope:
    """Name manager for Blocks (reference: block.py:30-85)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Create prefix and params for a new Block."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from ..name import NameManager
                prefix = NameManager.current.get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        self._name_scope = None
        from ..name import Prefix
        self._name_scope = Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        if self._name_scope is not None:
            self._name_scope.__exit__(ptype, value, trace)
            self._name_scope = None
        _BlockScope._current.value = self._old_scope


class Block:
    """Base class for all neural network layers and models
    (reference: block.py:123-374)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): {_indent(repr(block), 2)}"
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        """Register parameters and child blocks (reference: block.py:180)."""
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)) and \
                    not isinstance(existing, type(value)):
                raise TypeError(
                    f"Changing attribute type for {self.name} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or \
                self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value
            # directly-assigned Parameters also live in the ParameterDict so
            # sharing via params= sees them (reference: block.py __setattr__)
            self._params._params.setdefault(value.name, value)
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """Returns a name-scope context manager (reference: block.py:237)."""
        return self._scope

    @property
    def params(self):
        """The Block's ParameterDict — the live dict (with its shared-dict
        link intact), not a copy, so ``params=other.collect_params()``
        sharing works (reference: block.py:245)."""
        return self._params

    def collect_params(self, select=None):
        """ParameterDict of this Block and all children
        (reference: block.py:252)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret._params.update(
                {name: value for name, value in self.params.items()
                 if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        """Register a child block (reference: block.py:304)."""
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def apply(self, fn):
        """Apply fn recursively to every child and self
        (reference: block.py:318)."""
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer as _init
        init = init if init is not None else _init.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        """Cast parameters and children (reference: block.py:357)."""
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def hybridize(self, active=True, **kwargs):
        """No-op on plain Blocks; recurses (reference: block.py:348)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    # -- checkpoint ----------------------------------------------------------
    def save_parameters(self, filename):
        """Save parameters to file using *structural* names — portable across
        prefixes (reference: block.py save_parameters)."""
        params = self._collect_params_with_prefix()
        from ..ndarray import save as nd_save
        nd_save(filename, {k: v._check_and_get() for k, v in params.items()})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        from ..ndarray import load as nd_load
        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise IOError(
                        f"Parameter '{name}' is missing in file '{filename}'")
        for name, v in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise IOError(
                        f"Parameter '{name}' loaded from file '{filename}' is "
                        "not present in this Block")
                continue
            params[name].set_data(v)

    # legacy prefix-keyed forms (reference: block.py save_params/load_params)
    def save_params(self, filename):
        self.collect_params().save(filename, strip_prefix=self.prefix)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        self.collect_params().load(filename, ctx, allow_missing, ignore_extra,
                                   self.prefix)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- forward -------------------------------------------------------------
    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, *args):
        """Override to implement the computation (reference: block.py:373)."""
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary table (reference: block.py summary)."""
        rows = []

        def hook(block, depth):
            for name, child in block._children.items():
                n_params = sum(
                    int(np.prod(p.shape)) for p in child.params.values()
                    if p.shape_is_known())
                rows.append(("  " * depth + child.__class__.__name__ +
                             f"({child.name})", n_params))
                hook(child, depth + 1)

        total = sum(int(np.prod(p.shape))
                    for p in self.collect_params().values()
                    if p.shape_is_known())
        rows.append((self.__class__.__name__ + f"({self.name})", total))
        hook(self, 1)
        width = max(len(r[0]) for r in rows) + 4
        lines = [f"{'Layer':<{width}}Params", "-" * (width + 8)]
        for name, n in rows:
            lines.append(f"{name:<{width}}{n}")
        print("\n".join(lines))


def _indent(s, num_spaces):
    lines = s.split("\n")
    if len(lines) == 1:
        return s
    first = lines.pop(0)
    return first + "\n" + "\n".join(" " * num_spaces + line for line in lines)


# ---------------------------------------------------------------------------
# Trace-time functional state (BatchNorm running stats, Dropout RNG)
# ---------------------------------------------------------------------------
class _TraceState:
    """Collects parameter writes made during a jit trace so they become
    functional outputs of the compiled graph (the reference mutates aux
    states in-place inside the engine; XLA requires the functional form)."""

    _current = threading.local()

    def __init__(self):
        self.writes = OrderedDict()  # param -> traced value

    @staticmethod
    def active():
        return getattr(_TraceState._current, "value", None)


class remat_units:
    """While active, blocks marked ``_remat_unit`` checkpoint their call
    inside a staged forward (``TrainStep(remat="layer")``). ``saved``:
    unit prefix -> the bytes that unit holds for its backward pass
    beside its inputs (``ops.remat``), once the forward is traced.
    ``trips``: how often what is being traced now will run (a scanned
    body's trace stands for every trip: ``nn.HybridLoop`` sets it)."""

    def __init__(self):
        self.saved = {}
        self.trips = 1

    def __enter__(self):
        self._prev = getattr(_TraceState._current, "remat_units", None)
        _TraceState._current.remat_units = self
        return self

    def __exit__(self, *exc):
        _TraceState._current.remat_units = self._prev


def stateful_write(param, value):
    """Write an NDArray/array into a Parameter, trace-aware.

    In eager mode this mutates the parameter immediately; inside a
    hybridized (jitted) forward the write is recorded and applied with the
    concrete value after the compiled call returns.
    """
    data = value._data if isinstance(value, NDArray) else value
    tr = _TraceState.active()
    if tr is not None:
        tr.writes[param] = data
    else:
        param._check_and_get()._data = data


_sym_trace_vars = threading.local()


class HybridBlock(Block):
    """A Block that can be staged into a single XLA computation
    (reference: block.py:376-598; CachedOp analog src/imperative/cached_op.cc).
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = {}
        self._cached_graph = {}
        self._cached_param_list = None

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Activate staged (jit) execution (reference: block.py:332).

        static_alloc/static_shape are accepted for API parity; XLA always
        plans memory statically, so they are implied.
        """
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._clear_cached_op()
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def _clear_cached_op(self):
        self._cached_graph = {}
        self._cached_param_list = None

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def register_child(self, block, name=None):
        super().register_child(block, name)
        self._clear_cached_op()

    def infer_shape(self, *args):
        """Infer parameter shapes from inputs for deferred initialization.

        Built-in layers override this; custom HybridBlocks with 0-dim
        parameter shapes must too. (The reference infers via the symbolic
        graph, block.py:470; with XLA the layer-local rule is equivalent and
        avoids a second tracing machinery.)
        """
        raise NotImplementedError(
            f"{self.__class__.__name__} has parameters with unknown shape. "
            "Override infer_shape() to support deferred initialization, or "
            "construct with fully-specified shapes.")

    def infer_type(self, *args):
        for p in self._reg_params.values():
            p.dtype = args[0].dtype

    def _gather_params(self):
        out = {}
        for name, p in self._reg_params.items():
            out[name] = p.data()
        return out

    def _finish_deferred(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init:
                p._finish_deferred_init()

    #: a block that sets this is one unit of recomputation: while a
    #: staged forward is traced under ``remat_units()`` its call runs
    #: under ``jax.checkpoint``, so the backward pass keeps the unit's
    #: inputs and what its operators name (``ops.remat.kept``) and
    #: recomputes the rest of its insides
    _remat_unit = False

    def __call__(self, *args):
        from ..symbol.symbol import Symbol as _Sym
        if args and isinstance(args[0], _Sym):
            return self.forward(*args)
        if self._active and _TraceState.active() is None:
            return self._call_cached(*args)
        if self._remat_unit and _TraceState.active() is not None:
            units = getattr(_TraceState._current, "remat_units", None)
            if units is not None:
                return self._call_remat(units, *args)
        return self.forward(*args)

    def _call_remat(self, units, *args):
        """``forward`` under ``jax.checkpoint``, which keeps what
        ``ops.remat`` says. Parameter writes made inside
        (``stateful_write``) leave the checkpointed function as outputs
        and are handed to the trace around it."""
        import jax
        from ..ops import remat
        outer = _TraceState.active()
        seen = {}

        def unit(*arrays):
            inner = _TraceState()
            _TraceState._current.value = inner
            try:
                out = self.forward(*[_wrap(a) for a in arrays])
            finally:
                _TraceState._current.value = outer
            outs = out if isinstance(out, tuple) else (out,)
            seen["params"] = list(inner.writes)
            seen["tuple"] = isinstance(out, tuple)
            return tuple(o._data for o in outs), tuple(inner.writes.values())

        # traced once: the jaxpr says what the unit keeps, then runs
        arrays = [a._data for a in args]
        traced, shapes = jax.make_jaxpr(
            jax.checkpoint(unit, policy=remat.POLICY),
            return_shape=True)(*arrays)
        units.saved[self.prefix] = units.saved.get(self.prefix, 0) \
            + units.trips * remat.kept_bytes(traced.jaxpr)
        outs, writes = jax.tree.unflatten(
            jax.tree.structure(shapes),
            jax.core.eval_jaxpr(traced.jaxpr, traced.consts, *arrays))
        for p, w in zip(seen["params"], writes):
            outer.writes[p] = w
        outs = tuple(_wrap(o) for o in outs)
        return outs if seen["tuple"] else outs[0]

    def forward(self, x, *args):
        """Gather this block's params and defer to ``hybrid_forward``
        (reference: block.py:541-560).

        When ``x`` is a Symbol the forward composes the symbolic graph
        instead: parameters become variables named by their full name, with
        grad_req=='null' ones marked auxiliary (the reference builds this
        graph in _get_graph, block.py:468)."""
        from ..symbol.symbol import Symbol as _Sym
        if isinstance(x, _Sym):
            from .. import symbol as sym_module
            from ..symbol.symbol import var as _sym_var
            cache = getattr(_sym_trace_vars, "vars", None)
            if cache is None:
                # direct net(symbol) call outside _trace_symbol: dedupe
                # variables per thread so a Parameter shared by two blocks
                # maps to ONE node (two same-named nodes confuse bind)
                if not hasattr(_sym_trace_vars, "fallback"):
                    _sym_trace_vars.fallback = {}
                cache = _sym_trace_vars.fallback
            params = {}
            for name, p in self._reg_params.items():
                v = cache.get(p.name)
                if v is not None and \
                        bool(v._node.attrs.get("__is_aux__")) != \
                        (p.grad_req == "null"):
                    # grad_req classification changed since the node was
                    # cached: mint a fresh node rather than mutating one
                    # embedded in previously built graphs
                    v = None
                if v is None:
                    v = _sym_var(p.name)
                    if p.grad_req == "null":
                        v._node.attrs["__is_aux__"] = True
                    cache[p.name] = v
                params[name] = v
            return self.hybrid_forward(sym_module, x, *args, **params)
        try:
            params = self._gather_params()
        except DeferredInitializationError:
            self._finish_deferred(x, *args)
            params = self._gather_params()
        return self.hybrid_forward(nd_module, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        """Override to implement the computation. ``F`` is the op namespace
        (``mxnet_tpu.nd``; the same code also runs under jit tracing because
        every op is a pure jax function)."""
        raise NotImplementedError

    # -- staged execution -----------------------------------------------------
    def _get_param_list(self):
        if self._cached_param_list is None:
            self._cached_param_list = [
                p for _, p in sorted(self.collect_params().items())]
        return self._cached_param_list

    def _build_jit(self, training):
        import jax

        block = self
        param_list = self._get_param_list()

        def staged(pvals, arg_arrays, key):
            from .. import random as _random
            saved = [(p._data._data if p._data is not None else None)
                     for p in param_list]
            tr = _TraceState()
            _TraceState._current.value = tr
            prev_r = autograd.set_recording(False)
            prev_t = autograd.set_training(training)
            _random.push_trace_key(key)
            try:
                for p, v in zip(param_list, pvals):
                    p._data._data = v
                out = block.forward(*[_wrap(a) for a in arg_arrays])
            finally:
                _random.pop_trace_key()
                autograd.set_training(prev_t)
                autograd.set_recording(prev_r)
                _TraceState._current.value = None
                for p, v in zip(param_list, saved):
                    if p._data is not None:
                        p._data._data = v
            outs = out if isinstance(out, tuple) else (out,)
            out_arrays = tuple(o._data for o in outs)
            write_params = list(tr.writes.keys())
            write_vals = tuple(tr.writes[p] for p in write_params)
            staged._write_params = write_params
            return out_arrays, write_vals

        return jax.jit(staged), staged

    def _call_cached(self, *args):
        import jax.numpy as jnp
        from .. import random as _random

        nd_args = [a if isinstance(a, NDArray) else _wrap(jnp.asarray(a))
                   for a in args]
        param_list = self._get_param_list()
        # deferred init: run shape inference against these inputs first
        needs_init = any(p._deferred_init for p in param_list)
        if needs_init:
            try:
                for p in param_list:
                    p._check_and_get()
            except DeferredInitializationError:
                # one eager forward completes all nested deferred inits
                out = self.forward(*nd_args)
                self._cached_param_list = None
                param_list = self._get_param_list()
                return out

        training = autograd.is_training()
        recording = autograd.is_recording()
        cache_key = (training,)
        if cache_key not in self._cached_graph:
            self._cached_graph[cache_key] = self._build_jit(training)
        jitted, raw = self._cached_graph[cache_key]

        pvals = tuple(p.data()._data for p in param_list)
        arg_arrays = tuple(a._data for a in nd_args)
        key = _random.next_key()

        if recording:
            n_p = len(pvals)

            def closed(*flat):
                outs, writes = jitted(flat[:n_p], flat[n_p:], key)
                return outs + tuple(writes)

            import jax
            all_out, vjp_fn = jax.vjp(closed, *(pvals + arg_arrays))
            write_params = getattr(raw, "_write_params", [])
            n_main = len(all_out) - len(write_params)
            out_nds = [_wrap(o) for o in all_out[:n_main]]
            write_nds = [_wrap(o) for o in all_out[n_main:]]
            node = autograd.TapeNode(vjp_fn, param_list + nd_args,
                                     len(all_out), self.name, fn=closed)
            for i, o in enumerate(out_nds + write_nds):
                o._node = node
                o._node_index = i
            node.outputs = out_nds + write_nds
            # TapeNode.parents must be the NDArray wrappers of the inputs
            node.parents = [p.data() for p in param_list] + nd_args
            with autograd.pause():
                for p, w in zip(write_params, write_nds):
                    p._check_and_get()._data = w._data
        else:
            outs, writes = jitted(pvals, arg_arrays, key)
            write_params = getattr(raw, "_write_params", [])
            out_nds = [_wrap(o) for o in outs]
            for p, w in zip(write_params, writes):
                p._check_and_get()._data = w
        return out_nds[0] if len(out_nds) == 1 else tuple(out_nds)

    def export(self, path, epoch=0, num_inputs=1):
        """Export to ``<path>-symbol.json`` + ``<path>-NNNN.params``
        (reference: block.py:590 export — the symbol/params pair that
        Module.load / mx.model.load_checkpoint consumes).

        The graph is traced symbolically (inference mode); parameters are
        classified into ``arg:``/``aux:`` keys via the traced symbol's
        list_arguments/list_auxiliary_states, falling back to the
        grad_req=='null' aux convention for params the trace didn't touch.
        """
        sym = self._trace_symbol(num_inputs=num_inputs)
        sym.save(f"{path}-symbol.json")
        aux_names = set(sym.list_auxiliary_states())
        arg_names = set(sym.list_arguments())
        params = {}
        for name, p in self.collect_params().items():
            if name in aux_names:
                key = "aux:" + name
            elif name in arg_names:
                key = "arg:" + name
            else:
                key = ("aux:" if p.grad_req == "null" else "arg:") + name
            params[key] = p._check_and_get()
        from ..ndarray import save as nd_save
        nd_save(f"{path}-{epoch:04d}.params", params)
        return sym

    def _trace_symbol(self, num_inputs=1):
        """Trace this block into a Symbol graph (inference mode).

        Input variables are named ``data`` (single input) or ``data0..N``,
        matching the reference's export convention."""
        from ..symbol.symbol import var as _sym_var
        if num_inputs == 1:
            inputs = [_sym_var("data")]
        else:
            inputs = [_sym_var(f"data{i}") for i in range(num_inputs)]
        _sym_trace_vars.vars = {}
        prev_t = autograd.set_training(False)
        prev_r = autograd.set_recording(False)
        try:
            out = self.forward(*inputs)
        finally:
            autograd.set_recording(prev_r)
            autograd.set_training(prev_t)
            _sym_trace_vars.vars = None
        if isinstance(out, tuple):
            from ..symbol.symbol import Group
            return Group([o for o in out])
        return out


class SymbolBlock(HybridBlock):
    """Construct a block from a Symbol (reference: block.py:599).

    Implemented with the symbol layer in ``mxnet_tpu.symbol``.
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        from .. import symbol as _sym
        from .parameter import ParameterDict
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        self._outputs = outputs
        self._inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        # graph params keep their raw symbol names — no block prefix
        # (reference: block.py SymbolBlock uses the unprefixed shared dict)
        self._params = ParameterDict("", shared=self._params._shared
                                     if params is None else params)
        input_names = {i.name for i in self._inputs}
        for name in outputs.list_arguments():
            if name not in input_names:
                self._reg_params[name] = self.params.get(
                    name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            self._reg_params[name] = self.params.get(
                name, grad_req="null", allow_deferred_init=True)

    def forward(self, *args):
        arg_dict = {i.name: a for i, a in zip(self._inputs, args)}
        for name, p in self._reg_params.items():
            arg_dict[name] = p.data()
        res = self._outputs.eval_dict(arg_dict)
        return res[0] if len(res) == 1 else tuple(res)

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError
