"""Symbol: the declarative graph API.

TPU-native rebuild of ``mxnet.symbol`` (reference: python/mxnet/symbol/
symbol.py — composition, infer_shape :933, simple_bind :1279, bind :1543,
tojson/save :1186-1212, load :2498; native graph src/nnvm/, 3rdparty/nnvm).

Architectural mapping: the reference's NNVM graph + pass pipeline
(InferShape/PlanMemory/Gradient) is replaced by *tracing the symbol's
evaluation function through JAX* — shape inference is ``jax.eval_shape``,
memory planning is XLA's, and gradients are ``jax.grad`` of the traced
evaluation. The Symbol object itself remains a real, serializable DAG so
reference-format JSON round-trips.
"""
from __future__ import annotations

import contextlib
import json
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError
from ..ops import get_op, has_op
from ..ops.registry import _OPS, parse_attr
from .op_info import op_input_names

__all__ = ["Symbol", "var", "Variable", "Group", "load", "load_json"]


import itertools as _itertools

_node_uid = _itertools.count()


class _Node:
    """One graph node (op or variable)."""

    __slots__ = ("op", "name", "attrs", "inputs", "num_outputs",
                 "user_attrs", "uid")

    def __init__(self, op, name, attrs=None, inputs=(), num_outputs=1,
                 user_attrs=None):
        self.op = op  # None for variables
        self.name = name
        self.attrs = dict(attrs or {})
        self.inputs = list(inputs)  # list of (Node, out_index)
        self.num_outputs = num_outputs
        self.user_attrs = dict(user_attrs or {})
        self.uid = next(_node_uid)  # stable RNG salt, same in sub-evals


class Symbol:
    """A node-output handle in the symbolic graph (reference:
    symbol.py:56)."""

    def __init__(self, node: _Node, out_index: int = 0,
                 outputs: Optional[List["Symbol"]] = None):
        self._node = node
        self._out_index = out_index
        self._group = outputs  # for Group symbols

    # -- identity ------------------------------------------------------------
    @property
    def name(self):
        if self._group is not None:
            return None
        return self._node.name

    @property
    def output_name(self):
        """Reference naming: op outputs are '{name}_output[i]'
        (symbol.py list_outputs convention)."""
        node = self._node
        if node.op is None:
            return node.name
        if node.num_outputs > 1:
            return f"{node.name}_output{self._out_index}"
        return f"{node.name}_output"

    def __repr__(self):
        if self._group is not None:
            names = ", ".join(s.name or "?" for s in self._group)
            return f"<Symbol group [{names}]>"
        return f"<Symbol {self.name}>"

    def attr(self, key):
        return self._node.user_attrs.get(key)

    def attr_dict(self):
        """{node_name: attrs} over the graph (reference: symbol.py:331)."""
        ret = {}
        for node in self._topo_nodes():
            if node.user_attrs:
                ret[node.name] = {k: str(v)
                                  for k, v in node.user_attrs.items()}
        return ret

    def _set_attr(self, **kwargs):
        self._node.user_attrs.update(kwargs)

    # -- graph walk ----------------------------------------------------------
    def _roots(self):
        return [s._node for s in self._group] if self._group is not None \
            else [self._node]

    def _topo_nodes(self) -> List[_Node]:
        seen = {}
        order = []

        def visit(node):
            if id(node) in seen:
                return
            seen[id(node)] = node
            for parent, _ in node.inputs:
                visit(parent)
            order.append(node)

        for r in self._roots():
            visit(r)
        return order

    def list_arguments(self):
        """Variable (argument) names in topo order (reference:
        symbol.py:779)."""
        return [n.name for n in self._topo_nodes()
                if n.op is None and not n.attrs.get("__is_aux__")]

    def list_auxiliary_states(self):
        """(reference: symbol.py:826)"""
        return [n.name for n in self._topo_nodes()
                if n.op is None and n.attrs.get("__is_aux__")]

    def list_outputs(self):
        if self._group is not None:
            return [name for s in self._group for name in s.list_outputs()]
        return [self.output_name]

    def get_internals(self):
        """A group over every node output (reference: symbol.py:460)."""
        outs = []
        for node in self._topo_nodes():
            for i in range(node.num_outputs):
                outs.append(Symbol(node, i))
        return Group(outs)

    def get_children(self):
        if not self._node.inputs:
            return None
        return Group([Symbol(p, i) for p, i in self._node.inputs])

    def __getitem__(self, index):
        if self._group is not None:
            if isinstance(index, str):
                for s in self._group:
                    if index in (s.name, s.output_name):
                        return s
                raise ValueError(f"no output named {index}")
            return self._group[index]
        if isinstance(index, str):
            internals = self.get_internals()
            return internals[index]
        outs = [Symbol(self._node, i)
                for i in range(self._node.num_outputs)]
        return outs[index]

    def __iter__(self):
        if self._group is not None:
            return iter(self._group)
        return iter([Symbol(self._node, i)
                     for i in range(self._node.num_outputs)])

    def __len__(self):
        if self._group is not None:
            return len(self._group)
        return self._node.num_outputs

    # -- composition sugar ----------------------------------------------------
    def _binop(self, op_name, other, rev=False):
        from . import _symbol_op
        if isinstance(other, Symbol):
            a, b = (other, self) if rev else (self, other)
            return _symbol_op(op_name, [a, b], {})
        scalar_ops = {
            "broadcast_add": "_plus_scalar", "broadcast_sub":
            ("_rminus_scalar" if rev else "_minus_scalar"),
            "broadcast_mul": "_mul_scalar", "broadcast_div":
            ("_rdiv_scalar" if rev else "_div_scalar"),
            "broadcast_power":
            ("_rpower_scalar" if rev else "_power_scalar"),
        }
        return _symbol_op(scalar_ops[op_name], [self], {"scalar": other})

    def __add__(self, other):
        return self._binop("broadcast_add", other)

    __radd__ = __add__

    def __sub__(self, other):
        return self._binop("broadcast_sub", other)

    def __rsub__(self, other):
        return self._binop("broadcast_sub", other, rev=True)

    def __mul__(self, other):
        return self._binop("broadcast_mul", other)

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binop("broadcast_div", other)

    def __rtruediv__(self, other):
        return self._binop("broadcast_div", other, rev=True)

    def __pow__(self, other):
        return self._binop("broadcast_power", other)

    def __neg__(self):
        from . import _symbol_op
        return _symbol_op("negative", [self], {})

    # -- fluent methods (reference: symbol.py fluent-method codegen) ---------
    def _unop(self, op_name, **attrs):
        from . import _symbol_op
        return _symbol_op(op_name, [self],
                          {k: v for k, v in attrs.items() if v is not None})

    def reshape(self, *shape, **kwargs):
        # accepts reshape((2, 3)), reshape([2, 3]), reshape(2, 3) and
        # reshape(shape=(2, 3)) like the reference fluent API
        if "shape" in kwargs:
            shape = kwargs.pop("shape")
        elif len(shape) == 1:
            shape = shape[0]
        if isinstance(shape, int):
            shape = (shape,)
        return self._unop("Reshape", shape=tuple(shape), **kwargs)

    def flatten(self):
        return self._unop("Flatten")

    def transpose(self, axes=None):
        return self._unop("transpose", axes=axes)

    def swapaxes(self, dim1, dim2):
        return self._unop("SwapAxis", dim1=dim1, dim2=dim2)

    def expand_dims(self, axis):
        return self._unop("expand_dims", axis=axis)

    def squeeze(self, axis=None):
        return self._unop("squeeze", axis=axis)

    def astype(self, dtype):
        return self._unop("Cast", dtype=str(np.dtype(dtype)))

    def sum(self, axis=None, keepdims=False):
        return self._unop("sum", axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        return self._unop("mean", axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        return self._unop("max", axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        return self._unop("min", axis=axis, keepdims=keepdims)

    def clip(self, a_min, a_max):
        return self._unop("clip", a_min=a_min, a_max=a_max)

    def slice_axis(self, axis, begin, end):
        return self._unop("slice_axis", axis=axis, begin=begin, end=end)

    # -- evaluation ----------------------------------------------------------
    def _output_symbols(self):
        return list(self._group) if self._group is not None else [self]

    def eval_arrays(self, arg_arrays: Dict[str, "np.ndarray"],
                    training=False, rng_key=None, device_map=None,
                    preset=None):
        """Evaluate outputs given raw arrays for every variable."""
        outs, _ = self.eval_arrays_ex(arg_arrays, training, rng_key,
                                      device_map=device_map,
                                      preset=preset)
        return outs

    def build_device_map(self, group2ctx, default_device=None):
        """{node_name: jax.Device} from ``__ctx_group__`` annotations +
        a group->Context mapping (the PlaceDevice pass, reference
        graph_executor.cc:406; AttrScope(ctx_group=...) attribute.py)."""
        dmap = {}
        known = set(group2ctx or ())
        for node in self._topo_nodes():
            grp = node.user_attrs.get("__ctx_group__")
            if grp is not None:
                if grp not in known:
                    raise MXNetError(
                        f"node '{node.name}' is annotated with "
                        f"ctx_group='{grp}' but group2ctx only maps "
                        f"{sorted(known)}")
                dmap[node.name] = group2ctx[grp].jax_device
            elif default_device is not None:
                dmap[node.name] = default_device
        return dmap

    @staticmethod
    def _apply_node_op(node, ins, training, rng_key, scoped=True):
        """Dispatch ONE op node on resolved input values — the single
        place that parses attrs and injects training flags / per-node
        RNG keys. Shared by the eager walk (eval_arrays_ex) and the
        segmented walk (_make_segment_fn): the two must stay
        bit-identical (same uid fold salt, same BN semantics) or the
        Monitor's tapped pass diverges from training. Returns
        (outs tuple, parsed attrs).

        The one site, too, that names a graph's work in a compiled
        program: with ``scoped`` the operator runs under the scope
        ``mx_op_<node.op>`` (``telemetry.trace.scope_table``), unless its
        registration says that it names its own parts (a scope around
        them would change the paths their readers match). The eager walk
        of a group2ctx Executor, which runs every step, asks for none."""
        import jax
        from ..ops.registry import get_op
        attrs = {k: parse_attr(v) for k, v in node.attrs.items()
                 if not k.startswith("__")}
        opdef = get_op(node.op)
        if node.op in ("BatchNorm", "BatchNorm_v1", "Dropout", "RNN",
                       "_FusedBNReLUConv", "_FusedBNReLUConvK"):
            attrs["training"] = training
        if node.op in ("Dropout", "RNN") and training:
            base = rng_key if rng_key is not None \
                else jax.random.PRNGKey(0)
            # salt by the node's uid (not topo index): sub-graph evals
            # (implicit-loss recompute) then draw the SAME key per node,
            # so forward and backward see identical dropout masks
            attrs["key"] = jax.random.fold_in(base, node.uid % (2 ** 31))
        innames = node.attrs.get("__input_names__")
        with jax.named_scope("mx_op_" + node.op) \
                if scoped and not opdef.names_its_parts \
                else contextlib.nullcontext():
            if innames:
                res = opdef.fn(**dict(zip(parse_attr(innames), ins)),
                               **attrs)
            else:
                res = opdef.fn(*ins, **attrs)
        return (res if isinstance(res, tuple) else (res,)), attrs

    @staticmethod
    def _bn_aux_updates(node, outs, attrs, training, resolve_var):
        """[(aux var name, new value)] BatchNorm running-stat folds
        (functional form of the reference's in-place aux mutation,
        batch_norm.cc). ``resolve_var(p)`` -> the variable's current
        value. Shared by both graph walkers. ``_FusedBNReLUConv``
        (ops/pallas_fused.py) mirrors BatchNorm's layout — moving stats
        at input positions 3/4, batch stats at outputs 1/2 — exactly so
        this fold applies to it unchanged."""
        if not training or node.op not in (
                "BatchNorm", "BatchNorm_v1", "_FusedBNReLUConv",
                "_FusedBNReLUConvK") \
                or attrs.get("use_global_stats"):
            return []
        momentum = attrs.get("momentum", 0.9)
        ups = []
        for pos, stat_idx in ((3, 1), (4, 2)):
            p, _ = node.inputs[pos]
            if p.op is None:
                old = resolve_var(p)
                ups.append((p.name,
                            momentum * old +
                            (1 - momentum) * outs[stat_idx]))
        return ups

    def eval_arrays_ex(self, arg_arrays: Dict[str, "np.ndarray"],
                      training=False, rng_key=None, internals=None,
                      device_map=None, preset=None):
        """Evaluate; returns (outputs, aux_updates).

        ``preset``: optional ``{(id(node), out_idx): value}`` seed for
        the evaluation cache — the parameter-expression hoisting hook
        (symbol/passes/hoist.py): a preset output short-circuits its
        whole subgraph, so variables only reachable through it need not
        appear in ``arg_arrays``.

        ``internals``: optional dict filled with every op node's outputs
        keyed ``{node.name}_output`` — the Monitor tap point (reference:
        GraphExecutor::SetMonitorCallback graph_executor.cc:121).

        ``training`` reaches training-aware ops (BatchNorm batch stats,
        Dropout active); each stochastic node draws a key folded from
        ``rng_key``. ``aux_updates`` maps aux var name → new value (BatchNorm
        running stats), the functional form of the reference's in-place aux
        mutation (batch_norm.cc).

        ``device_map``: optional {node_name: jax.Device} from a group2ctx
        bind (the PlaceDevice pass, reference graph_executor.cc:406).
        Inputs crossing into a differently-placed node get a
        ``jax.device_put`` — the ``_CrossDeviceCopy`` analog — and eager
        dispatch then runs each op where its data lives. Only valid
        OUTSIDE jit (the group2ctx Executor path runs unjitted)."""
        import jax
        import jax.numpy as jnp
        cache: Dict[tuple, object] = dict(preset) if preset else {}
        aux_updates: Dict[str, object] = {}

        def node_out(node, idx):
            key = (id(node), idx)
            if key in cache:
                return cache[key]
            if node.op is None:
                if node.name not in arg_arrays:
                    raise MXNetError(
                        f"missing argument '{node.name}' for eval")
                val = arg_arrays[node.name]
                cache[key] = val
                return val
            ins = [node_out(p, i) for p, i in node.inputs]
            if device_map is not None:
                dev = device_map.get(node.name)
                if dev is not None:
                    ins = [jax.device_put(v, dev) for v in ins]
            outs, attrs = Symbol._apply_node_op(
                node, ins, training, rng_key, scoped=device_map is None)
            for i, o in enumerate(outs):
                cache[(id(node), i)] = o
                if internals is not None:
                    suffix = "_output" if i == 0 else f"_output{i}"
                    internals[node.name + suffix] = o
            for name, val in Symbol._bn_aux_updates(
                    node, outs, attrs, training,
                    lambda p: node_out(p, 0)):
                aux_updates[name] = val
            return cache[key]

        outputs = [node_out(s._node, s._out_index)
                   for s in self._output_symbols()]
        return outputs, aux_updates

    # -- segmented (jit-per-device) evaluation --------------------------------
    def build_segment_plan(self, device_map, extra_outputs=()):
        """Partition the graph into contiguous same-device segments for
        the group2ctx Executor: each segment jit-compiles as one XLA
        program pinned (by input placement) to its device, with
        ``device_put`` transfers only at segment boundaries — the
        compiled analog of the reference's per-device execution plan +
        _CrossDeviceCopy (graph_executor.cc:406). The old fallback ran
        every op eagerly (per-op dispatch).

        ``extra_outputs``: additional (node, idx) values to surface
        (the implicit-loss head inputs, so fwd_loss composes without a
        second graph walk). Returns an opaque plan consumed by
        ``eval_segmented``."""
        op_nodes = [n for n in self._topo_nodes() if n.op is not None]
        segs = []
        cur_dev, cur = object(), None
        for n in op_nodes:
            dev = device_map.get(n.name)
            if cur is None or dev is not cur_dev:
                cur = []
                segs.append((dev, cur))
                cur_dev = dev
            cur.append(n)
        node_seg = {}
        for si, (_d, ns) in enumerate(segs):
            for n in ns:
                node_seg[id(n)] = si
        want = [(s._node, s._out_index) for s in self._output_symbols()]
        want += [(n, i) for n, i in extra_outputs]
        needed = {}          # (id(node), idx) -> (node, idx)
        for n, i in want:
            if n.op is not None:
                needed[(id(n), i)] = (n, i)
        # one pass: last segment consuming each value (topo order makes
        # the final assignment the max) — keeps the plan O(edges)
        last_consumer = {}
        for si, (_d, ns) in enumerate(segs):
            for m in ns:
                for q, j in m.inputs:
                    last_consumer[(id(q), j)] = si
        plan_segs = []
        for si, (dev, ns) in enumerate(segs):
            in_keys, out_keys, var_names = [], [], []
            seen_in = set()
            inside = {id(n) for n in ns}
            for n in ns:
                for p, i in n.inputs:
                    k = (id(p), i)
                    if p.op is None:
                        if p.name not in var_names:
                            var_names.append(p.name)
                    elif id(p) not in inside and k not in seen_in:
                        seen_in.add(k)
                        in_keys.append(k)
                for i in range(max(n.num_outputs, 1)):
                    k = (id(n), i)
                    if last_consumer.get(k, -1) > si or k in needed:
                        out_keys.append(k)
            plan_segs.append({"dev": dev, "nodes": ns,
                              "in_keys": in_keys, "out_keys": out_keys,
                              "var_names": var_names, "jit": {}})
        return {"segs": plan_segs, "want": want}

    def _make_segment_fn(self, seg, training):
        """(fn, aux_names): pure fn(invals, varvals, key) ->
        (outvals, aux_update_vals ordered by aux_names)."""
        nodes = seg["nodes"]
        in_keys = list(seg["in_keys"])
        out_keys = list(seg["out_keys"])
        var_names = list(seg["var_names"])
        aux_names = ()
        if training:
            names = set()
            for n in nodes:
                if n.op not in ("BatchNorm", "BatchNorm_v1",
                                "_FusedBNReLUConv", "_FusedBNReLUConvK"):
                    continue
                attrs = {k: parse_attr(v) for k, v in n.attrs.items()
                         if not k.startswith("__")}
                if attrs.get("use_global_stats"):
                    continue
                for pos in (3, 4):
                    p, _i = n.inputs[pos]
                    if p.op is None:
                        names.add(p.name)
            aux_names = tuple(sorted(names))

        def fn(invals, varvals, key):
            env = dict(zip(in_keys, invals))
            vmap = dict(zip(var_names, varvals))
            aux_up = {}
            for node in nodes:
                ins = [vmap[p.name] if p.op is None else env[(id(p), i)]
                       for p, i in node.inputs]
                outs, attrs = Symbol._apply_node_op(node, ins, training,
                                                    key)
                for i, o in enumerate(outs):
                    env[(id(node), i)] = o
                for name, val in Symbol._bn_aux_updates(
                        node, outs, attrs, training,
                        lambda p: vmap[p.name]):
                    aux_up[name] = val
            return (tuple(env[k] for k in out_keys),
                    tuple(aux_up[k] for k in aux_names))

        return fn, aux_names

    def eval_segmented(self, plan, arg_arrays, training=False,
                       rng_key=None):
        """Run a build_segment_plan: jitted segment programs with
        device_put transfers between; returns (wanted values in plan
        order, aux_updates)."""
        import jax
        env = {}
        aux_updates = {}
        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)
        for seg in plan["segs"]:
            entry = seg["jit"].get(training)
            if entry is None:
                raw, aux_names = self._make_segment_fn(seg, training)
                entry = (jax.jit(raw), aux_names)
                seg["jit"][training] = entry
            jf, aux_names = entry
            dev = seg["dev"]

            def place(v):
                return jax.device_put(v, dev) if dev is not None else v

            invals = tuple(place(env[k]) for k in seg["in_keys"])
            varvals = []
            for nm in seg["var_names"]:
                if nm not in arg_arrays:
                    raise MXNetError(
                        f"missing argument '{nm}' for eval")
                varvals.append(place(arg_arrays[nm]))
            outs, aux_vals = jf(invals, tuple(varvals), rng_key)
            env.update(zip(seg["out_keys"], outs))
            aux_updates.update(zip(aux_names, aux_vals))
        out = []
        for n, i in plan["want"]:
            if n.op is None:
                out.append(arg_arrays[n.name])
            else:
                out.append(env[(id(n), i)])
        return out, aux_updates

    def eval_dict(self, arg_dict):
        """Evaluate with NDArray inputs → NDArray outputs (autograd-aware:
        the whole graph records as one tape node)."""
        from ..ndarray.ndarray import NDArray, _invoke_fn
        names = [n for n in self.list_arguments() +
                 self.list_auxiliary_states() if n in arg_dict]
        nds = [arg_dict[n] for n in names]

        def fn(*arrays):
            amap = dict(zip(names, arrays))
            return tuple(self.eval_arrays(amap))

        res = _invoke_fn(f"symbol_{id(self)}", fn, list(nds))
        return list(res) if isinstance(res, tuple) else [res]

    def infer_shape(self, *args, **kwargs):
        """Infer shapes via jax.eval_shape (reference: symbol.py:933; native
        InferShape pass infer_graph_attr_pass.cc:325).

        Returns (arg_shapes, out_shapes, aux_shapes)."""
        return self._infer_shape_impl(False, *args, **kwargs)

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        known: Dict[str, tuple] = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    known[n] = tuple(s)
        known.update({k: tuple(v) for k, v in kwargs.items()
                      if v is not None})
        shapes, node_out_shapes = self._propagate_shapes(known)
        arg_shapes = [shapes.get(n) for n in arg_names]
        aux_shapes = [shapes.get(n) for n in aux_names]
        out_shapes = [node_out_shapes.get((id(s._node), s._out_index))
                      for s in self._output_symbols()]
        if not partial and any(s is None for s in arg_shapes + out_shapes):
            missing = [n for n, s in zip(arg_names, arg_shapes) if s is None]
            raise MXNetError(
                f"infer_shape incomplete; unknown: {missing}. Provide input "
                "shapes for all data variables.")
        return arg_shapes, out_shapes, aux_shapes

    def _propagate_shapes(self, known: Dict[str, tuple]):
        """Best-effort forward shape propagation from known variable
        shapes — the InferShape walk (reference:
        infer_graph_attr_pass.cc:325) shared by ``infer_shape`` and the
        fusion rewrite pass (fusion.py). Returns ``(var_shapes,
        node_out_shapes)`` where the latter maps ``(id(node), out_idx)``
        to a shape tuple for every node it could resolve."""
        import jax
        # propagate forward symbolically: give unknown args a placeholder by
        # deferring — we solve layer-by-layer like the reference's InferShape
        shapes = dict(known)
        nodes = self._topo_nodes()
        node_out_shapes: Dict[tuple, tuple] = {}

        def try_node(node):
            if node.op is None:
                if node.name in shapes:
                    node_out_shapes[(id(node), 0)] = shapes[node.name]
                elif "__shape__" in node.attrs:
                    # Variable(shape=...) declared its own shape
                    # (reference: mx.sym.var shape kwarg seeds InferShape).
                    # 0 means unknown-dim in the reference convention —
                    # only fully-known shapes may seed, else eval_shape
                    # would happily propagate zero-sized arrays
                    s = tuple(parse_attr(node.attrs["__shape__"]))
                    if all(int(d) > 0 for d in s):
                        shapes[node.name] = s
                        node_out_shapes[(id(node), 0)] = s
                return
            in_shapes = []
            for p, i in node.inputs:
                s = node_out_shapes.get((id(p), i))
                in_shapes.append(s)
            opdef = get_op(node.op)
            attrs = {k: parse_attr(v) for k, v in node.attrs.items()
                     if not k.startswith("__")}
            # infer missing weight-shaped inputs from the op semantics by
            # using shape hints (deferred like gluon); only FullyConnected/
            # Convolution/BatchNorm-style ops need this
            if any(s is None for s in in_shapes):
                hinted = _hint_param_shapes(node, in_shapes, attrs)
                if hinted:
                    for (p, i), s in hinted.items():
                        node_out_shapes[(id(p), i)] = s
                        if p.op is None:
                            shapes[p.name] = s
                    in_shapes = [node_out_shapes.get((id(p), i))
                                 for p, i in node.inputs]
            if any(s is None for s in in_shapes):
                return
            try:
                sds = [jax.ShapeDtypeStruct(s, np.float32)
                       for s in in_shapes]
                innames = node.attrs.get("__input_names__")
                if innames:
                    innames = parse_attr(innames)
                    out = jax.eval_shape(
                        lambda *xs: opdef.fn(**dict(zip(innames, xs)),
                                             **attrs), *sds)
                else:
                    out = jax.eval_shape(
                        lambda *xs: opdef.fn(*xs, **attrs), *sds)
            except Exception:
                return
            outs = out if isinstance(out, tuple) else (out,)
            for i, o in enumerate(outs):
                node_out_shapes[(id(node), i)] = tuple(o.shape)

        for node in nodes:
            try_node(node)
        return shapes, node_out_shapes

    def infer_type(self, *args, **kwargs):
        arg_names = self.list_arguments()
        dt = np.float32
        return ([dt] * len(arg_names),
                [dt] * len(self._output_symbols()),
                [dt] * len(self.list_auxiliary_states()))

    # -- binding -------------------------------------------------------------
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    stype_dict=None, group2ctx=None, shared_arg_names=None,
                    shared_exec=None, shared_buffer=None, **kwargs):
        """Allocate arrays and bind (reference: symbol.py:1279;
        GraphExecutor::Init graph_executor.cc:951)."""
        from ..executor import Executor
        from .. import ndarray as nd
        arg_shapes, _, aux_shapes = self.infer_shape(**kwargs)
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        # group2ctx: variables annotated with ctx_group live on their
        # group's device (reference: symbol.py:1280-1429 simple_bind
        # group2ctx -> PlaceDevice); ungrouped ones on the default ctx
        var_ctx = {}
        if group2ctx:
            for node in self._topo_nodes():
                if node.op is None:
                    grp = node.user_attrs.get("__ctx_group__")
                    if grp is not None and grp in group2ctx:
                        var_ctx[node.name] = group2ctx[grp]

        def _alloc(n, s):
            return nd.zeros(s, ctx=var_ctx.get(n, ctx))

        args = {}
        for n, s in zip(arg_names, arg_shapes):
            if shared_buffer is not None and n in shared_buffer:
                args[n] = shared_buffer[n]
            else:
                args[n] = _alloc(n, s)
                if shared_buffer is not None:
                    shared_buffer[n] = args[n]
        args_grad = {}
        if grad_req != "null":
            for n, s in zip(arg_names, arg_shapes):
                args_grad[n] = _alloc(n, s)
        aux_states = {n: _alloc(n, s)
                      for n, s in zip(aux_names, aux_shapes)}
        return Executor(self, ctx, args, args_grad, grad_req, aux_states,
                        group2ctx=group2ctx)

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        """(reference: symbol.py:1543)"""
        from ..executor import Executor
        arg_names = self.list_arguments()
        if isinstance(args, (list, tuple)):
            args = dict(zip(arg_names, args))
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(arg_names, args_grad))
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(self.list_auxiliary_states(), aux_states))
        return Executor(self, ctx, args or {}, args_grad, grad_req,
                        aux_states or {}, group2ctx=group2ctx)

    def eval(self, ctx=None, **kwargs):
        return self.bind(ctx, kwargs, grad_req="null").forward()

    def grad(self, wrt):  # pragma: no cover - legacy
        raise NotImplementedError(
            "Symbol.grad was removed in the reference too; bind with "
            "args_grad and call backward")

    # -- serialization (MXNet JSON graph format) ------------------------------
    def tojson(self):
        """Serialize to the reference's JSON graph format
        (reference: symbol.py:1212; format legacy_json_util.cc)."""
        nodes = self._topo_nodes()
        idx = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            jnodes.append({
                "op": n.op if n.op is not None else "null",
                "name": n.name,
                "attrs": {k: str(v) for k, v in n.attrs.items()
                          if not k.startswith("__")},
                "inputs": [[idx[id(p)], i, 0] for p, i in n.inputs],
            })
        arg_nodes = [i for i, n in enumerate(nodes) if n.op is None]
        heads = [[idx[id(s._node)], s._out_index, 0]
                 for s in self._output_symbols()]
        return json.dumps({
            "nodes": jnodes,
            "arg_nodes": arg_nodes,
            "node_row_ptr": list(range(len(nodes) + 1)),
            "heads": heads,
            "attrs": {"mxnet_version": ["int", 10100]},
        }, indent=2)

    def save(self, fname):
        from ..base import atomic_write
        with atomic_write(fname, mode="w") as f:
            f.write(self.tojson())

    # util parity
    def debug_str(self):
        lines = []
        for n in self._topo_nodes():
            op = n.op or "Variable"
            ins = ", ".join(f"{p.name}[{i}]" for p, i in n.inputs)
            lines.append(f"{op}({ins}) -> {n.name}")
        return "\n".join(lines)


def _hint_param_shapes(node, in_shapes, attrs):
    """Infer weight/bias/aux shapes for layer ops from the data shape —
    the per-op analog of the reference's FInferShape functions."""
    if not node.inputs or in_shapes[0] is None:
        return None
    data_shape = in_shapes[0]
    hints = {}
    names, _ = op_input_names(node.op)
    if node.op == "FullyConnected":
        num_hidden = int(attrs.get("num_hidden"))
        flatten = attrs.get("flatten", True)
        in_units = int(np.prod(data_shape[1:])) if flatten \
            else data_shape[-1]
        want = {"weight": (num_hidden, in_units), "bias": (num_hidden,)}
    elif node.op in ("Convolution", "Deconvolution"):
        kernel = attrs.get("kernel")
        num_filter = int(attrs.get("num_filter"))
        num_group = int(attrs.get("num_group", 1))
        kernel = tuple(kernel) if isinstance(kernel, (tuple, list)) \
            else (kernel,)
        cin = data_shape[1]
        if node.op == "Convolution":
            want = {"weight": (num_filter, cin // num_group) + kernel,
                    "bias": (num_filter,)}
        else:
            want = {"weight": (cin, num_filter // num_group) + kernel,
                    "bias": (num_filter,)}
    elif node.op in ("BatchNorm", "BatchNorm_v1", "LayerNorm",
                     "InstanceNorm"):
        axis = int(attrs.get("axis", 1 if node.op != "LayerNorm" else -1))
        c = data_shape[axis]
        want = {"gamma": (c,), "beta": (c,), "moving_mean": (c,),
                "moving_var": (c,)}
    elif node.op in ("Embedding", "_contrib_SparseEmbedding"):
        want = {"weight": (int(attrs.get("input_dim")),
                           int(attrs.get("output_dim")))}
    elif node.op in ("SoftmaxOutput", "Softmax", "SVMOutput"):
        # label shape = data shape without the class axis (softmax_output.cc
        # FInferShape); multi_output keeps trailing spatial dims
        if attrs.get("multi_output"):
            want = {"label": (data_shape[0],) + tuple(data_shape[2:])}
        else:
            want = {"label": tuple(data_shape[:-1])}
    elif node.op in ("LinearRegressionOutput", "LogisticRegressionOutput",
                     "MAERegressionOutput"):
        want = {"label": tuple(data_shape)}
    elif node.op == "RNN":
        # flat cuDNN-layout parameter vector + (L*dirs, N, H) states from
        # the (T, N, C) data shape (reference: rnn-inl.h GetRnnParamSize)
        from ..ops.nn import rnn_param_size
        h = int(attrs.get("state_size"))
        layers = int(attrs.get("num_layers", 1))
        bi = bool(attrs.get("bidirectional", False))
        mode = attrs.get("mode", "lstm")
        dirs = 2 if bi else 1
        n = rnn_param_size(mode, layers, data_shape[2], h, bi)
        st = (layers * dirs, data_shape[1], h)
        want = {"parameters": (n,), "state": st, "state_cell": st}
    else:
        return None
    if names:
        for pos, nm in enumerate(names[:len(node.inputs)]):
            if in_shapes[pos] is None and nm in want:
                p, i = node.inputs[pos]
                hints[(p, i)] = want[nm]
        # aux inputs follow arg inputs in node.inputs
        for pos in range(len(names), len(node.inputs)):
            if in_shapes[pos] is None:
                p, i = node.inputs[pos]
                aux_nm = p.name.rsplit("_", 1)[-1]
                full = "moving_" + aux_nm if not aux_nm.startswith("moving") \
                    else aux_nm
                for cand in (full, "moving_mean", "moving_var"):
                    if cand in want:
                        hints[(p, i)] = want[cand]
                        break
    return hints


def var(name, attr=None, shape=None, lr_mult=None, wd_mult=None, dtype=None,
        init=None, stype=None, **kwargs):
    """Create a variable symbol (reference: symbol.py:2425)."""
    attrs = {}
    if shape is not None:
        attrs["__shape__"] = str(tuple(shape))
    node = _Node(None, name, attrs=attrs)
    if init is not None:
        # user_attrs reach Module.init_params via attr_dict -> InitDesc's
        # __init__ override (initializer.py:96); instances serialize as
        # dumps() JSON so constructor args survive (reference stores
        # init.dumps() the same way)
        node.user_attrs["__init__"] = init if isinstance(init, str) \
            else init.dumps()
    if attr:
        node.user_attrs.update(attr)
    from ..attribute import apply_scope_attrs
    apply_scope_attrs(node)
    for k, v in kwargs.items():
        if k.startswith("__") and k.endswith("__"):
            node.user_attrs[k] = v
    if lr_mult is not None:
        node.user_attrs["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        node.user_attrs["__wd_mult__"] = str(wd_mult)
    return Symbol(node)


Variable = var


def Group(symbols: Sequence[Symbol]):
    """Group outputs into one symbol (reference: symbol.py:2482)."""
    flat = []
    for s in symbols:
        flat.extend(s._output_symbols())
    g = Symbol(flat[0]._node, 0, outputs=flat)
    return g


def load_json(json_str: str) -> Symbol:
    """Parse the reference JSON graph format (reference: symbol.py:2540)."""
    data = json.loads(json_str)
    jnodes = data["nodes"]
    nodes: List[_Node] = []
    aux_markers = set()
    # first pass: find aux inputs by op signature
    for jn in jnodes:
        opname = jn["op"]
        if opname != "null":
            names, aux = op_input_names(opname)
            if names is not None and aux:
                n_args = len(names)
                for pos, (nid, out_i, _) in enumerate(jn["inputs"]):
                    if pos >= n_args:
                        aux_markers.add(nid)
    for i, jn in enumerate(jnodes):
        opname = jn["op"]
        attrs = jn.get("attrs", jn.get("param", {})) or {}
        if opname == "null":
            node = _Node(None, jn["name"], attrs=dict(attrs))
            if i in aux_markers:
                node.attrs["__is_aux__"] = True
        else:
            if not has_op(opname):
                raise MXNetError(f"op '{opname}' in JSON graph is not "
                                 "registered")
            opdef = get_op(opname)
            from . import _node_num_outputs
            parsed = {k: parse_attr(v) for k, v in attrs.items()}
            node = _Node(opname, jn["name"], attrs=dict(attrs),
                         inputs=[(nodes[nid], out_i)
                                 for nid, out_i, _ in jn["inputs"]],
                         num_outputs=_node_num_outputs(opname, opdef,
                                                       parsed))
        nodes.append(node)
    heads = data.get("heads", [[len(nodes) - 1, 0, 0]])
    outs = [Symbol(nodes[nid], out_i) for nid, out_i, _ in heads]
    if len(outs) == 1:
        return outs[0]
    return Group(outs)


def load(fname) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())
