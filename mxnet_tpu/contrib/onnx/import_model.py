"""ONNX graph -> Symbol converter.

TPU-native rebuild of the reference importer (reference:
python/mxnet/contrib/onnx/_import/import_model.py, _import/import_onnx.py,
_import/import_helper.py op mapping). The converter walks the ONNX graph in
topological order, mapping each node onto the registered op surface;
initializer tensors become arg_params.

The ``onnx`` package is only needed to *parse* .onnx files
(``import_model``); ``import_onnx_graph`` accepts any object with the
GraphProto structure (node/input/output/initializer), so converted graphs
and the op mapping are testable without the dependency.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

__all__ = ["import_model", "import_onnx_graph"]


def _attr_value(a):
    """Decode an AttributeProto-shaped object to a python value."""
    if hasattr(a, "type"):
        # real onnx AttributeProto: type enum selects the field
        t = a.type
        mapping = {1: "f", 2: "i", 3: "s", 4: "t", 6: "floats", 7: "ints"}
        field = mapping.get(t)
        if field:
            v = getattr(a, field)
            if field == "s":
                return v.decode() if isinstance(v, bytes) else v
            if field in ("floats", "ints"):
                return tuple(v)
            return v
    for field in ("ints", "floats"):
        v = getattr(a, field, None)
        if v:
            return tuple(v)
    for field in ("i", "f", "s"):
        if getattr(a, field, None) is not None:
            v = getattr(a, field)
            return v.decode() if isinstance(v, bytes) else v
    raise ValueError(f"cannot decode ONNX attribute {a!r}")


def _attrs(node) -> Dict:
    return {a.name: _attr_value(a) for a in getattr(node, "attribute", ())}


def _tensor_to_np(t):
    """TensorProto-shaped -> numpy."""
    if hasattr(t, "raw_data") and getattr(t, "raw_data", b""):
        # decode locally — onnx.numpy_helper would reject the vendored
        # subset's message class anyway (different descriptor type).
        # TensorProto.DataType enum values from the ONNX IR spec.
        _DT = {1: np.float32, 2: np.uint8, 3: np.int8, 4: np.uint16,
               5: np.int16, 6: np.int32, 7: np.int64, 9: np.bool_,
               10: np.float16, 11: np.float64, 12: np.uint32,
               13: np.uint64}
        code = getattr(t, "data_type", 1)
        if code == 16:  # bfloat16: numpy via ml_dtypes (jax dependency)
            import ml_dtypes
            return np.frombuffer(
                t.raw_data, ml_dtypes.bfloat16).reshape(tuple(t.dims))
        if code not in _DT:  # e.g. 8=string: no numpy dtype
            try:  # a real TensorProto may still decode via onnx itself
                from onnx import numpy_helper
                return numpy_helper.to_array(t)
            except Exception:
                pass
            raise NotImplementedError(
                f"ONNX tensor {getattr(t, 'name', '?')!r}: data_type "
                f"{code} raw_data is not supported")
        return np.frombuffer(t.raw_data, _DT[code]).reshape(tuple(t.dims))
    for field, dt in (("float_data", np.float32), ("int64_data", np.int64),
                      ("int32_data", np.int32), ("double_data", np.float64)):
        data = list(getattr(t, field, ()) or ())
        if data:
            return np.asarray(data, dt).reshape(tuple(t.dims))
    if hasattr(t, "array"):
        return np.asarray(t.array)
    raise ValueError(f"cannot decode ONNX tensor {getattr(t, 'name', t)!r}")


def _sym_pads(attrs, ndim, op_name):
    """ONNX pads are (begin..., end...); the op surface takes one symmetric
    value per spatial dim — reject silent truncation of asymmetric pads."""
    pads = tuple(attrs.get("pads", (0,) * 2 * ndim))
    begin, end = pads[:ndim], pads[ndim:]
    if tuple(begin) != tuple(end):
        raise NotImplementedError(
            f"{op_name}: asymmetric ONNX pads {pads} are not supported "
            "(symmetric begin==end only)")
    return begin


def _pool_attrs(attrs, pool_type):
    kernel = tuple(attrs.get("kernel_shape", (1, 1)))
    stride = tuple(attrs.get("strides", (1,) * len(kernel)))
    return dict(kernel=kernel, stride=stride,
                pad=_sym_pads(attrs, len(kernel), pool_type + "Pool"),
                pool_type=pool_type)


def import_onnx_graph(graph):
    """Convert a GraphProto-shaped object; returns
    (sym, arg_params, aux_params) — the reference's from_onnx contract
    (reference: _import/import_onnx.py GraphProto.from_onnx)."""
    from ... import symbol as sym_mod
    from ...ndarray import array as nd_array
    from ...symbol.symbol import var as sym_var

    params = {t.name: _tensor_to_np(t) for t in graph.initializer}
    tensors: Dict[str, object] = {}
    aux_names: List[str] = []

    for inp in graph.input:
        name = inp if isinstance(inp, str) else inp.name
        if name not in params:
            tensors[name] = sym_var(name)

    def get(name):
        if name in tensors:
            return tensors[name]
        if name in params:
            tensors[name] = sym_var(name)
            return tensors[name]
        raise KeyError(f"ONNX tensor {name!r} referenced before definition")

    for node in graph.node:
        op = node.op_type
        attrs = _attrs(node)
        ins = [get(n) for n in node.input if n]
        name = node.name or node.output[0]
        if op == "Conv":
            kernel = tuple(attrs.get("kernel_shape"))
            out = sym_mod.Convolution(
                *ins, kernel=kernel,
                stride=tuple(attrs.get("strides", (1,) * len(kernel))),
                pad=_sym_pads(attrs, len(kernel), "Conv"),
                dilate=tuple(attrs.get("dilations", (1,) * len(kernel))),
                num_filter=params[node.input[1]].shape[0],
                num_group=int(attrs.get("group", 1)),
                no_bias=len(ins) < 3, name=name)
        elif op == "Gemm":
            if attrs.get("transA", 0):
                raise NotImplementedError("Gemm: transA=1 is not supported")
            alpha = float(attrs.get("alpha", 1.0))
            beta = float(attrs.get("beta", 1.0))
            w = params[node.input[1]]
            if not attrs.get("transB", 0):
                # our FullyConnected wants (units, in); transpose stored W
                w = np.ascontiguousarray(w.T)
            if alpha != 1.0:
                w = w * alpha            # fold alpha into the weight
            params[node.input[1]] = w
            if len(node.input) > 2 and beta != 1.0:
                params[node.input[2]] = params[node.input[2]] * beta
            out = sym_mod.FullyConnected(
                *ins, num_hidden=params[node.input[1]].shape[0],
                no_bias=len(ins) < 3, name=name)
        elif op == "MatMul":
            out = sym_mod.dot(*ins, name=name)
        elif op in ("Relu", "Sigmoid", "Tanh"):
            out = sym_mod.Activation(ins[0], act_type=op.lower(), name=name)
        elif op == "Softmax":
            out = sym_mod.softmax(ins[0], axis=int(attrs.get("axis", -1)),
                                  name=name)
        elif op == "MaxPool":
            out = sym_mod.Pooling(ins[0], **_pool_attrs(attrs, "max"),
                                  name=name)
        elif op == "AveragePool":
            out = sym_mod.Pooling(ins[0], **_pool_attrs(attrs, "avg"),
                                  name=name)
        elif op == "GlobalAveragePool":
            out = sym_mod.Pooling(ins[0], kernel=(1, 1), pool_type="avg",
                                  global_pool=True, name=name)
        elif op == "BatchNormalization":
            out = sym_mod.BatchNorm(
                *ins, eps=float(attrs.get("epsilon", 1e-5)),
                momentum=float(attrs.get("momentum", 0.9)),
                fix_gamma=False, name=name)
            # running mean/var are auxiliary states: mark their variable
            # nodes so list_auxiliary_states()/bind load them from
            # aux_params (reference: from_onnx aux handling)
            for aux_in in node.input[3:5]:
                if aux_in in tensors:
                    tensors[aux_in]._node.attrs["__is_aux__"] = True
            aux_names.extend(node.input[3:5])
        elif op == "Add":
            out = sym_mod.broadcast_add(*ins, name=name)
        elif op == "Sub":
            out = sym_mod.broadcast_sub(*ins, name=name)
        elif op == "Mul":
            out = sym_mod.broadcast_mul(*ins, name=name)
        elif op == "Div":
            out = sym_mod.broadcast_div(*ins, name=name)
        elif op == "Sum":
            out = ins[0]
            for extra in ins[1:]:
                out = sym_mod.broadcast_add(out, extra)
        elif op == "Flatten":
            out = sym_mod.Flatten(ins[0], name=name)
        elif op == "Reshape":
            if len(node.input) > 1 and node.input[1] in params:
                shape = tuple(int(s) for s in params.pop(node.input[1]))
            else:
                shape = tuple(attrs.get("shape", ()))
            out = sym_mod.Reshape(ins[0], shape=shape, name=name)
        elif op == "Transpose":
            out = sym_mod.transpose(ins[0],
                                    axes=tuple(attrs.get("perm", ())),
                                    name=name)
        elif op == "Concat":
            out = sym_mod.concat(*ins, dim=int(attrs.get("axis", 1)),
                                 name=name)
        elif op == "Dropout":
            out = sym_mod.Dropout(ins[0], p=float(attrs.get("ratio", 0.5)),
                                  name=name)
        elif op == "Identity":
            out = ins[0]
        elif op == "Constant":
            params[node.output[0]] = _tensor_to_np(attrs["value"])
            tensors[node.output[0]] = sym_var(node.output[0])
            continue
        elif op == "Pad":
            # ONNX pads = (begin_0..begin_n, end_0..end_n); the Pad op's
            # pad_width interleaves (begin, end) per axis
            pads = tuple(attrs.get("pads", ()))
            half = len(pads) // 2
            interleaved = tuple(
                v for i in range(half) for v in (pads[i], pads[half + i]))
            out = sym_mod.Pad(ins[0], mode=attrs.get("mode", "constant"),
                              pad_width=interleaved, name=name)
        elif op == "Clip":
            # opset >= 11 passes min/max as inputs 1-2 (constant tensors)
            a_min = float(attrs.get("min", -np.inf))
            a_max = float(attrs.get("max", np.inf))
            extra = [n for n in node.input[1:] if n]
            if extra:
                vals = [float(np.asarray(params.pop(n)).reshape(()))
                        for n in extra if n in params]
                if len(vals) >= 1:
                    a_min = vals[0]
                if len(vals) >= 2:
                    a_max = vals[1]
            out = sym_mod.clip(ins[0], a_min=a_min, a_max=a_max, name=name)
        else:
            raise NotImplementedError(
                f"ONNX op {op!r} is not mapped (reference coverage: "
                "contrib/onnx/_import/import_helper.py)")
        outs = out if isinstance(out, (list, tuple)) else [out]
        for out_name, o in zip(node.output, outs):
            tensors[out_name] = o

    out_syms = [tensors[o if isinstance(o, str) else o.name]
                for o in graph.output]
    sym = out_syms[0] if len(out_syms) == 1 else sym_mod.Group(out_syms)
    arg_params = {k: nd_array(v) for k, v in params.items()
                  if k not in aux_names}
    aux_params = {k: nd_array(params[k]) for k in aux_names if k in params}
    return sym, arg_params, aux_params


def import_model(model_file):
    """Load a real .onnx file (reference: import_model.py:import_model).

    Parsing uses the vendored ONNX IR protobuf subset
    (proto/onnx_subset.proto — field numbers match upstream onnx.proto,
    protobuf skips unknown fields), so no ``onnx`` package is needed;
    falls back to the ``onnx`` package if it is installed and the subset
    schema ever falls short."""
    graph = None
    with open(model_file, "rb") as f:  # OSError (bad path) propagates
        raw = f.read()
    try:
        from .proto import onnx_subset_pb2 as P
        model = P.ModelProto()
        model.ParseFromString(raw)
        if model.graph.node:
            graph = model.graph
    except Exception:
        pass  # wire-format parse failed; try the onnx package below
    if graph is None:
        # parse-level fallback only: conversion errors must propagate
        # with their own messages, not be masked by a missing-onnx
        # ImportError
        import onnx
        graph = onnx.load(model_file).graph
    return import_onnx_graph(graph)
