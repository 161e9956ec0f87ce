"""Operator registry.

TPU-native rebuild of the reference's NNVM op registry
(reference: include/mxnet/op_attr_types.h, src/operator/ — ~300
``NNVM_REGISTER_OP`` sites). Each op here is a *pure function over jax arrays*
``fn(*arrays, **attrs) -> array | tuple``; XLA replaces FCompute kernels,
shape/dtype inference, memory planning and fusion. The registry feeds three
consumers:

- ``mxnet_tpu.ndarray``: eager NDArray wrappers (analog of the generated
  functions in python/mxnet/ndarray/register.py:29-156),
- ``mxnet_tpu.symbol``: lazy graph nodes with the same names,
- ``jit``/hybridize: traced directly.
"""
from __future__ import annotations

import ast
from typing import Callable, Dict, Optional, Sequence

__all__ = ["OpDef", "register_op", "get_op", "list_ops", "alias", "parse_attr"]

_OPS: Dict[str, "OpDef"] = {}


class OpDef:
    __slots__ = ("name", "fn", "aliases", "no_grad", "num_outputs",
                 "attr_types", "names_its_parts")

    def __init__(self, name: str, fn: Callable, aliases=(), no_grad=False,
                 num_outputs: int = 1, attr_types: Optional[dict] = None,
                 names_its_parts: bool = False):
        self.name = name
        self.fn = fn
        self.aliases = tuple(aliases)
        self.no_grad = no_grad
        self.num_outputs = num_outputs
        self.attr_types = attr_types or {}
        self.names_its_parts = names_its_parts

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)

    def __repr__(self):
        return f"OpDef({self.name})"


def register_op(name: str, aliases: Sequence[str] = (), no_grad: bool = False,
                num_outputs: int = 1, names_its_parts: bool = False):
    """Register an operator implementation under its MXNet name(s).

    ``names_its_parts``: the operator's body opens ``jax.named_scope``s
    for its own parts (``mx_rnn_scan``, ``mx_attn_fwd``). A Symbol
    graph's walk (``Symbol._apply_node_op``) gives such an operator no
    ``mx_op_<name>`` scope, which would enclose them: the readers of a
    device trace anchor their patterns at the start of a scope's path."""

    def _reg(fn):
        opdef = OpDef(name, fn, aliases, no_grad, num_outputs,
                      names_its_parts=names_its_parts)
        _OPS[name] = opdef
        for a in aliases:
            _OPS[a] = opdef
        return fn

    return _reg


def alias(existing: str, *names: str):
    opdef = _OPS[existing]
    for n in names:
        _OPS[n] = opdef


def get_op(name: str) -> OpDef:
    try:
        return _OPS[name]
    except KeyError:
        raise KeyError(f"Operator '{name}' is not registered "
                       f"({len(set(id(o) for o in _OPS.values()))} ops known)") from None


def has_op(name: str) -> bool:
    return name in _OPS


def list_ops():
    """All registered op names (analog of MXListAllOpNames, c_api.cc)."""
    return sorted(_OPS)


def parse_attr(value):
    """Parse a string-typed attribute as it appears in Symbol JSON.

    The reference stores all graph attrs as strings (dmlc::Parameter
    serialization); e.g. kernel="(3, 3)", no_bias="True", num_hidden="64".
    """
    if not isinstance(value, str):
        return value
    v = value.strip()
    low = v.lower()
    if low in ("true", "false"):
        return low == "true"
    if low in ("none", "null"):
        return None
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        return value
