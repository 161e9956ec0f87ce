"""Custom operators written in Python: ``mx.operator.CustomOp`` and the
``Custom`` op, plus the Pallas custom-kernel hook (the TPU analog of the
reference's runtime-compiled CUDA via ``mx.rtc``).

TPU-native rebuild of the reference custom-op bridge (reference:
python/mxnet/operator.py:422-579 CustomOp/CustomOpProp/register,
src/operator/custom/custom.cc:49-125 callback trampoline). The reference
runs Python callbacks on a dedicated thread, asynchronously on the engine;
here the callbacks run at dispatch time:

- **eager**: forward runs directly on NDArrays; when autograd is recording,
  a tape node re-enters ``backward`` with the same req/in/out protocol.
- **inside jit** (hybridized blocks / Symbol executors): the op is staged
  via ``jax.pure_callback`` with a ``jax.custom_vjp`` wrapping the
  CustomOp backward — the XLA program calls back into Python, exactly the
  capability boundary the reference's C-callback trampoline has.

Pallas hook: ``register_pallas`` registers a user-written Pallas TPU kernel
as a first-class op (usable from nd/sym/Gluon, differentiable if the author
supplies a VJP) — replacing mx.rtc.CudaModule (reference:
src/common/rtc.cc:35-61, python/mxnet/rtc.py:42-173).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["CustomOp", "CustomOpProp", "register", "get_registered",
           "register_pallas", "PallasKernel"]


class CustomOp:
    """Base class for custom operators (reference: operator.py:422)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        """Write src into dst honoring the grad_req (reference:
        operator.py:459)."""
        if req == "null":
            return
        if req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] += src


class CustomOpProp:
    """Describes a custom op's signature (reference: operator.py:468)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), []

    def list_outputs(self):
        return ["output"]

    def list_arguments(self):
        return ["data"]

    def list_auxiliary_states(self):
        return []

    def need_top_grad(self):
        return self.need_top_grad_

    def create_operator(self, ctx, in_shapes, in_dtypes):
        raise NotImplementedError


_registry: Dict[str, type] = {}


def register(reg_name):
    """Class decorator registering a CustomOpProp under ``op_type``
    (reference: operator.py:602)."""

    def do_register(prop_cls):
        _registry[reg_name] = prop_cls
        return prop_cls

    return do_register


def get_registered(op_type):
    if op_type not in _registry:
        raise KeyError(f"custom op type {op_type!r} is not registered; "
                       "use mx.operator.register")
    return _registry[op_type]


# ---------------------------------------------------------------------------
# the Custom op: dispatches to a registered CustomOpProp
# ---------------------------------------------------------------------------
def _custom_staged(op_type, arrays, prop_kwargs=None):
    """Staged (inside-jit) path via pure_callback + custom_vjp
    (the capability analog of the reference's engine-async C callbacks)."""
    import jax
    import jax.numpy as jnp
    from .context import current_context
    from .ndarray.ndarray import _wrap

    # Custom(...) keyword attrs parameterize the prop, as the reference
    # passes them to the CustomOpProp constructor (operator.py:765)
    prop = get_registered(op_type)(**(prop_kwargs or {}))
    n_args = len(prop.list_arguments())
    in_shapes = [list(a.shape) for a in arrays[:n_args]]
    _, out_shapes, _ = prop.infer_shape(in_shapes)
    in_dtypes = [np.dtype(a.dtype) for a in arrays[:n_args]]
    _, out_dtypes, _ = prop.infer_type(in_dtypes)
    out_struct = [jax.ShapeDtypeStruct(tuple(s), np.dtype(t))
                  for s, t in zip(out_shapes, out_dtypes)]

    def host_forward(*host_arrays):
        op = prop.create_operator(current_context(), in_shapes,
                                  [a.dtype for a in host_arrays])
        ins = [_wrap(jnp.asarray(a)) for a in host_arrays[:n_args]]
        aux = [_wrap(jnp.asarray(a)) for a in host_arrays[n_args:]]
        outs = [_wrap(jnp.zeros(tuple(s), np.dtype(t)))
                for s, t in zip(out_shapes, out_dtypes)]
        op.forward(True, ["write"] * len(outs), ins, outs, aux)
        return tuple(np.asarray(o._data, np.dtype(t))
                     for o, t in zip(outs, out_dtypes))

    def host_backward(*host_arrays):
        k = len(out_struct)
        cts = host_arrays[:k]
        prim = host_arrays[k:]
        op = prop.create_operator(current_context(), in_shapes,
                                  [a.dtype for a in prim])
        ins = [_wrap(jnp.asarray(a)) for a in prim[:n_args]]
        aux = [_wrap(jnp.asarray(a)) for a in prim[n_args:]]
        outs = [_wrap(jnp.zeros(tuple(s), np.dtype(t)))
                for s, t in zip(out_shapes, out_dtypes)]
        op.forward(True, ["write"] * len(outs), ins, outs, aux)
        grads = [_wrap(jnp.zeros(a.shape, a.dtype)) for a in ins]
        op.backward(["write"] * len(grads),
                    [_wrap(jnp.asarray(c)) for c in cts],
                    ins, outs, grads, aux)
        return tuple(np.asarray(g._data) for g in grads)

    @jax.custom_vjp
    def call(*xs):
        return jax.pure_callback(host_forward, tuple(out_struct), *xs)

    def call_fwd(*xs):
        return call(*xs), xs

    def call_bwd(xs, cts):
        grad_struct = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in xs[:n_args]]
        gs = jax.pure_callback(host_backward, tuple(grad_struct),
                               *(tuple(cts) + tuple(xs)))
        # aux states get zero cotangents (custom_vjp rejects None entries)
        return tuple(gs) + tuple(jnp.zeros(x.shape, x.dtype)
                                 for x in xs[n_args:])

    call.defvjp(call_fwd, call_bwd)
    res = call(*arrays)
    return res[0] if len(res) == 1 else res


def _custom_op_fn(*arrays, op_type=None, **kw):
    """Registry entry for the 'Custom' op. Sees raw jax arrays eagerly, or
    tracers inside jit — both route through pure_callback + custom_vjp
    (eagerly, pure_callback just executes the Python immediately)."""
    if op_type is None:
        raise ValueError("Custom requires op_type=")
    return _custom_staged(op_type, list(arrays), prop_kwargs=kw)


# ---------------------------------------------------------------------------
# Pallas custom-kernel hook (mx.rtc analog)
# ---------------------------------------------------------------------------
class PallasKernel:
    """A user-written Pallas TPU kernel wrapped as a callable op
    (reference capability: rtc.py:42-173 CudaModule/CudaKernel — runtime
    user kernels; here they compile through Mosaic instead of NVRTC).

    kernel_fn: pallas kernel ``(in_ref..., out_ref) -> None``.
    out_shape: output shape, or fn(in_shapes) -> shape.
    vjp: optional ``(cts, *primals) -> grads tuple`` for differentiability.
    The kernel is interpreted off TPU backends and compiled by Mosaic on
    them (``ops.pallas_fused.interpret_mode`` — not a caller's choice).
    """

    def __init__(self, kernel_fn, out_shape, name="pallas_op", grid=None,
                 vjp: Optional[Callable] = None):
        self.kernel_fn = kernel_fn
        self.out_shape = out_shape
        self.name = name
        self.grid = grid
        self.vjp = vjp

    def _call_arrays(self, *arrays):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from .ops.pallas_fused import interpret_mode

        shape = self.out_shape(tuple(a.shape for a in arrays)) \
            if callable(self.out_shape) else self.out_shape
        out = jax.ShapeDtypeStruct(tuple(shape), arrays[0].dtype)
        kw = {}
        if self.grid is not None:
            kw["grid"] = self.grid
        run = pl.pallas_call(self.kernel_fn, out_shape=out,
                             interpret=interpret_mode(), **kw)
        if self.vjp is None:
            return run(*arrays)

        vjp_fn = self.vjp

        @jax.custom_vjp
        def call(*xs):
            return run(*xs)

        def fwd(*xs):
            return run(*xs), xs

        def bwd(xs, ct):
            return tuple(vjp_fn(ct, *xs))

        call.defvjp(fwd, bwd)
        return call(*arrays)

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray, _invoke_fn
        if inputs and isinstance(inputs[0], NDArray):
            return _invoke_fn(self.name, self._call_arrays, list(inputs))
        return self._call_arrays(*inputs)


def register_pallas(name, kernel_fn, out_shape, grid=None, vjp=None,
                    aliases=()):
    """Register a Pallas kernel as a first-class op: callable as
    ``nd.<name>`` and usable in symbols/hybridized blocks."""
    from .ops.registry import register_op

    pk = PallasKernel(kernel_fn, out_shape, name=name, grid=grid, vjp=vjp)
    register_op(name, aliases=aliases, no_grad=vjp is None)(pk._call_arrays)
    # expose as a generated nd.<name> function if nd was already imported
    import sys
    nd_pkg = sys.modules.get(f"{__package__}.ndarray")
    if nd_pkg is not None and not hasattr(nd_pkg, name):
        from .ops.registry import _OPS
        setattr(nd_pkg, name, nd_pkg._make_op_func(_OPS[name]))
    return pk


# register the Custom op itself (reference: NNVM op 'Custom',
# src/operator/custom/custom.cc:49)
from .ops.registry import register_op as _register_op  # noqa: E402

_register_op("Custom", aliases=["_Custom"])(_custom_op_fn)
