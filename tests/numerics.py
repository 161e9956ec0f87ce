"""How the numerics tests compare an op with its plain form: each side is
traced ONCE under ``jax.jit``, value and gradients from one
``jax.value_and_grad``, and the leaves are compared one by one, the
failing one by name. Called eagerly, every primitive of an op, of its
reference and of both backward passes is dispatched and compiled on its
own: a thousand compilations a file where two do.

A test states what it compares (two functions, their arguments, the
cotangent, the arguments differentiated, the tolerances); this module owns
how. A ``jax.default_matmul_precision`` block must enclose the call.

Beside them, how a test takes an op's TPU branch on this backend."""
import functools
from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax


class Tol(NamedTuple):
    """``|got - want| <= atol + scaled * max(max|want|, floor)
    + rtol * |want|`` (``rtol`` defaults as ``np.testing.assert_allclose``
    does)."""
    rtol: float = 1e-7
    atol: float = 0.0
    scaled: float = 0.0
    floor: float = 0.0


#: the same program: every entry equal
TO_THE_BIT = Tol(rtol=0.0)


def kernel_tol(tol):
    """The kernel files' measure: ``tol`` of each entry and of the wanted
    leaf's largest."""
    return Tol(rtol=tol, scaled=tol)


def close(got, want, tol, name="", same_dtype=False):
    """Hold every leaf of ``got`` to the like leaf of ``want`` within
    ``tol`` (a ``Tol``, scaled by the wanted leaf's largest entry), naming
    the leaf that fails."""
    got, tree = jax.tree_util.tree_flatten_with_path(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want), (name, tree)
    for (path, a), b in zip(got, want):
        leaf = name + jax.tree_util.keystr(path)
        assert jnp.shape(a) == jnp.shape(b), leaf
        if same_dtype:
            assert a.dtype == b.dtype, leaf
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        largest = max(float(np.abs(b).max()), tol.floor)
        np.testing.assert_allclose(
            a, b, rtol=tol.rtol, atol=tol.atol + tol.scaled * largest,
            err_msg=leaf)


def traced(fn, args, cot=None, wrt=()):
    """``(fn(*args), gradients)`` from one compiled program: the gradients
    of ``sum(out * cot)`` over the output's leaves for the arguments
    ``wrt`` (``None`` without a cotangent). ``fn`` is traced afresh at
    every call, so what a test patched around this call is what runs."""
    if cot is None:
        return jax.jit(lambda *a: fn(*a))(*args), None

    def loss(*a):
        *a, cot = a
        out = fn(*a)
        return sum(jnp.sum(o * c) for o, c in zip(
            jax.tree_util.tree_leaves(out),
            jax.tree_util.tree_leaves(cot), strict=True)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, wrt if isinstance(wrt, int) else tuple(wrt),
        has_aux=True))(*args, cot)
    return out, grads


def agree(got, want, args, cot=None, wrt=(), *, value, grads=None,
          same_dtype=False):
    """Trace ``got`` and ``want`` once each on ``args`` and hold the first's
    value to the second's within ``value`` and, with a cotangent, its
    gradients for the arguments ``wrt`` within ``grads``. Returns what
    ``got`` gave, ``(value, gradients)``, for a test that asserts more."""
    mine, theirs = (traced(fn, args, cot, wrt) for fn in (got, want))
    close(mine[0], theirs[0], value, "value", same_dtype)
    if cot is not None:
        close(mine[1], theirs[1], grads or value, "gradient", same_dtype)
    return mine


# -- an op's TPU branch on this backend ----------------------------------------
@pytest.fixture()
def kernels_here(monkeypatch):
    """The op takes its TPU branch on this backend, the attention kernels
    interpreted."""
    from mxnet_tpu.ops import attn_kernel
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    for name in ("forward", "backward"):
        monkeypatch.setattr(attn_kernel, name, functools.partial(
            getattr(attn_kernel, name), interpret=True))


class LoweredForATpu:
    """Stands where ``ops.seq`` names ``jax.lax``: every
    ``platform_dependent`` takes its TPU branch, as a lowering for a TPU
    would."""

    def __getattr__(self, name):
        return getattr(lax, name)

    @staticmethod
    def platform_dependent(*args, tpu, default):
        return tpu(*args)
