"""Device context abstraction over ``jax.devices()``.

TPU-native rebuild of ``mxnet.context`` (reference: python/mxnet/context.py,
include/mxnet/base.h:133-160). The reference's device types {cpu, gpu,
cpu_pinned, cpu_shared} map here to {cpu, tpu (accelerator), cpu (host
staging is implicit in JAX's transfer machinery)}. ``gpu()`` is kept as an
alias for the accelerator so reference scripts run unmodified.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    """A device context.

    Usable as a ``with`` scope like the reference (context.py:98):

        with mx.tpu(0):
            x = mx.nd.zeros((2, 2))
    """

    # device type codes kept numerically compatible with the reference
    # (include/mxnet/base.h:135-139) plus a new kTPU.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx: Optional["Context"] = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX mapping ---------------------------------------------------------
    @property
    def jax_device(self):
        """The ``jax.Device`` this context denotes. An accelerator
        context names one chip or none: in a process that has no
        accelerator, or with a ``device_id`` past the last chip, it
        raises — ``tpu(5)`` on four chips is not chip 1, and ``tpu(0)``
        without a chip is not the CPU. CPU ids stay nominal, as in the
        reference (``cpu(1)`` is valid on any host)."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = [d for d in jax.devices() if d.platform == "cpu"]
            if not devs:
                devs = jax.devices("cpu")
            return devs[self.device_id % len(devs)]
        from .base import MXNetError
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if not devs:
            raise MXNetError(
                f"context {self} names an accelerator, but JAX sees none "
                f"(default backend '{jax.default_backend()}'); use "
                "mx.cpu() or mx.current_context()")
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                f"context {self}: device_id out of range, this process "
                f"has {len(devs)} {devs[0].platform} device(s)")
        return devs[self.device_id]

    def empty_cache(self):
        """Reference API parity (context.py:161); XLA owns the allocator, so
        this is a best-effort hint."""
        for d in jax.devices():
            try:
                d.memory_stats()
            except Exception:
                pass


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for the process accelerator. Reference scripts that say
    ``mx.gpu(i)`` transparently get TPU chip *i*."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    """Number of accelerator chips visible (reference: context.py:242)."""
    return len([d for d in jax.devices() if d.platform != "cpu"])


def num_tpus() -> int:
    return num_gpus()


def current_context() -> Context:
    """The active default context (reference: context.py:216)."""
    ctx = getattr(Context._default_ctx, "value", None)
    if ctx is not None:
        return ctx
    # default to the accelerator if present, else cpu
    return Context("tpu", 0) if num_gpus() else Context("cpu", 0)
