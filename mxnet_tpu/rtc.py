"""Runtime kernel compilation facade.

The reference compiles user-supplied CUDA C at runtime via NVRTC
(reference: src/common/rtc.cc:35-61, python/mxnet/rtc.py:42-173
CudaModule/CudaKernel). The TPU-native equivalent is a user-supplied
Pallas kernel compiled by Mosaic — exposed here as ``PallasModule`` with
the CudaModule ergonomics, on top of ``mxnet_tpu.operator.PallasKernel``.
"""
from __future__ import annotations

from .operator import PallasKernel, register_pallas

__all__ = ["PallasModule", "PallasKernel", "CudaModule"]


class PallasModule:
    """Holds named Pallas kernels (CudaModule analog: rtc.py:42).

    Usage::

        mod = rtc.PallasModule()
        k = mod.get_kernel(my_kernel_fn, out_shape=lambda s: s[0])
        y = k(x)
    """

    def __init__(self):
        self._kernels = {}

    def get_kernel(self, kernel_fn, out_shape, name=None, grid=None,
                   vjp=None):
        """(CudaModule.get_kernel analog: rtc.py:106)"""
        name = name or getattr(kernel_fn, "__name__", "pallas_kernel")
        pk = PallasKernel(kernel_fn, out_shape, name=name, grid=grid,
                          vjp=vjp)
        self._kernels[name] = pk
        return pk


def CudaModule(*args, **kwargs):  # pragma: no cover - compat shim
    raise NotImplementedError(
        "CUDA RTC does not exist on TPU; write a Pallas kernel and wrap it "
        "with mx.rtc.PallasModule / mx.operator.register_pallas instead")
