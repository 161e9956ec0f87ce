"""Runtime feature detection (reference: python/mxnet/libinfo.py build
metadata; later mx.runtime.Features — capability kept here).

``Features()`` reports what this build/environment supports, the analog of
the reference's compile-time USE_* flags (make/config.mk:64-144) resolved
at runtime instead.
"""
from __future__ import annotations

__all__ = ["Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        mark = "✔" if self.enabled else "✖"
        return f"{mark} {self.name}"


def _detect():
    import jax
    feats = {}
    platforms = {d.platform for d in jax.devices()}
    feats["TPU"] = "tpu" in platforms
    feats["CPU"] = True
    feats["CUDA"] = "gpu" in platforms          # ≙ USE_CUDA config.mk:64
    feats["DIST_KVSTORE"] = True                # ≙ USE_DIST_KVSTORE :144
    feats["INT8_QUANTIZATION"] = True
    feats["SPARSE"] = True
    try:
        from . import native
        feats["NATIVE_IO"] = native.available() # ≙ the C++ io layer
    except Exception:
        feats["NATIVE_IO"] = False
    try:
        import jax.experimental.pallas  # noqa: F401
        feats["PALLAS"] = True                  # ≙ USE CUDA RTC rtc.cc
    except ImportError:
        feats["PALLAS"] = False
    try:
        from torch.utils import tensorboard  # noqa: F401
        feats["TENSORBOARD"] = True
    except Exception:
        feats["TENSORBOARD"] = False
    try:
        import onnx  # noqa: F401
        feats["ONNX"] = True
    except ImportError:
        feats["ONNX"] = False
    return feats


class Features(dict):
    """dict of name -> Feature (reference API: mx.runtime.Features)."""

    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _detect().items()})

    def is_enabled(self, name):
        return self[name].enabled


def feature_list():
    return list(Features().values())
