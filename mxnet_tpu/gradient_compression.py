"""2-bit gradient compression with error feedback.

TPU-native rebuild of the reference's gradient compression
(reference: src/kvstore/gradient_compression.h:37-134, .cc quantize/
dequantize kernels; python surface kvstore.py set_gradient_compression).

Semantics (verified against incubator-mxnet/tests/nightly/test_kvstore.py
``compute_expected_2bit_quantization``): per element, with error feedback
``v = grad + residual``:

- v >= threshold   -> code ``11``, sends +threshold, residual v - threshold
- v <= -threshold  -> code ``10``, sends -threshold, residual v + threshold
- otherwise        -> code ``00``, sends 0, residual v

Wire format: 16 two-bit codes packed per 32-bit word. The reference builds
a bit string MSB-first and reinterprets each 32-char chunk with its *bytes*
reversed as a little-endian float32; equivalently, string position p maps
to bit ``8*(p//8) + 7 - p%8`` of the uint32. The packing here reproduces
that layout bit-exactly (so compressed buffers are interchangeable), as a
single fused XLA computation (segment_sum over per-element contributions)
instead of the reference's per-word CPU/CUDA kernels.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

__all__ = ["GradientCompression", "quantize_2bit", "dequantize_2bit"]


def _bit_index(p):
    """String position -> bit index in the packed uint32 (see module doc)."""
    return 8 * (p // 8) + 7 - (p % 8)


@functools.partial(jax.jit, static_argnums=2)
def _quantize_2bit_jit(grad, residual, threshold):
    import jax
    import jax.numpy as jnp
    flat = grad.ravel() + residual.ravel()
    n = flat.shape[0]
    pos = flat >= threshold
    neg = flat <= -threshold
    dequant = jnp.where(pos, threshold, jnp.where(neg, -threshold, 0.0))
    new_residual = (flat - dequant).reshape(grad.shape)

    # pack: element j -> chars (2j, 2j+1); '11' for +, '10' for -
    idx = jnp.arange(n)
    hi_bit = _bit_index(2 * (idx % 16))        # marker bit (set for + and -)
    lo_bit = _bit_index(2 * (idx % 16) + 1)    # sign bit (set for + only)
    word = idx // 16
    n_words = (n + 15) // 16
    contrib = jnp.where(pos | neg, jnp.uint32(1) << hi_bit.astype(jnp.uint32),
                        jnp.uint32(0)) \
        | jnp.where(pos, jnp.uint32(1) << lo_bit.astype(jnp.uint32),
                    jnp.uint32(0))
    packed = jax.ops.segment_sum(contrib, word, num_segments=n_words)
    return packed.astype(jnp.uint32).view(jnp.float32), new_residual, \
        dequant.reshape(grad.shape)


def quantize_2bit(grad, residual, threshold):
    """Returns (packed float32 buffer, new residual, dequantized values)."""
    import jax.numpy as jnp
    return _quantize_2bit_jit(jnp.asarray(grad, jnp.float32),
                              jnp.asarray(residual, jnp.float32),
                              float(threshold))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _dequantize_2bit_jit(packed, n, threshold):
    import jax.numpy as jnp
    words = packed.view(jnp.uint32)
    idx = jnp.arange(n)
    hi = (words[idx // 16] >> _bit_index(2 * (idx % 16)).astype(jnp.uint32)) & 1
    lo = (words[idx // 16] >>
          _bit_index(2 * (idx % 16) + 1).astype(jnp.uint32)) & 1
    return jnp.where(hi == 1,
                     jnp.where(lo == 1, threshold, -threshold), 0.0)


def dequantize_2bit(packed, n, threshold, shape=None):
    """Decode a packed buffer of ``n`` elements back to {-t, 0, +t}."""
    import jax.numpy as jnp
    out = _dequantize_2bit_jit(jnp.asarray(packed), int(n), float(threshold))
    return out.reshape(shape) if shape is not None else out


class GradientCompression:
    """Per-key compression state holder (reference:
    gradient_compression.h:52 GradientCompression with kTwoBit)."""

    def __init__(self, type="2bit", threshold=0.5):
        if str(type) not in ("2bit", "none"):
            raise ValueError(f"unsupported compression type {type!r}")
        self.type = str(type)
        self.threshold = float(threshold)
        self._residuals = {}

    @property
    def active(self):
        return self.type == "2bit"

    def compress(self, key, grad):
        """Quantize with per-key error feedback; returns the dequantized
        gradient (what the receiving end reconstructs)."""
        import jax.numpy as jnp
        if not self.active:
            return grad
        res = self._residuals.get(key)
        if res is None or res.shape != grad.shape:
            res = jnp.zeros(grad.shape, jnp.float32)
        packed, new_res, dequant = quantize_2bit(grad, res, self.threshold)
        self._residuals[key] = new_res
        return dequant.astype(grad.dtype)

    def get_compressed_size(self, original_size):
        """(reference: gradient_compression.h GetCompressedSize)"""
        return ((original_size + 15) // 16) * 4 if self.active \
            else original_size * 4

    def encode_params(self):
        return {"type": self.type, "threshold": self.threshold}
