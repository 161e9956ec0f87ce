"""Post-training quantization subsystem (round 19).

On a bandwidth-bound machine halving bytes IS the speedup, and
quantization is the largest untouched byte lever: int8 weights move a quarter of the
f32 bytes, and an int8 KV-cache halves-and-then-some the decode state
that every decode step re-reads. Two measured deliverables:

- **int8 weight PTQ as a graph pass** (symbol/passes/int8_ptq.py):
  :func:`calibrate` observes a module's conv/FC weights (per-channel
  absmax / percentile, :mod:`.observers`) into a :class:`QuantConfig`;
  under :func:`quant_scope` the ``int8_ptq`` pass rewrites enabled
  sites to ``dequantize(int8_weight) · scale`` with the scale derived
  IN-GRAPH from the current weights. Predictor hoisting then
  precomputes the int8 weight as a program argument while a
  ``__no_hoist__`` barrier on the dequantize keeps the f32 expansion
  inside the program — the serving program's weight traffic is int8,
  verified by the pass manager's measured bytes gate (Relay's
  quantization-as-graph-rewrite, arXiv:1810.00952, under our
  arXiv:2301.13062 cost-model verifier).
- **int8 KV-cache** for decode serving (serving/decode/):
  ``MXTPU_DECODE_KV_DTYPE=int8`` stores each cache row quantized with
  a per-(slot, position, head) f32 scale, dequantized at f32 compute.
  Per-row scales keep slot lanes independent, so continuous batching
  stays bit-identical to solo decode — the r16 pin, now under int8.

Observability: ``quant::`` telemetry (``mx.quant_report()``) and the
``tools/quant.py`` CLI (calibrate / show / verify).
"""
from __future__ import annotations

from .observers import (AbsMaxObserver, PercentileObserver, make_observer,
                        compute_scales, quantize_np, dequantize_np,
                        QMAX, SCALE_FLOOR)
from .calibrate import (QuantConfig, calibrate, find_sites, set_config,
                        current_config, quant_scope)

__all__ = ["AbsMaxObserver", "PercentileObserver", "make_observer",
           "compute_scales", "quantize_np", "dequantize_np", "QMAX",
           "SCALE_FLOOR", "QuantConfig", "calibrate", "find_sites",
           "set_config", "current_config", "quant_scope", "quant_report"]


def _collect(reset):
    from ..telemetry import registry as _treg
    snap = _treg.snapshot(reset=reset, prefix="quant::")
    out = {}
    for name, vals in snap.items():
        out[name.split("::", 1)[1]] = vals.get("value")
    return out


from ..telemetry import registry as _treg_mod  # noqa: E402

quant_report = _treg_mod.collector_view("quant", _collect)
