"""Microbench for the Pallas fused BN-apply+ReLU+matmul kernel.

The kernel itself was promoted into ``mxnet_tpu/ops/pallas_fused.py``
(round 6) and is wired into the compiled training step by the
graph-rewrite fusion pass (mxnet_tpu/symbol/fusion.py, flag
MXTPU_PALLAS_FUSION); this tool remains the standalone best-effort
microbench of the raw (M, K) @ (K, N) kernel.

MEASUREMENT CAVEAT: a standalone timing of a sub-millisecond kernel is
mostly dispatch — lax.scan bodies lower with conservative scheduling,
and XLA's algebraic simplifier collapses linear-op repetition chains.
The authoritative numbers are whole-step, from a device trace
(tools/step_profile.py); this tool prints host-clock times of a chained
loop and names no device metric.

Usage: python tools/pallas_fused_bn_bench.py [M] [K] [N]
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402

from mxnet_tpu.ops.pallas_fused import (       # noqa: E402,F401
    bn_relu_matmul, select_tiles, _make_kernel)

# back-compat alias: the raw one-tile kernel body (tests and downstream
# scripts imported ``_kernel`` from this tool before the promotion)
_kernel = _make_kernel(relu=True)


@jax.jit
def unfused(x, w, scale, shift):
    xhat = jnp.maximum(x * scale + shift, 0.0).astype(x.dtype)
    return jnp.dot(xhat, w, preferred_element_type=jnp.float32).astype(
        x.dtype)


def _time(f, x, w, scale, shift, inner=16, reps=5):
    """Per-application time with the op repeated INSIDE one jitted chain
    (a lone launch pays a dispatch floor that would swamp a sub-ms op).
    The input is perturbed per iteration so XLA cannot hoist the op out
    of the loop; the perturbation (one extra elementwise pass) is
    identical for both candidates."""

    @jax.jit
    def many(x, w, scale, shift):
        # straight-line unrolled chain (lax.scan bodies lower with
        # conservative scheduling on TPU and distort kernel time); the
        # carried scalar feeds the next input, so XLA can neither hoist
        # the op nor collapse iterations (relu breaks linearity)
        acc = jnp.float32(0)
        for _ in range(inner):
            xi = x + acc.astype(x.dtype)
            z = f(xi, w, scale, shift)
            acc = jnp.sum(z.astype(jnp.float32)) * jnp.float32(1e-12)
        return acc

    out = many(x, w, scale, shift)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = many(x, w, scale, shift)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / inner


def main():
    m = int(sys.argv[1]) if len(sys.argv) > 1 else 128 * 56 * 56
    k = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    n = int(sys.argv[3]) if len(sys.argv) > 3 else 256
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, k).astype(np.float32),
                    jnp.bfloat16)
    w = jnp.asarray(rng.randn(k, n).astype(np.float32) * 0.1,
                    jnp.bfloat16)
    scale = jnp.asarray(rng.rand(k).astype(np.float32) + 0.5,
                        jnp.bfloat16)
    shift = jnp.asarray(rng.randn(k).astype(np.float32) * 0.1,
                        jnp.bfloat16)
    # correctness
    a = np.asarray(bn_relu_matmul(x, w, scale, shift), np.float32)
    b = np.asarray(unfused(x, w, scale, shift), np.float32)
    err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)
    t_f = _time(lambda a, b, c, d: bn_relu_matmul(a, b, c, d),
                x, w, scale, shift)
    t_u = _time(unfused, x, w, scale, shift)
    bytes_min = (m * k + k * n + m * n) * 2          # one touch each
    bytes_unfused = (2 * m * k + k * n + m * n) * 2  # + write/read xhat
    print(f"M={m} K={k} N={n} bf16   rel err {err:.3e}")
    print(f"unfused (XLA)  : {t_u*1e3:7.3f} ms  "
          f"{bytes_unfused/t_u/1e9:6.0f} GB/s effective")
    print(f"fused (pallas) : {t_f*1e3:7.3f} ms  "
          f"{bytes_min/t_f/1e9:6.0f} GB/s effective")
    print(f"speedup        : {t_u/t_f:0.2f}x   "
          f"(traffic floor ratio {bytes_unfused/bytes_min:0.2f}x)")


if __name__ == "__main__":
    main()
