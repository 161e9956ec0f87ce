"""Pallas fused BN-apply(+ReLU)+matmul kernel and its graph-level op.

In ResNet-50 training XLA cannot fuse the normalize/activation pass
across the BN statistics barrier into the MXU convolution that consumes
it, so every BN'd activation is written and read once more than the
arithmetic needs. The cuDNN-style fix —
the one the reference gets from NVIDIA's libraries — is a kernel whose
PROLOGUE applies BN+ReLU while tiles stream into the matmul,
eliminating the materialized normalized tensor (one write + one read of
the full activation) per 1x1 convolution.

``bn_relu_matmul`` is that kernel for the generic (M, K) @ (K, N)
case. Whether it pays on the chip is not measured: on the v5e the pass
gate keeps it out of the default step (PERF.md section 6, PR 21). The
graph op
uses the NCHW-native orientation (``_make_nchw_kernel``): per sample
the (C, H·W) slab of an NCHW activation is contiguous, so contracting
``w (O, C) @ xhat (C, H·W)`` streams the activation directly — no
relayout on either side.

``_FusedBNReLUConv`` is the internal graph op the fusion rewrite pass
(symbol/fusion.py) substitutes for matched ``BatchNorm -> Activation
(relu) -> Convolution(1x1)`` subgraphs. It preserves exact BatchNorm
semantics — per-batch statistics in training, moving stats otherwise —
and mirrors BatchNorm's (out, mean, var) output layout and (…,
moving_mean, moving_var) input positions so the executors' running-stat
fold applies unchanged.

Differentiation: ONE custom VJP covers the whole op, statistics
included — the analytic fused BatchNorm backward (the same coverage as
cuDNN's BatchNormBackward), which assembles d(data) in a single
full-tensor pass instead of naive autodiff's separate mean/var chains.
On TPU the backward recomputes the normalized activation from the raw
residuals (one elementwise pass — precisely the memory-traffic win);
off-TPU the interpreter has to materialize it anyway, so it doubles as
the residual. Off-TPU the whole path runs in interpret mode / stock XLA
ops, so tier-1 CPU tests exercise the same op, rewrite, and VJP;
:func:`interpret_mode` is the one place that decides which.

Tiles are chosen for Mosaic, not for the interpreter: the last block
dimension is a multiple of 128 or the whole array dimension, the
second-to-last a multiple of the dtype's sublane count (8 for 4-byte,
16 for 2-byte, 32 for 1-byte elements) or whole, and the double-buffered
working set fits the VMEM budget the kernels request
(``_VMEM_LIMIT_BYTES``). A shape no such tile serves returns None from
the selectors, and the rewrite pass bails that site by name.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp

from jax import shard_map as _shard_map

from .registry import register_op

__all__ = ["bn_relu_matmul", "bn_relu_conv_nchw", "select_tiles",
           "select_conv_tiles", "conv_tile_failure", "interpret_mode",
           "fused_bn_relu_conv", "mesh_scope", "active_mesh"]


def interpret_mode():
    """Whether ``pallas_call`` runs interpreted — THE decision, for
    every kernel in the package (``operator.PallasKernel`` included).
    Mosaic exists only on a TPU backend, so everywhere else the kernels
    interpret (what tier-1 runs on CPU). On a TPU backend the answer is
    always False and no caller can ask otherwise: an interpreted kernel
    on the chip still returns right answers, which is exactly how a
    kernel Mosaic refuses would go unnoticed."""
    return jax.default_backend() != "tpu"

# ---------------------------------------------------------------------------
# trace-time mesh scope (ROADMAP item 1: shard_map-compatible kernels)
# ---------------------------------------------------------------------------
# GSPMD cannot partition an opaque Pallas custom call, so under a mesh
# bind the kernel invocations below wrap themselves in shard_map over
# the batch axis — each device runs the kernel on its batch shard, and
# the surrounding statistics/folding/backward stay plain jnp for GSPMD
# to partition (global BN batch stats, psum'd parameter gradients).
# The mesh reaches the op at TRACE time through this scope: the fused
# step / pass-manager measurement enters mesh_scope(mesh, axis) around
# lowering, and the op reads it when the pallas_call is built. AD never
# differentiates through the shard_map (it sits inside the ops' custom
# VJPs, whose backward is plain jnp): jax cannot transpose a
# check_vma=False shard_map, and check_vma=False is mandatory because
# pallas_call has no replication rule.
_MESH_SCOPE = threading.local()


@contextlib.contextmanager
def mesh_scope(mesh, axis="data"):
    """Declare the mesh/batch-axis for fused kernels traced inside the
    scope (thread-local; trace-time only — the compiled program carries
    the shard_map, not the scope)."""
    prev = getattr(_MESH_SCOPE, "value", None)
    _MESH_SCOPE.value = None if mesh is None else (mesh, axis)
    try:
        yield
    finally:
        _MESH_SCOPE.value = prev


def active_mesh():
    """The (mesh, batch_axis) declared by the innermost
    :func:`mesh_scope`, or None (single-device trace)."""
    return getattr(_MESH_SCOPE, "value", None)


def _batch_shards(batch):
    """(mesh, axis, per-device batch) when a mesh scope is active and
    the batch divides its axis; else None (the kernel stays unwrapped —
    off-mesh traces, and mesh traces whose batch cannot split, which
    the rewrite passes' bytes gate then judges as-is)."""
    scope = active_mesh()
    if scope is None:
        return None
    mesh, axis = scope
    if axis not in getattr(mesh, "shape", {}):
        return None
    ndev = int(mesh.shape[axis])
    if ndev <= 1 or batch % ndev:
        return None
    return mesh, axis, batch // ndev

# output-tile candidates, largest first. Divisibility alone does not make
# one usable: _blocks() admits only what Mosaic's block rules accept.
_BM_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)
_BN_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)
_LANE = 128
# Scoped VMEM the kernels request. The chip has 128 MiB per core (v5e)
# but scopes a kernel to 16 MiB unless told otherwise, and a whole-row
# f32 block of a 56x56 stage is already past that. A tile is admitted
# only while its estimated working set stays under HALF of this: the
# other half is headroom for Mosaic's own temporaries, which the
# estimate cannot see.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

# MXTPU_PALLAS_TILES parse cache: (raw env string, parsed (bm, bn))
_TILE_OVERRIDE_CACHE = ("", None)


def _tile_override():
    """The ``MXTPU_PALLAS_TILES`` override — ``"<bm>,<bn>"``, the
    candidate pair tried FIRST by :func:`select_tiles` (and, mapped to
    (bs, bo), by :func:`select_conv_tiles`) before the built-in
    largest-first scan. This is the tuner's per-trial tile knob.

    Validation is loud and strict: two positive integers, each a
    multiple of 8 (MXU sublane alignment — see the TPU tile-shape
    table), bounded by the built-in candidate maxima (bm ≤ 1024,
    bn ≤ 512). Anything else raises MXNetError at selection time, so a
    bad tile fails the BIND/TRIAL that consulted it, never the process
    and never silently. A valid tile that doesn't divide the shape at
    hand, or that Mosaic would refuse for it, is not an error —
    selection falls back to the built-in candidates (the knob steers,
    the shape decides)."""
    global _TILE_OVERRIDE_CACHE
    raw = os.environ.get("MXTPU_PALLAS_TILES", "").strip()
    if not raw:
        return None
    if _TILE_OVERRIDE_CACHE[0] == raw:
        return _TILE_OVERRIDE_CACHE[1]
    from ..base import MXNetError

    def bad(why):
        return MXNetError(
            f"MXTPU_PALLAS_TILES={raw!r} is invalid ({why}): expected "
            f"'<bm>,<bn>' with positive multiples of 8, bm <= "
            f"{_BM_CANDIDATES[0]}, bn <= {_BN_CANDIDATES[0]}")

    parts = [p.strip() for p in raw.split(",")]
    if len(parts) != 2:
        raise bad("need exactly two comma-separated values")
    try:
        bm, bn = int(parts[0]), int(parts[1])
    except ValueError:
        raise bad("non-integer value")
    if bm <= 0 or bn <= 0:
        raise bad("non-positive tile")
    if bm % 8 or bn % 8:
        raise bad("not a multiple of 8")
    if bm > _BM_CANDIDATES[0] or bn > _BN_CANDIDATES[0]:
        raise bad("out of bounds")
    _TILE_OVERRIDE_CACHE = (raw, (bm, bn))
    return (bm, bn)


def _sublane(dtype):
    """Rows of one native tile: 8 for 4-byte, 16 for 2-byte, 32 for
    1-byte elements (the last dimension is always 128 lanes)."""
    return 32 // jnp.dtype(dtype).itemsize


def _pad(n, unit):
    return -(-n // unit) * unit


def _blocks(full, candidates, unit, prefer=None):
    """Block sizes Mosaic accepts along one dimension of extent
    ``full``, largest first: the dividing candidates that are multiples
    of ``unit`` (128 for the last block dimension, the dtype's sublane
    count for the one before it) and the dimension taken whole, which
    is always legal (the compiler pads it). ``prefer`` — the tuner's
    override — moves to the front when it is among them."""
    out = {c for c in candidates if full % c == 0 and c % unit == 0}
    out.add(int(full))
    out = sorted(out, reverse=True)
    if prefer in out:
        out.remove(prefer)
        out.insert(0, prefer)
    return out


def _fits_vmem(act, other, out, k, itemsize):
    """Whether one grid step's working set (element counts of the
    PADDED activation / weight / output blocks, contraction length
    ``k``) stays inside the admitted half of ``_VMEM_LIMIT_BYTES``: the
    three streamed blocks double-buffered (the pipeline fetches block
    i+1 while block i computes), the folded scale and shift vectors
    (each padded out to full 128-lane tiles, double-buffered), and the
    f32 temporaries the kernel body materializes — the upcast and the
    normalized activation, and the f32 accumulator."""
    need = (2 * (act + other + out) * itemsize
            + 4 * _pad(k, 8) * _LANE * 4
            + (2 * act + out) * 4)
    return need <= _VMEM_LIMIT_BYTES // 2


def select_tiles(m, n, k, dtype=jnp.float32):
    """(bm, bn) output-tile split Mosaic accepts for an (M, K) @ (K, N)
    fused matmul in ``dtype``, or None. The x block is (bm, K) and the
    w block (K, bn): bm is a second-to-last block dimension, bn a last
    one. M and N must divide by 8 (a ragged MXU feed is not worth a
    kernel). An ``MXTPU_PALLAS_TILES`` override is preferred per
    dimension when it is legal for the shape."""
    if m % 8 or n % 8:
        return None
    ov = _tile_override() or (None, None)
    item = jnp.dtype(dtype).itemsize
    kl, ks = _pad(k, _LANE), _pad(k, _sublane(dtype))
    for bm in _blocks(m, _BM_CANDIDATES, _sublane(dtype), ov[0]):
        for bn in _blocks(n, _BN_CANDIDATES, _LANE, ov[1]):
            bnp = _pad(bn, _LANE)
            if _fits_vmem(bm * kl, ks * bnp, bm * bnp, k, item):
                return bm, bn
    return None


def select_conv_tiles(n_out, spatial, n_in, dtype=jnp.float32):
    """(bo, bs) output tiles Mosaic accepts for the NCHW-native fused
    1×1 conv in ``dtype`` — bo over output channels, bs over the
    flattened spatial dim — or None (the rewrite pass's bail-out rule).
    The weight block is (bo, C), the activation block (1, C, bs) and
    the output block (1, bo, bs): bs is the LANE dimension, so it is a
    128-multiple that divides H·W or H·W whole — 56², 28², 14² and 7²
    have no such divisor and are taken whole, padded to the next 128 —
    and bo a sublane-multiple or num_filter whole. Output channels must
    divide by 8. An ``MXTPU_PALLAS_TILES`` override ``"<bm>,<bn>"``
    maps to (bs, bo) — bm is the spatial-like dim, bn the channel-like
    one — and is preferred per dimension when it is legal."""
    if n_out % 8:
        return None
    ov = _tile_override() or (None, None)
    item = jnp.dtype(dtype).itemsize
    cl, cs = _pad(n_in, _LANE), _pad(n_in, _sublane(dtype))
    for bs in _blocks(spatial, _BM_CANDIDATES, _LANE, ov[0]):
        bsp = _pad(bs, _LANE)
        for bo in _blocks(n_out, _BN_CANDIDATES, _sublane(dtype), ov[1]):
            bop = _pad(bo, _sublane(dtype))
            if _fits_vmem(cs * bsp, bop * cl, bop * bsp, n_in, item):
                return bo, bs
    return None


def conv_tile_failure(n_out, spatial, n_in, dtype=jnp.float32):
    """Why ``select_conv_tiles`` returned None — the fusion report's
    bail-out reason."""
    if n_out % 8:
        return f"num_filter={n_out} not divisible by 8"
    return (f"no Mosaic-legal block of the (num_filter={n_out}, "
            f"spatial={spatial}) output fits VMEM at C={n_in} in "
            f"{jnp.dtype(dtype).name}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _make_kernel(relu):
    def _kernel(x_ref, w_ref, scale_ref, shift_ref, o_ref):
        """One (bm, bn) output tile of the (M, K) @ (K, N) form:
        normalize (+ReLU) the x tile on the fly (VMEM, fused into the
        MXU feed) and contract over the whole K. The prologue runs in
        f32 — the VPU has no narrower arithmetic on v5e — and the MXU
        is fed in the input dtype."""
        x = x_ref[...]
        z = x.astype(jnp.float32) * scale_ref[...] + shift_ref[...]
        if relu:
            z = jnp.maximum(z, 0.0)
        o_ref[...] = jnp.dot(
            z.astype(x.dtype), w_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)
    return _kernel


def _make_nchw_kernel(relu):
    def _kernel(w_ref, x_ref, scale_ref, shift_ref, o_ref):
        """One (1, bo, bs) output block of the NCHW-native fused conv:
        normalize (+ReLU) the (1, C, bs) activation block on the fly
        and contract the (bo, C) weight block over the whole C. f32
        prologue, input-dtype MXU feed (see ``_make_kernel``)."""
        x = x_ref[0]                          # (C, bs)
        z = x.astype(jnp.float32) * scale_ref[...] + shift_ref[...]
        if relu:                              # (C, 1) broadcasts
            z = jnp.maximum(z, 0.0)
        o_ref[0] = jnp.dot(
            w_ref[...], z.astype(x.dtype),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)
    return _kernel


def _make_prologue_kernel(relu):
    def _kernel(x_ref, scale_ref, shift_ref, o_ref):
        """Whole-array BN-apply(+ReLU) prologue (interpret path): the
        normalized activation the fused-matmul kernel would stream."""
        z = x_ref[...] * scale_ref[...] + shift_ref[...]
        if relu:
            z = jnp.maximum(z, 0.0)
        o_ref[...] = z.astype(o_ref.dtype)
    return _kernel


def _mosaic_params(grid_rank):
    """Compiler parameters of the tiled kernels when Mosaic compiles
    them: every grid axis writes disjoint output blocks, and the VMEM
    scope is the one the tile selectors budgeted against."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * grid_rank,
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _conv1x1(xhat, w4):
    dn = jax.lax.conv_dimension_numbers(xhat.shape, w4.shape,
                                        ("NCHW", "OIHW", "NCHW"))
    return jax.lax.conv_general_dilated(
        xhat, w4, (1, 1), [(0, 0), (0, 0)], dimension_numbers=dn)


# ---------------------------------------------------------------------------
# the generic (M, K) @ (K, N) fused matmul (bench tool / kernel tests)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _fused_matmul(relu, bm, bn):
    from jax.experimental import pallas as pl
    kernel = _make_kernel(relu)

    @jax.custom_vjp
    def f(x, w, scale, shift):
        m, k = x.shape
        n = w.shape[1]
        interpret = interpret_mode()
        return pl.pallas_call(
            kernel,
            grid=(m // bm, n // bn),
            in_specs=[
                pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                pl.BlockSpec((k, bn), lambda i, j: (0, j)),
                pl.BlockSpec((1, k), lambda i, j: (0, 0)),
                pl.BlockSpec((1, k), lambda i, j: (0, 0)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            interpret=interpret,
            compiler_params=None if interpret else _mosaic_params(2),
            name="bn_relu_matmul",
        )(x, w, scale.reshape(1, k).astype(jnp.float32),
          shift.reshape(1, k).astype(jnp.float32))

    def f_fwd(x, w, scale, shift):
        # raw-input residuals: the normalized activation is recomputed
        # in f_bwd (one elementwise pass) rather than written out
        return f(x, w, scale, shift), (x, w, scale, shift)

    def f_bwd(res, g):
        x, w, scale, shift = res
        z = x * scale + shift
        xhat = (jnp.maximum(z, 0.0) if relu else z).astype(x.dtype)
        dxhat = jnp.dot(g, w.T, preferred_element_type=jnp.float32)
        dz = jnp.where(xhat > 0, dxhat, 0.0) if relu else dxhat
        dx = (dz * scale).astype(x.dtype)
        dscale = jnp.sum(dz * x, axis=0).astype(scale.dtype)
        dshift = jnp.sum(dz, axis=0).astype(scale.dtype)
        dw = jnp.dot(xhat.T, g,
                     preferred_element_type=jnp.float32).astype(w.dtype)
        return dx, dw, dscale, dshift

    f.defvjp(f_fwd, f_bwd)
    return f


def bn_relu_matmul(x, w, scale, shift, bm=None, bn=None, relu=True):
    """``act(x * scale + shift) @ w`` without materializing the
    normalized activation. x: (M, K); w: (K, N); scale/shift: (K,) — the
    folded BN parameters gamma/sqrt(var+eps) and beta - mu*scale.

    Tiles default to ``select_tiles``; explicit bm/bn must divide M/N
    (and, on the chip, be blocks Mosaic accepts). Interpreted off-TPU
    (:func:`interpret_mode`) so the same code path runs in CPU tests.
    Differentiable via a custom VJP (exact gradients of the composed
    expression, normalized activation recomputed in backward).
    """
    m, k = x.shape
    n = w.shape[1]
    if bm is None or bn is None:
        tiles = select_tiles(m, n, k, x.dtype)
        if tiles is None:
            raise ValueError(
                f"bn_relu_matmul: no tile Mosaic accepts for M={m}, "
                f"N={n}, K={k} in {x.dtype} (M and N must be divisible "
                "by 8 and one block must fit VMEM); pad the problem or "
                "pass explicit bm/bn")
        bm = tiles[0] if bm is None else bm
        bn = tiles[1] if bn is None else bn
    if m % bm or n % bn:
        raise ValueError(
            f"bn_relu_matmul needs M % bm == 0 and N % bn == 0 "
            f"(got M={m}, N={n}, bm={bm}, bn={bn}); pad the problem or "
            "pass smaller blocks — a truncated grid would leave output "
            "tiles uninitialized")
    return _fused_matmul(bool(relu), int(bm), int(bn))(x, w, scale, shift)


# ---------------------------------------------------------------------------
# the NCHW-native fused conv forward (used by the graph op)
# ---------------------------------------------------------------------------
def bn_relu_conv_nchw(x, w, scale, shift, relu=True):
    """NCHW-native fused BN-apply(+ReLU)+1×1-conv FORWARD: ``act(x *
    scale + shift) ⊛ w`` contracted over channels, x (B, C, H, W),
    w (O, C) → (B, O, H, W). On TPU this is the tiled fused-matmul
    kernel, compiled by Mosaic — the normalized activation never
    reaches HBM. Off-TPU (:func:`interpret_mode`; CPU tests) the
    interpreter must materialize it regardless, so the prologue runs as
    a whole-array interpreted Pallas kernel and the stock 1×1
    convolution does the contraction. Returns ``(out, xhat)``; xhat is
    None on TPU.

    Forward only; the graph op's custom VJP (analytic fused BN backward)
    lives in ``_fused_bn_conv_vjp``.

    Under an active :func:`mesh_scope` whose batch axis divides B, the
    pallas_call wraps itself in ``shard_map(..., check_vma=False)``
    over the batch dimension — per-device kernel on the batch shard,
    weights/folded-stats replicated — so the op composes with GSPMD
    partitioning instead of being an opaque custom call the mesh bind
    must reject."""
    from jax.experimental import pallas as pl
    b, c, h, w_sp = x.shape
    s = h * w_sp
    o = w.shape[0]
    ms = _batch_shards(b)
    if interpret_mode():
        kern = _make_prologue_kernel(relu)

        def _prologue(xl, sc, sh):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct(xl.shape, xl.dtype),
                interpret=True,
            )(xl, sc, sh)

        sc = scale.reshape(1, c, 1, 1)
        sh = shift.reshape(1, c, 1, 1)
        if ms is not None:
            from jax.sharding import PartitionSpec as P
            mesh, axis, _ = ms
            xhat = _shard_map(_prologue, mesh=mesh,
                              in_specs=(P(axis), P(), P()),
                              out_specs=P(axis),
                              check_vma=False)(x, sc, sh)
        else:
            xhat = _prologue(x, sc, sh)
        return _conv1x1(xhat, w.reshape(o, c, 1, 1)).astype(x.dtype), \
            xhat
    tiles = select_conv_tiles(o, s, c, x.dtype)
    if tiles is None:
        # the rewrite pass bails such sites by name (symbol/fusion.py);
        # reaching here means the pass and the op disagree on the dtype
        raise ValueError(
            f"bn_relu_conv_nchw: {conv_tile_failure(o, s, c, x.dtype)}")
    bo, bs = tiles
    kern = _make_nchw_kernel(relu)

    def _tiled(wl, xl, sc, sh):
        bl = xl.shape[0]          # per-device batch inside shard_map
        return pl.pallas_call(
            kern,
            grid=(bl, o // bo, s // bs),
            in_specs=[
                pl.BlockSpec((bo, c), lambda g, i, j: (i, 0)),
                pl.BlockSpec((1, c, bs), lambda g, i, j: (g, 0, j)),
                pl.BlockSpec((c, 1), lambda g, i, j: (0, 0)),
                pl.BlockSpec((c, 1), lambda g, i, j: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bo, bs),
                                   lambda g, i, j: (g, i, j)),
            out_shape=jax.ShapeDtypeStruct((bl, o, s), xl.dtype),
            interpret=False,
            compiler_params=_mosaic_params(3),
            name="bn_relu_conv1x1",
        )(wl, xl, sc, sh)

    xr = x.reshape(b, c, s)
    sc = scale.reshape(c, 1).astype(jnp.float32)
    sh = shift.reshape(c, 1).astype(jnp.float32)
    if ms is not None:
        from jax.sharding import PartitionSpec as P
        mesh, axis, _ = ms
        out = _shard_map(_tiled, mesh=mesh,
                         in_specs=(P(), P(axis), P(), P()),
                         out_specs=P(axis),
                         check_vma=False)(w, xr, sc, sh)
    else:
        out = _tiled(w, xr, sc, sh)
    return out.reshape(b, o, h, w_sp), None


# ---------------------------------------------------------------------------
# the graph op: BN(+ReLU)+1×1 conv with the analytic fused backward
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _fused_bn_conv_vjp(relu, batch_stats, fix_gamma, eps):
    """Whole-op custom VJP: (data, gamma, beta, moving_mean, moving_var,
    w2 (O, C)) -> (out, mean, var). The backward is the ANALYTIC fused
    BatchNorm backward (cuDNN BatchNormBackward coverage): d(data) is
    assembled in one full-tensor pass,

        dx = scale·dz + cx·x + c0,   scale/cx/c0 all (C,)-sized,

    instead of naive autodiff's separate mean-/var-chain passes.
    Running-stat inputs receive no gradient (reference semantics: aux
    states are not differentiated, batch_norm.cc)."""

    def stats(x):
        if batch_stats:
            return jnp.mean(x, axis=(0, 2, 3)), jnp.var(x, axis=(0, 2, 3))
        return None, None

    def fold(x, gamma, beta, mean, var):
        g = jnp.ones_like(gamma) if fix_gamma else gamma
        scale = g * jax.lax.rsqrt(var + eps)
        return g, scale, beta - mean * scale

    def fwd(x, gamma, beta, mm, mv, w2):
        mean, var = stats(x)
        if mean is None:
            mean, var = mm, mv
        _, scale, shift = fold(x, gamma, beta, mean, var)
        out, xhat = bn_relu_conv_nchw(x, w2, scale, shift, relu=relu)
        return out, mean, var, xhat

    @jax.custom_vjp
    def f(x, gamma, beta, mm, mv, w2):
        out, mean, var, _ = fwd(x, gamma, beta, mm, mv, w2)
        return out, mean, var

    def f_fwd(x, gamma, beta, mm, mv, w2):
        out, mean, var, xhat = fwd(x, gamma, beta, mm, mv, w2)
        # on TPU xhat is None: the backward recomputes it from the raw
        # residuals (that recompute IS the traffic win); the interpreter
        # materializes it anyway, so there it doubles as the residual
        return (out, mean, var), (x, gamma, beta, mean, var, w2, xhat)

    def f_bwd(res, cts):
        g_out, g_mean, g_var = cts
        x, gamma, beta, mean, var, w2, xhat = res
        b, c, h, w_sp = x.shape
        n = b * h * w_sp
        o = w2.shape[0]
        g_eff, scale, shift = fold(x, gamma, beta, mean, var)
        inv = jax.lax.rsqrt(var + eps)
        if xhat is None:
            z = x * scale[:, None, None] + shift[:, None, None]
            xhat = (jnp.maximum(z, 0.0) if relu else z).astype(x.dtype)
        # dxhat/dw through XLA's own conv-grad lowering
        _, conv_vjp = jax.vjp(_conv1x1, xhat, w2.reshape(o, c, 1, 1))
        dxhat, dw4 = conv_vjp(g_out.astype(xhat.dtype))
        # relu mask from xhat (xhat > 0 ⟺ z > 0)
        dz = jnp.where(xhat > 0, dxhat, 0.0) if relu else dxhat
        # (C,)-sized moments of dz in ONE variadic reduction (a second
        # pass re-reading dz would double the traffic);
        # sum(dz·(x-mean)) = s1 - mean·s0
        dzx = dz * x
        s0, s1 = jax.lax.reduce(
            (dz, dzx), (jnp.zeros((), dz.dtype), jnp.zeros((), dzx.dtype)),
            lambda a, b: (a[0] + b[0], a[1] + b[1]), (0, 2, 3))
        t = s1 - mean * s0
        dbeta = s0.astype(beta.dtype)
        dgamma = jnp.zeros_like(gamma) if fix_gamma \
            else (t * inv).astype(gamma.dtype)
        if batch_stats:
            # analytic training-mode dx — one assembly pass — plus the
            # (usually zero) cotangents of the mean/var outputs folded
            # into the same coefficients
            coef = g_eff * (inv ** 3) * t / n
            cx = -coef + 2.0 * g_var / n
            c0 = (-scale * s0 + coef * mean * n) / n + g_mean / n \
                - 2.0 * mean * g_var / n
            dx = (dz * scale[:, None, None] + x * cx[:, None, None]
                  + c0[:, None, None]).astype(x.dtype)
        else:
            # moving stats are constants wrt x; their output cotangents
            # belong to the running-stat inputs, which take no gradient
            dx = (dz * scale[:, None, None]).astype(x.dtype)
        return (dx, dgamma, dbeta, jnp.zeros_like(mean),
                jnp.zeros_like(var),
                dw4.reshape(o, c).astype(w2.dtype))

    f.defvjp(f_fwd, f_bwd)
    return f


# ---------------------------------------------------------------------------
# the residual-chain graph op: BN(+ReLU)+conv of ANY geometry with the
# same analytic fused backward (round 12's residual_fusion pass)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _fused_bn_convk_vjp(relu, batch_stats, fix_gamma, eps, stride, pad,
                        dilate, groups):
    """Whole-op custom VJP for the GENERAL conv case: (data, gamma,
    beta, moving_mean, moving_var, w4 (O, C/g, kh, kw)) -> (out, mean,
    var). The forward is the stock lax convolution over the normalized
    activation (no Pallas kernel — arbitrary k×k/stride/pad geometries
    don't tile like the 1×1 contraction), but the BACKWARD is the same
    analytic fused BatchNorm backward as the 1×1 op: the normalized
    activation is RECOMPUTED from raw residuals instead of stored
    (dropping an activation-sized saved tensor per site — the bytes win
    the pass manager's gate verifies), d(data) assembles in one
    full-tensor pass, and the (C,)-sized dz moments come from one
    variadic reduction. The conv half of the gradient goes through
    XLA's own conv-grad lowering via ``jax.vjp``."""

    def _conv(xhat, w4):
        dn = jax.lax.conv_dimension_numbers(xhat.shape, w4.shape,
                                            ("NCHW", "OIHW", "NCHW"))
        return jax.lax.conv_general_dilated(
            xhat, w4, stride, [(p, p) for p in pad], rhs_dilation=dilate,
            dimension_numbers=dn, feature_group_count=groups)

    def stats(x):
        if batch_stats:
            return jnp.mean(x, axis=(0, 2, 3)), jnp.var(x, axis=(0, 2, 3))
        return None, None

    def fold(gamma, beta, mean, var):
        g = jnp.ones_like(gamma) if fix_gamma else gamma
        scale = g * jax.lax.rsqrt(var + eps)
        return g, scale, beta - mean * scale

    def fwd(x, gamma, beta, mm, mv, w4):
        mean, var = stats(x)
        if mean is None:
            mean, var = mm, mv
        _, scale, shift = fold(gamma, beta, mean, var)
        z = x * scale[:, None, None] + shift[:, None, None]
        xhat = (jnp.maximum(z, 0.0) if relu else z).astype(x.dtype)
        out = _conv(xhat, w4.astype(x.dtype)).astype(x.dtype)
        return out, mean, var

    @jax.custom_vjp
    def f(x, gamma, beta, mm, mv, w4):
        return fwd(x, gamma, beta, mm, mv, w4)

    def f_fwd(x, gamma, beta, mm, mv, w4):
        out, mean, var = fwd(x, gamma, beta, mm, mv, w4)
        # raw-input residuals only: xhat recomputes in f_bwd (one
        # elementwise pass instead of an activation-sized store)
        return (out, mean, var), (x, gamma, beta, mean, var, w4)

    def f_bwd(res, cts):
        g_out, g_mean, g_var = cts
        x, gamma, beta, mean, var, w4 = res
        b, c, h, w_sp = x.shape
        n = b * h * w_sp
        g_eff, scale, shift = fold(gamma, beta, mean, var)
        inv = jax.lax.rsqrt(var + eps)
        z = x * scale[:, None, None] + shift[:, None, None]
        xhat = (jnp.maximum(z, 0.0) if relu else z).astype(x.dtype)
        _, conv_vjp = jax.vjp(_conv, xhat, w4.astype(x.dtype))
        dxhat, dw4 = conv_vjp(g_out.astype(xhat.dtype))
        dz = jnp.where(xhat > 0, dxhat, 0.0) if relu else dxhat
        dzx = dz * x
        s0, s1 = jax.lax.reduce(
            (dz, dzx), (jnp.zeros((), dz.dtype), jnp.zeros((), dzx.dtype)),
            lambda a, b: (a[0] + b[0], a[1] + b[1]), (0, 2, 3))
        t = s1 - mean * s0
        dbeta = s0.astype(beta.dtype)
        dgamma = jnp.zeros_like(gamma) if fix_gamma \
            else (t * inv).astype(gamma.dtype)
        if batch_stats:
            coef = g_eff * (inv ** 3) * t / n
            cx = -coef + 2.0 * g_var / n
            c0 = (-scale * s0 + coef * mean * n) / n + g_mean / n \
                - 2.0 * mean * g_var / n
            dx = (dz * scale[:, None, None] + x * cx[:, None, None]
                  + c0[:, None, None]).astype(x.dtype)
        else:
            dx = (dz * scale[:, None, None]).astype(x.dtype)
        return (dx, dgamma, dbeta, jnp.zeros_like(mean),
                jnp.zeros_like(var), dw4.astype(w4.dtype))

    f.defvjp(f_fwd, f_bwd)
    return f


def _tup2(v, default):
    if v is None:
        return default
    if isinstance(v, (int, float)):
        return (int(v), int(v))
    return tuple(int(x) for x in v)


@register_op("_FusedBNReLUConvK", num_outputs=3)
def fused_bn_relu_conv_general(data, gamma, beta, moving_mean, moving_var,
                               weight, bias=None, eps=1e-3, momentum=0.9,
                               fix_gamma=True, use_global_stats=False,
                               act_type="relu", axis=1, kernel=None,
                               stride=None, pad=None, dilate=None,
                               num_filter=None, num_group=1, no_bias=True,
                               training=False, **kw):
    """BatchNorm -> [Activation(relu) ->] Convolution of ANY geometry as
    ONE op with the analytic fused BN backward (internal; substituted by
    symbol/passes/residual_fusion.py, never user-built). Mirrors
    BatchNorm's (out, mean, var) output layout and (…, moving_mean,
    moving_var) input positions 3/4 so the executors' running-aux fold
    (Symbol._bn_aux_updates) applies unchanged; ``momentum`` is consumed
    there, not here."""
    batch_stats = bool(training) and not use_global_stats
    out, mean, var = _fused_bn_convk_vjp(
        act_type == "relu", batch_stats, bool(fix_gamma), float(eps),
        _tup2(stride, (1, 1)), _tup2(pad, (0, 0)), _tup2(dilate, (1, 1)),
        int(num_group or 1),
    )(data, gamma, beta, moving_mean, moving_var, weight)
    if not no_bias and bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out.astype(data.dtype), mean, var


@register_op("_FusedBNReLUConv", num_outputs=3)
def fused_bn_relu_conv(data, gamma, beta, moving_mean, moving_var, weight,
                       bias=None, eps=1e-3, momentum=0.9, fix_gamma=True,
                       use_global_stats=False, act_type="relu", axis=1,
                       num_filter=None, no_bias=True, training=False, **kw):
    """BatchNorm -> Activation(relu) -> Convolution(1x1/s1/p0) as ONE op
    (internal; substituted by symbol/fusion.py, never user-built).

    Returns (conv_out, batch_mean, batch_var) — BatchNorm's output
    layout, with moving_mean/moving_var at input positions 3/4 like
    BatchNorm, so the executors' running-aux fold (Symbol._bn_aux_updates)
    applies to this op unchanged. ``momentum`` is consumed there, not
    here."""
    C = data.shape[1]
    O = weight.shape[0]
    batch_stats = bool(training) and not use_global_stats
    out, mean, var = _fused_bn_conv_vjp(
        act_type == "relu", batch_stats, bool(fix_gamma), float(eps),
    )(data, gamma, beta, moving_mean, moving_var,
      weight.reshape(O, C).astype(data.dtype))
    if not no_bias and bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out.astype(data.dtype), mean, var
