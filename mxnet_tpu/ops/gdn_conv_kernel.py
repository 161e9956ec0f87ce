"""The Gated DeltaNet mixer's way from its kept projection to the rule's
operands as kernels (Pallas on Mosaic), for ``ops.seq.gated_delta_net``
where its program is lowered for a TPU and the heads are whole lane tiles:
the causal depthwise convolution over ``[q | k | v]``, SiLU, and the L2
norm of every ``q`` and ``k`` head, one forward and one backward kernel
under one ``custom_vjp``.

The projection stays as the product wrote it, (B, L, 2 G N + 2 H P) with
columns ``[q | k | v | z]``: the kernels window the ``q``, the ``k`` and
the ``v`` columns of a block of rows at their offsets in the packed array
(three views of one operand), so nothing is sliced out or padded, and the
gate's ``z`` columns are never read. The forward kernel writes ``q`` and
``k`` (B, L, G N) and ``v`` (B, L, H P), row-major, as
``gdn_kernel.forward`` reads them.

The grid is (column parts, batch, blocks of ``block_rows`` rows); a step
works one part of each of ``q``, ``k`` and ``v``. The ``taps - 1`` rows
before a block come as a halo, a second window of the same operand one
sublane tile high that ends where the block begins; a batch entry's first
block takes zeros in its place. Inside a step a loop walks the heads of a
part (``v``: its lane tiles). A head's rows of the block go to a float32
VMEM scratch below the halo's, once, and every tap is then a load from
that scratch at its own row offset (a vector load may begin at any row: the
shifted rows cost load slots and no vector operation; turned in registers
they were a third of the forward kernel's). Groups of ``_GROUP`` rows are
then a few registers: the taps multiplied and summed, SiLU by one
``tanh``, and for ``q`` and ``k`` the norm over the head, a reduction
along the lanes of values already in registers.

The backward kernel walks the blocks in reverse. For a head it forms the
pre-activation again from the same scratch (a unit keeps nothing new),
goes back through the norm (``r (dy - y sum(dy y))``), the rounding (as
the identity) and SiLU, and writes the pre-activation's cotangent to a
second scratch, above the ``taps - 1`` first rows of the block after (that
block was the step before; its rows wait in a third scratch, and a batch
entry's last block takes zeros); the rows' cotangent is the taps over that
scratch at row offsets again. The weight's gradient is summed over the
rows of a group in registers, over the groups in the loop's carry and over
blocks and batch in the resident output block, float32 throughout.

The arithmetic between load and store is float32. The values are rounded
to the operand's dtype where the plain form and the benchmark's reference
hold them: after SiLU (what the norm reads) and on store.

What a call holds in VMEM is stated by ``held_bytes`` and held under
``_BUDGET_BYTES`` by ``takes``, the rule of shapes: shapes it does not
take stay the plain form.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GAUGE = "gdn::conv_kernel_sites"

_F32 = jnp.float32
_LANES = 128
_SUBLANES = 8
#: the rows a grid step works of a sequence longer than that
_BLOCK = 256
#: the most rows a pass of the innermost loop works
_GROUP = 64
#: the most columns of ``[q | k | v]`` a grid step works
_COLUMNS = 4096
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# of them, what the blocks and the scratch a call names may take
_BUDGET_BYTES = 32 * 1024 * 1024
_EPS = 1e-6


# ---------------------------------------------------------------------------
# the rule of shapes
# ---------------------------------------------------------------------------
#: the mixer's heads: ``keys`` heads of ``n`` for ``q`` and for ``k``,
#: ``values`` heads of ``p``
Heads = collections.namedtuple("Heads", "keys n values p")


def _tile(dtype):
    """The rows of a sublane tile of ``dtype``."""
    return 32 // jnp.dtype(dtype).itemsize


def parts(heads):
    """The column parts of the grid: the fewest that leave a step at most
    ``_COLUMNS`` columns with whole ``q`` and ``k`` heads, whole lane tiles
    of ``v``, and ``v``'s window at a whole multiple of its width in the
    packed rows; None where there are none."""
    wide_k, wide_v = heads.keys * heads.n, heads.values * heads.p
    for count in range(1, heads.keys + 1):
        if heads.keys % count or wide_v % (count * _LANES):
            continue
        if (2 * wide_k) % (wide_v // count):
            continue
        if (2 * wide_k + wide_v) // count <= _COLUMNS:
            return count
    return None


def block_rows(length):
    """``(padded length, rows a step)``: steps of ``_BLOCK`` rows, a
    shorter sequence one step of whole groups."""
    rows = _BLOCK if length >= _BLOCK else -(-length // 32) * 32
    return -(-length // rows) * rows, rows


def held_bytes(heads, itemsize):
    """What the backward call, the larger, names in VMEM: every block
    twice, for the pipeline (the rows, their halo, the three cotangents in,
    the rows' cotangent out, the taps in and their gradient out), and the
    carried rows' scratch and a head's rows and their cotangent in
    float32."""
    wide = (2 * heads.keys * heads.n + heads.values * heads.p) \
        // (parts(heads) or 1)
    blocks = (3 * _BLOCK + 32 // itemsize) * wide * itemsize \
        + 2 * _SUBLANES * wide * 4
    return 2 * blocks + _SUBLANES * wide * 4 \
        + 2 * (_BLOCK + _SUBLANES) * max(heads.n, _LANES) * 4


def takes(heads, taps, dtype, weight_dtype):
    """Whether the kernels take a mixer of ``heads`` whose convolution has
    ``taps`` taps, the projection in ``dtype``: key and value heads whole
    lane tiles of 128, 2 to 9 taps (the rows before a block are one
    sublane tile of float32), bfloat16 or float32 and the taps' weight in
    the same dtype, column parts that exist, and what a call holds in
    VMEM under the budget. Shapes alone."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    if jnp.dtype(weight_dtype) != jnp.dtype(dtype):
        return False
    if min(heads) <= 0 or heads.n % _LANES or heads.p % _LANES:
        return False
    if not 2 <= taps <= _SUBLANES + 1 or parts(heads) is None:
        return False
    return held_bytes(heads, jnp.dtype(dtype).itemsize) <= _BUDGET_BYTES


# ---------------------------------------------------------------------------
# a head's rows in a block
# ---------------------------------------------------------------------------
def _group_of(rows):
    """The rows of a group: the most, at most ``_GROUP``, that divide a
    block of ``rows``."""
    return max(size for size in (32, 64, 128)
               if size <= _GROUP and rows % size == 0)


def _rounded(x, dtype):
    return x.astype(dtype).astype(_F32)


def _inverse_norm(s):
    """One over the L2 norm of ``s`` along the lanes, (rows, 1)."""
    return lax.rsqrt(jnp.sum(s * s, axis=1, keepdims=True) + _EPS)


def _sum(terms):
    return functools.reduce(lambda a, b: a + b, terms)


def _taps_of(w_ref, cols, taps, rows, scale=1.0):
    """The taps of the channels ``cols`` along ``rows`` rows, times
    ``scale``."""
    return [jnp.broadcast_to(scale * w_ref[j:j + 1, cols], (rows, cols.size))
            for j in range(taps)]


def _rows_to(buf, x_ref, halo_ref, cols, first):
    """The block's rows of the channels ``cols`` into ``buf`` in float32,
    below the eight rows before them (zeros where ``first``)."""
    rows, top = x_ref.shape[0], halo_ref.shape[0] - _SUBLANES
    lanes = slice(0, cols.size)
    buf[0:_SUBLANES, lanes] = jnp.where(
        first, 0.0, halo_ref[top:top + _SUBLANES, cols].astype(_F32))
    buf[_SUBLANES:_SUBLANES + rows, lanes] = x_ref[:, cols].astype(_F32)


def _shifted(buf, base, size, lanes, taps):
    """``[x_{t - taps + 1}, ..., x_t]`` for the ``size`` rows from
    ``base`` of what ``_rows_to`` wrote: loads at row offsets."""
    return [buf[pl.ds(base + _SUBLANES - (taps - 1 - j), size), lanes]
            for j in range(taps)]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _forward_part(x_ref, halo_ref, w_ref, o_ref, buf, first, unit, scale,
                  taps):
    """One part's columns of a block: ``unit`` lanes at a time (a head
    where ``scale`` says the part is normalised), the groups of rows in
    turn."""
    dtype = o_ref.dtype
    size = _group_of(x_ref.shape[0])
    lanes = slice(0, unit)

    def one(u, carry):
        cols = pl.ds(pl.multiple_of(u * unit, _LANES), unit)
        # half the pre-activation: silu(x) = x / 2 + x / 2 tanh(x / 2)
        ws = _taps_of(w_ref, cols, taps, size, 0.5)
        _rows_to(buf, x_ref, halo_ref, cols, first)

        def group(g, carry):
            base = pl.multiple_of(g * size, size)
            half = _sum(w * x for w, x in zip(
                ws, _shifted(buf, base, size, lanes, taps)))
            s = half + half * jnp.tanh(half)
            if scale is not None:
                s = _rounded(s, dtype)
                s = s * (_inverse_norm(s) * scale)
            o_ref[pl.ds(base, size), cols] = s.astype(dtype)
            return carry

        return lax.fori_loop(0, x_ref.shape[0] // size, group, carry,
                             unroll=True)

    lax.fori_loop(0, x_ref.shape[1] // unit, one, 0)


def _fwd_kernel(xq, xk, xv, hq, hk, hv, wq, wk, wv, oq, ok, ov, buf, *,
                heads, taps):
    first = pl.program_id(2) == 0
    _forward_part(xq, hq, wq, oq, buf, first, heads.n, heads.n ** -0.5, taps)
    _forward_part(xk, hk, wk, ok, buf, first, heads.n, 1.0, taps)
    _forward_part(xv, hv, wv, ov, buf, first, _LANES, None, taps)


def _specs(heads, rows, tile, taps, at):
    """Block specs of a grid (part, batch, block) whose step ``i`` works
    the block ``at(i)``: for ``q``, ``k`` and ``v``, their windows in the
    packed rows, the halos before them and their taps, and the windows of
    arrays that hold one of them alone."""
    count = parts(heads)
    wide_k = heads.keys * heads.n // count
    wide_v = heads.values * heads.p // count
    offsets = (0, count, 2 * heads.keys * heads.n // wide_v)
    wides = (wide_k, wide_k, wide_v)
    up = rows // tile

    def before(i):
        return jnp.maximum(at(i) * up - 1, 0)

    return {
        "packed": [pl.BlockSpec((None, rows, w),
                                lambda c, b, i, o=o: (b, at(i), o + c))
                   for w, o in zip(wides, offsets)],
        "halo": [pl.BlockSpec((None, tile, w),
                              lambda c, b, i, o=o: (b, before(i), o + c))
                 for w, o in zip(wides, offsets)],
        "taps": [pl.BlockSpec((taps, w), lambda c, b, i, o=o: (0, o + c))
                 for w, o in zip(wides, offsets)],
        "alone": [pl.BlockSpec((None, rows, w), lambda c, b, i: (b, at(i), c))
                  for w in wides],
    }


def _call(kernel, name, grid, interpret, **specs):
    return pl.pallas_call(
        kernel, grid=grid, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES), **specs)


def _rows_scratch(rows, heads):
    """A head's rows of a block in float32, eight rows beside them."""
    return pltpu.VMEM((rows + _SUBLANES, max(heads.n, _LANES)), _F32)


def _padded(x, length):
    if x.shape[1] == length:
        return x
    return jnp.pad(x, ((0, 0), (0, length - x.shape[1]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def forward(qkvz, weight, heads, interpret=False):
    """``(q, k, v)``, the rule's operands (B, L, G N), (B, L, G N) and
    (B, L, H P) in ``qkvz``'s dtype, from the packed projection ``qkvz``
    (B, L, 2 G N + 2 H P) and the taps ``weight`` (2 G N + H P, taps):
    ``silu(conv(.))`` a channel, ``q`` and ``k`` then L2-normalised a
    head, ``q`` scaled by ``N ** -0.5``. (Jitted, as ``backward`` is: a
    step's like layers and both passes of a recomputation unit then share
    one trace of the kernel.)"""
    heads = Heads(*heads)
    bsz, length, _ = qkvz.shape
    taps = weight.shape[1]
    padded, rows = block_rows(length)
    specs = _specs(heads, rows, _tile(qkvz.dtype), taps, lambda i: i)
    x = _padded(qkvz, padded)
    w = weight.astype(_F32).T
    wide_k, wide_v = heads.keys * heads.n, heads.values * heads.p
    outs = _call(
        functools.partial(_fwd_kernel, heads=heads, taps=taps),
        "gdn_conv_fwd_kernel", (parts(heads), bsz, padded // rows),
        interpret,
        in_specs=specs["packed"] + specs["halo"] + specs["taps"],
        out_specs=specs["alone"],
        out_shape=[jax.ShapeDtypeStruct((bsz, padded, wide), qkvz.dtype)
                   for wide in (wide_k, wide_k, wide_v)],
        scratch_shapes=[_rows_scratch(rows, heads)])(
            x, x, x, x, x, x, w, w, w)
    return tuple(o[:, :length] for o in outs)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _backward_part(x_ref, halo_ref, w_ref, dy_ref, dx_ref, dw_ref, after_ref,
                   buf, d_buf, first, last, unit, scale, taps):
    """One part's columns of a block, ``unit`` lanes at a time: the
    pre-activation's cotangent of every group into ``d_buf``, then the
    rows' cotangent from it."""
    dtype = dx_ref.dtype
    rows = x_ref.shape[0]
    size = _group_of(rows)
    lanes = slice(0, unit)

    def one(u, carry):
        cols = pl.ds(pl.multiple_of(u * unit, _LANES), unit)
        halves = _taps_of(w_ref, cols, taps, size, 0.5)
        _rows_to(buf, x_ref, halo_ref, cols, first)
        d_buf[rows:rows + _SUBLANES, lanes] = jnp.where(
            last, 0.0, after_ref[:, cols])

        def back(g, sums):
            base = pl.multiple_of(g * size, size)
            xs = _shifted(buf, base, size, lanes, taps)
            half = _sum(w * x for w, x in zip(halves, xs))
            t = jnp.tanh(half)
            bent = half * t
            d = dy_ref[pl.ds(base, size), cols].astype(_F32)
            if scale is not None:
                s = _rounded(half + bent, dtype)
                r = _inverse_norm(s)
                y = s * r
                d = d - y * jnp.sum(d * y, axis=1, keepdims=True)
                d = d * (r if scale == 1.0 else r * scale)
            # silu'(x) = sig (1 + x (1 - sig)), x (1 - sig) = x/2 (1 - t)
            dpre = d * ((0.5 + 0.5 * t) * (1.0 + (half - bent)))
            d_buf[pl.ds(base, size), lanes] = dpre
            return tuple(
                acc + _sum((dpre * x)[k:k + _SUBLANES]
                           for k in range(0, size, _SUBLANES))
                for acc, x in zip(sums, xs))

        sums = lax.fori_loop(
            0, rows // size, back,
            (jnp.zeros((_SUBLANES, unit), _F32),) * taps, unroll=True)
        ws = _taps_of(w_ref, cols, taps, size)

        def rows_of(g, carry):
            base = pl.multiple_of(g * size, size)
            dx_ref[pl.ds(base, size), cols] = _sum(
                w * d_buf[pl.ds(base + taps - 1 - j, size), lanes]
                for j, w in enumerate(ws)).astype(dtype)
            return carry

        lax.fori_loop(0, rows // size, rows_of, 0, unroll=True)
        after_ref[:, cols] = d_buf[0:_SUBLANES, lanes]
        for j, acc in enumerate(sums):
            dw_ref[j:j + 1, cols] += jnp.sum(acc, axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, x_ref.shape[1] // unit, one, 0)


def _bwd_kernel(xq, xk, xv, hq, hk, hv, wq, wk, wv, dq, dk, dv,
                dxq, dxk, dxv, dwq, dwk, dwv, aq, ak, av, buf, d_buf, *,
                heads, taps):
    # the blocks in reverse: the step's block is the first of its batch
    # entry where it is the last step, and the other way around
    first = pl.program_id(2) == pl.num_programs(2) - 1
    last = pl.program_id(2) == 0

    @pl.when(last & (pl.program_id(1) == 0))
    def _():
        for dw in (dwq, dwk, dwv):
            dw[...] = jnp.zeros_like(dw)

    _backward_part(xq, hq, wq, dq, dxq, dwq, aq, buf, d_buf, first, last,
                   heads.n, heads.n ** -0.5, taps)
    _backward_part(xk, hk, wk, dk, dxk, dwk, ak, buf, d_buf, first, last,
                   heads.n, 1.0, taps)
    _backward_part(xv, hv, wv, dv, dxv, dwv, av, buf, d_buf, first, last,
                   _LANES, None, taps)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def backward(qkvz, weight, dq, dk, dv, heads, interpret=False):
    """``(d_conv, d_weight)`` from ``forward``'s operands and its outputs'
    cotangents: the cotangent of ``qkvz``'s convolved columns (B, L, 2 G N
    + H P) in its dtype, as three arrays ``q``'s, ``k``'s and ``v``'s, and
    the taps' (2 G N + H P, taps) in ``weight``'s."""
    heads = Heads(*heads)
    bsz, length, _ = qkvz.shape
    taps = weight.shape[1]
    padded, rows = block_rows(length)
    blocks = padded // rows
    specs = _specs(heads, rows, _tile(qkvz.dtype), taps,
                   lambda i: blocks - 1 - i)
    x = _padded(qkvz, padded)
    w = weight.astype(_F32).T
    cots = [_padded(d.astype(qkvz.dtype), padded) for d in (dq, dk, dv)]
    count = parts(heads)
    wides = [s.block_shape[-1] for s in specs["alone"]]
    sums = [pl.BlockSpec((taps, wide), lambda c, b, i: (0, c))
            for wide in wides]
    *d_rows, dwq, dwk, dwv = _call(
        functools.partial(_bwd_kernel, heads=heads, taps=taps),
        "gdn_conv_bwd_kernel", (count, bsz, blocks), interpret,
        in_specs=specs["packed"] + specs["halo"] + specs["taps"]
        + specs["alone"],
        out_specs=specs["alone"] + sums,
        out_shape=[jax.ShapeDtypeStruct(d.shape, qkvz.dtype) for d in cots]
        + [jax.ShapeDtypeStruct((taps, wide * count), _F32)
           for wide in wides],
        scratch_shapes=[pltpu.VMEM((_SUBLANES, wide), _F32)
                        for wide in wides]
        + [_rows_scratch(rows, heads)] * 2)(
            x, x, x, x, x, x, w, w, w, *cots)
    d_weight = jnp.concatenate([dwq, dwk, dwv], axis=1).T.astype(weight.dtype)
    return tuple(d[:, :length] for d in d_rows), d_weight
