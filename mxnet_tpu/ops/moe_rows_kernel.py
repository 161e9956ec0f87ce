"""The routed experts' row movement as kernels (Pallas on Mosaic), for
``ops.seq._dispatch``, ``_dispatch_pooled`` and ``_combine`` where their
program is lowered for a TPU: a token's row into the experts' buffer and
the experts' rows back, added up by token. Each row is read from the
device's memory once and written once.

Why not a copy a row. In a ``(rows, width)`` array a row of bfloat16 shares
its ``(16, 128)`` memory tiles with fifteen neighbours, and Mosaic takes no
asynchronous copy of one row of it ("slice shape must be aligned to
tiling"); the form in which a row lies in one piece, ``(rows, width / 128,
128)``, XLA reaches only by a copy of the whole array in the device's
memory, on each side of a kernel. So both kernels work in two phases over
one grid. First the source is read tile by tile, in order, through the
pipeline, and each tile is written into a VMEM scratch that holds the whole
source as ``(rows, width / 128, 128)``: there a row is whole registers, and
the reshape costs about two bundles a register. Then the output is written
tile by tile: its rows come out of the scratch by a vector load at a
dynamic row, and the tile goes back to the matrix form in the store. The
source's block index stands still through the second phase and the
output's through the first, and a block whose index stands still is not
moved. What it costs is VMEM: the whole source has to fit (``takes``).

``rows_by_index`` — ``out[i] = src[index[i]]``, zeros where ``index[i]``
names no row (``>= src``'s rows); ``index`` is a scalar-prefetch operand.
It is the dispatch forward (``buf = rows[token]``). The other gather, of
the combine's backward, stays ``jnp.take``: XLA's gather of bfloat16 rows
reads 0.28 ms where this kernel reads 0.23 and the bytes say 0.14, and a
kernel that also formed the gates' gradient from the gathered tile read
the same as the take and XLA's two products (PERF.md, PR 48).

``rows_by_token`` — ``out[t] = sum of scale[j] * src[j]`` over the pool's
rows ``j`` whose ``token[j]`` is ``t``, each product and the sum in float32
in VMEM, rounded once, in the store. It is the combine forward (``src`` the
experts' output, ``scale`` the rows' gates) and the dispatch backward
(``src`` the pool's cotangent, no scale). It replaces a scatter-add: the
pool is sorted by (expert, token), so the rows of a tile of tokens are one
stretch of each expert's rows, and ``stretches`` finds them from ``token``
and the experts' first rows (comparisons; no second sort). Only rows
that hold a pair are added, however many a token has: a tile's work follows
the pairs its tokens hold and a step's follows the pool's fill, which the
router's balance keeps at the same share from seed to seed (PERF.md,
``moe_buffer_fill.train``), while the first phase, the larger, reads every
row of the pool whatever it holds. The grouped products beside it still
compute every row (``ops.seq.grouped_product``'s rule is about them).

``takes`` is the tiling rule: shapes it does not take keep ``jnp.take``
and ``.at[].add``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GAUGE = "moe::rows_kernel_sites"

_F32 = jnp.float32
_LANES = 128
_TILES = (256, 128)
# what a call may hold in VMEM: the resident source, the blocks (twice
# each, for the pipeline) and the float32 sums; a call asks for that and
# ``_SPARE_BYTES`` for what the compiler spills, not for a fixed limit, so
# that a small pool leaves the rest of VMEM to the program around it
_BUDGET_BYTES = 88 * 1024 * 1024
_SPARE_BYTES = 12 * 1024 * 1024
# the index arrays live in the scalar memory, whole
_SCALAR_BYTES = 256 * 1024


# ---------------------------------------------------------------------------
# the tiling rule
# ---------------------------------------------------------------------------
def resident_bytes(rows, width, itemsize):
    """What ``rows`` rows take in the scratch: a row's ``width / 128``
    sublanes are padded to whole tiles (8 of 32 bits, 16 of 16)."""
    sub = 8 * (4 // itemsize)
    return rows * (-(-(width // _LANES) // sub) * sub) * _LANES * itemsize


def held_bytes(rows, width, itemsize, tile):
    """What a call over a source of ``rows`` rows holds in VMEM: the
    source, a block of the source and of the output twice each, and a
    tile's float32 sums and their rounded copy."""
    return resident_bytes(rows, width, itemsize) \
        + tile * width * (4 * itemsize + 8)


def takes(pool, tokens, width, dtype):
    """The rows of a tile (of the pool and of the tokens alike), or nothing
    where the kernels do not take the shapes: the width whole lane tiles,
    pool and tokens whole tiles of 256 or 128 rows, the index arrays of a
    call within the scalar memory, and the larger of the two sources whole
    in VMEM beside a call's blocks and float32 sums."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4) or width % _LANES:
        return None
    tile = next((t for t in _TILES if pool % t == 0 and tokens % t == 0),
                None)
    if tile is None or 3 * 4 * pool > _SCALAR_BYTES:
        return None
    held = held_bytes(max(pool, tokens), width, itemsize, tile)
    return tile if held <= _BUDGET_BYTES else None


def stretches(token, tokens, starts, tile):
    """Where the rows of each tile of tokens lie in the pool: ``token`` (P,)
    int32 (``tokens`` where a row holds no pair), ``starts`` (E,) int32 the
    first row of each held expert's, rising. ``(tokens / tile + 1) * E``
    int32; the tile ``i`` has, for every expert ``r``, the rows ``[i * E +
    r]`` up to ``[(i + 1) * E + r]``. Inside an expert's rows ``token``
    rises, so expert and token together are a key that never falls, and a
    tile's edge in it is the count of the keys below. Counted in two
    levels, no loop and no second sort: the blocks of 128 keys that lie
    wholly below (their last key does), then the keys below in the one
    block after them; every edge against every key is fifty times the
    comparisons and three times the time (PERF.md, PR 48). A row that
    holds no pair is past every edge."""
    token = token.reshape(-1).astype(jnp.int32)
    regions = starts.shape[0]
    row = jnp.arange(token.shape[0], dtype=jnp.int32)
    region = jnp.sum(row[None, :] >= starts[1:, None].astype(jnp.int32),
                     axis=0, dtype=jnp.int32)
    blocks = (region * (tokens + 1) + token).reshape(-1, _LANES)
    edges = ((jnp.arange(tokens // tile + 1, dtype=jnp.int32) * tile)[:, None]
             + jnp.arange(regions, dtype=jnp.int32)[None, :] * (tokens + 1)
             ).reshape(-1)
    whole = jnp.sum(blocks[:, -1][:, None] < edges[None, :], axis=0,
                    dtype=jnp.int32)
    at = jnp.minimum(whole, blocks.shape[0] - 1)
    return at * _LANES + jnp.sum(blocks[at] < edges[:, None], axis=1,
                                 dtype=jnp.int32)


def _unrolled(count, body, by=8):
    """``body(r)`` for ``r`` below ``count``, ``by`` to a trip (the lowering
    unrolls a loop wholly or not at all)."""
    def trip(k, carry):
        for r in range(by):
            body(k * by + r)
        return carry

    lax.fori_loop(0, count // by, trip, None)


def _two_phases(src_ref, held, tile, src_tiles, write):
    """A kernel's body: the grid's first ``src_tiles`` steps put the
    source's tile into the scratch, each later one has ``write(at)`` write
    the output's tile ``at``."""
    i = pl.program_id(0)

    @pl.when(i < src_tiles)
    def _():
        held[pl.ds(i * tile, tile)] = src_ref[...].reshape(
            (tile,) + held.shape[1:])

    @pl.when(i >= src_tiles)
    def _():
        write(i - src_tiles)


def _call(kernel, name, scalars, src, out_rows, tile, stage, interpret):
    """``kernel`` over a grid of the source's tiles, then the output's
    (out_rows, width). ``stage``: the dtype of a tile-sized scratch in the
    resident form. The kernel gets the scalars, the source's block, the
    output's, the resident scratch and the stage."""
    rows, width = src.shape
    src_tiles = rows // tile
    row = (width // _LANES, _LANES)
    return pl.pallas_call(
        functools.partial(kernel, tile=tile, src_tiles=src_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(src_tiles + out_rows // tile,),
            in_specs=[pl.BlockSpec(
                (tile, width),
                lambda i, *_: (jnp.minimum(i, src_tiles - 1), 0))],
            out_specs=pl.BlockSpec(
                (tile, width),
                lambda i, *_: (jnp.maximum(i - src_tiles, 0), 0)),
            scratch_shapes=[pltpu.VMEM((rows,) + row, src.dtype),
                            pltpu.VMEM((tile,) + row, stage)]),
        out_shape=jax.ShapeDtypeStruct((out_rows, width), src.dtype),
        name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=held_bytes(rows, width, src.dtype.itemsize, tile)
            + _SPARE_BYTES))(*scalars, src)


# ---------------------------------------------------------------------------
# rows by index
# ---------------------------------------------------------------------------
def _by_index_kernel(index_ref, src_ref, out_ref, held, stage, *, tile,
                     src_tiles):
    rows = held.shape[0]
    zero = jnp.zeros(stage.shape[1:], stage.dtype)

    def write(at):
        def move(r):
            row = index_ref[at * tile + r]
            stage[r] = jnp.where(row < rows, held[jnp.minimum(row, rows - 1)],
                                 zero)

        _unrolled(tile, move)
        out_ref[...] = stage[...].reshape(out_ref.shape)

    _two_phases(src_ref, held, tile, src_tiles, write)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def rows_by_index(index, src, tile, interpret=False):
    """``src[index]`` with zeros where ``index`` names no row: ``index``
    (P,) int32, ``src`` (rows, width); (P, width)."""
    return _call(_by_index_kernel, "moe_rows_by_index_kernel", (index,), src,
                 index.shape[0], tile, src.dtype, interpret)


# ---------------------------------------------------------------------------
# rows added up by token
# ---------------------------------------------------------------------------
def _by_token_kernel(*refs, tile, src_tiles, regions, scaled):
    token_ref, stretch_ref = refs[:2]
    scale_ref = refs[2] if scaled else None
    src_ref, out_ref, held, acc = refs[-4:]

    def write(at):
        acc[...] = jnp.zeros_like(acc)

        def add(j, carry):
            row = held[j].astype(_F32)
            if scaled:
                row = row * scale_ref[j]
            t = token_ref[j] - at * tile
            acc[t] = acc[t] + row
            return carry

        def region(r, carry):
            return lax.fori_loop(stretch_ref[at * regions + r],
                                 stretch_ref[(at + 1) * regions + r], add,
                                 carry)

        lax.fori_loop(0, regions, region, None)
        out_ref[...] = acc[...].reshape(out_ref.shape).astype(out_ref.dtype)

    _two_phases(src_ref, held, tile, src_tiles, write)


@functools.partial(jax.jit, static_argnames=("tokens", "tile", "interpret"))
def rows_by_token(token, scale, src, tokens, starts, tile, interpret=False):
    """``out[t] = sum of scale[j] * src[j]`` over the rows with ``token[j]
    == t``, in float32, rounded once: ``token`` (P,) int32 sorted by
    (expert, token) with ``tokens`` where a row holds no pair, ``starts``
    (E,) int32 each expert's first row; ``scale`` (P,) float32 or nothing
    for ones; ``src`` (P, width); (tokens, width) in ``src``'s dtype."""
    token = token.reshape(-1).astype(jnp.int32)
    scalars = (token, stretches(token, tokens, starts, tile)) \
        + (() if scale is None else (scale.reshape(-1).astype(_F32),))
    return _call(
        functools.partial(_by_token_kernel, regions=starts.shape[0],
                          scaled=scale is not None),
        "moe_rows_by_token_kernel", scalars, src, tokens, tile, _F32,
        interpret)
