"""The routed experts' grouped products as kernels (Pallas on Mosaic), for
``ops.seq.pooled_gated_product`` where its program is lowered for a TPU:
forward, the rows' gradients and the weights' gradients of ``gate = buf
W1[g]``, ``up = buf W3[g]``, ``out = (silu(gate) * up) W2[g]``, ``g`` the
expert a row of the pool belongs to by ``sizes``.

The pool is walked in tiles of ``tile`` rows, group by group. The walk is
a list of *visits*, (group, tile) pairs in the pool's order, computed from
``sizes`` on the device (``visits``) and handed to the kernels as
scalar-prefetch operands: a tile that holds rows of two experts is visited
once for each, and a visit stores (or adds up) only the rows of its own
group. The grid is static, as long as the worst routing needs (``rows /
tile`` tiles and ``E - 1`` group ends inside a tile); the visits a routing
does not need come last, repeat the last visit's blocks, so that nothing
moves, and compute nothing. Every row of the pool is computed, whether it
holds a pair or nothing.

Two kernels carry the six calls:

``_rows_kernel``, a visit a grid step: sums of products of a row tile with
its group's whole matrix, which stays in VMEM while the group is walked
(its block index is the group's, and an unchanged index moves nothing),
in float32, then a few lines on the sums and on other row tiles, rounded
once to the compute dtype and stored under the group's row mask. It is
``up`` (two products of one row tile; the gating ``silu(gate) * up`` in
float32 on ``gate`` and ``up`` as the compute dtype holds them), ``down``,
the rows' gradient of ``down`` (``d_out W2[g]^T`` contracted over the
matrix' stored minor dimension, then the gating's derivative, which also
gives ``hid`` again for the weights' gradient) and the rows' gradient of
``up`` (``d_gate W1[g]^T + d_up W3[g]^T`` in one float32 sum).

``_weights_kernel``, for ``dW[e] = lhs[rows of e]^T rhs[rows of e]``: the
grid is (column blocks of the result, visits); a group's float32 sum
lives in VMEM from its first visit to its last, the rows of a neighbour
group in a shared tile masked to zero; an expert with no rows has one
visit that adds nothing and writes zeros.

What a call holds in VMEM is stated by ``rows_bytes`` and
``weights_bytes`` and held under ``_BUDGET_BYTES`` by ``tile_rows``, the
tiling rule: shapes it does not take stay ``lax.ragged_dot``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GAUGE = "moe::gmm_kernel_sites"

_F32 = jnp.float32
_LANES = 128
_TILES = (256, 128)
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# of them, what the blocks and sums a call names may take; the rest is the
# float32 products and their rounded copies before they are stored
_BUDGET_BYTES = 64 * 1024 * 1024
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


# ---------------------------------------------------------------------------
# the tiling rule
# ---------------------------------------------------------------------------
def rows_bytes(tile, hidden, ff, itemsize):
    """What the largest ``_rows_kernel`` call (``up``: two matrices, a row
    tile in, three out) names in VMEM: every block twice, for the
    pipeline."""
    return 2 * itemsize * (2 * hidden * ff + tile * (hidden + 3 * ff))


def weights_bytes(tile, hidden, ff, block, itemsize):
    """What the largest ``_weights_kernel`` call (``dW1`` and ``dW3``: a
    block of ``block`` of the hidden columns against all of ``ff``) names
    in VMEM: two float32 sums, both results' blocks twice, the row tiles
    twice."""
    return 2 * block * ff * (4 + 2 * itemsize) \
        + 2 * itemsize * tile * (block + 2 * ff)


def column_block(width):
    """The block of a weights' gradient's tiled dimension: the largest of
    1024, 512, 256, 128 that divides ``width``."""
    return next(b for b in (1024, 512, 256, 128) if width % b == 0)


def tile_rows(rows, hidden, ff, dtype):
    """The rows of a tile for a pool of ``rows`` rows ``hidden`` wide and
    experts ``ff`` wide, or nothing where the kernels do not take the
    shapes: both widths whole lane tiles, the pool whole tiles of 256 or
    128 rows (the larger that divides it), and what the calls hold in VMEM
    under the budget. Chosen on the chip at the Moonlight cell's shapes
    (PERF.md, PR 40): tiles of 512 are a tenth slower (a shared tile is
    computed once for each of its groups, so its rows are the work
    wasted), 256 and 128 time alike."""
    itemsize = jnp.dtype(dtype).itemsize
    if hidden % _LANES or ff % _LANES or itemsize not in (2, 4):
        return None
    tile = next((t for t in _TILES if rows % t == 0), None)
    if tile is None:
        return None
    held = max(rows_bytes(tile, hidden, ff, itemsize),
               weights_bytes(tile, hidden, ff, column_block(hidden),
                             itemsize))
    return tile if held <= _BUDGET_BYTES else None


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------
def visits(sizes, rows, tile):
    """The walk of a pool of ``rows`` rows in tiles of ``tile``, group by
    group, from ``sizes`` (E,) int32, which sum to ``rows``: ``(group (V,),
    tile (V,), offsets (E + 1,), count (1,))``, all int32, ``V = rows /
    tile + E - 1``. Visit ``v < count`` is group ``group[v]`` at tile
    ``tile[v]``; a group visits every tile that holds a row of its own, in
    order, and a group with no rows the tile its offset lies in, once. The
    visits from ``count`` on repeat the last one. Group ``g`` has the rows
    ``offsets[g]`` up to ``offsets[g + 1]``."""
    sizes = sizes.astype(jnp.int32)
    tiles = rows // tile
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tile, first)
    count = last - first + 1                 # an empty group's one visit
    upto = jnp.cumsum(count)
    v = jnp.minimum(jnp.arange(tiles + sizes.shape[0] - 1, dtype=jnp.int32),
                    upto[-1] - 1)
    group = jnp.sum(v[:, None] >= upto[None, :], axis=1, dtype=jnp.int32)
    at = first[group] + v - (upto - count)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, at.astype(jnp.int32), offsets, upto[-1:]


def _own_rows(walk, v, tile, width):
    """(tile, width) bool: the rows of visit ``v``'s tile that are its
    group's."""
    group_ref, tile_ref, offset_ref, _ = walk
    g = group_ref[v]
    row = tile_ref[v] * tile + lax.broadcasted_iota(jnp.int32, (tile, width),
                                                    0)
    return (row >= offset_ref[g]) & (row < offset_ref[g + 1])


# ---------------------------------------------------------------------------
# a row tile against its group's matrices
# ---------------------------------------------------------------------------
def _rows_kernel(*refs, terms, dims, finish, n_in, tile):
    walk, ins, outs = refs[:4], refs[4:4 + n_in], refs[4 + n_in:]
    v = pl.program_id(0)

    @pl.when(v < walk[3][0])
    def _():
        sums = []
        for term in terms:
            total = None
            for a, b in term:
                p = lax.dot_general(ins[a][...], ins[b][...], dims,
                                    preferred_element_type=_F32)
                total = p if total is None else total + p
            sums.append(total)
        own = {}
        for ref, val in zip(outs, finish(sums, ins, outs[0].dtype)):
            width = ref.shape[-1]
            if width not in own:
                own[width] = _own_rows(walk, v, tile, width)
            ref[...] = jnp.where(own[width], val.astype(ref.dtype), ref[...])


def _sums(sums, ins, dtype):
    """The sums themselves: the store rounds them."""
    return sums


def _gate(gate, up):
    """``silu(gate) * up`` and silu's value and slope, all float32."""
    s = jax.nn.sigmoid(gate)
    return gate * s * up, gate * s, s * (1.0 + gate * (1.0 - s))


def _gated(sums, ins, dtype):
    """``(gate, up, hid)``: the gating in float32 on both products as the
    compute dtype holds them."""
    gate, up = (t.astype(dtype) for t in sums)
    return gate, up, _gate(gate.astype(_F32), up.astype(_F32))[0]


def _gating_backward(sums, ins, dtype):
    """``(d_gate, d_up, hid)`` from ``d_hid`` as the compute dtype holds it
    and the kept ``gate`` and ``up`` (the row tiles after ``d_out``)."""
    d_hid = sums[0].astype(dtype).astype(_F32)
    gate, up = (ref[...].astype(_F32) for ref in ins[1:3])
    hid, silu, slope = _gate(gate, up)
    return d_hid * up * slope, d_hid * silu, hid


def _tile_at(v, group, tile, offsets, count):
    return tile[v], 0


def _group_at(v, group, tile, offsets, count):
    return group[v], 0, 0


def _rows_call(name, walk, tiles, matrices, outs, terms, dims, finish, tile,
               interpret):
    """``_rows_kernel`` over the row arrays ``tiles`` (rows, width) and the
    groups' ``matrices`` (E, a, b); ``outs``: the widths of the results,
    which have ``tiles[0]``'s rows and dtype. The inputs are numbered
    ``tiles`` first for ``terms``, pairs of (row tile, matrix) whose
    products ``dims`` are summed."""
    rows, dtype = tiles[0].shape[0], tiles[0].dtype
    e = matrices[0].shape[0]
    return pl.pallas_call(
        functools.partial(_rows_kernel, terms=terms, dims=dims, finish=finish,
                          n_in=len(tiles) + len(matrices), tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(rows // tile + e - 1,),
            in_specs=[pl.BlockSpec((tile, t.shape[1]), _tile_at)
                      for t in tiles]
            + [pl.BlockSpec((None,) + m.shape[1:], _group_at)
               for m in matrices],
            out_specs=[pl.BlockSpec((tile, w), _tile_at) for w in outs]),
        out_shape=[jax.ShapeDtypeStruct((rows, w), dtype) for w in outs],
        name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))(*walk, *tiles, *matrices)


# ---------------------------------------------------------------------------
# a group's rows against a group's rows
# ---------------------------------------------------------------------------
def _weights_kernel(*refs, n, tile):
    walk, lhs_ref = refs[:4], refs[4]
    rhs, outs, sums = (refs[5 + i * n:5 + (i + 1) * n] for i in range(3))
    group_ref, _, offset_ref, count_ref = walk
    v, last_v = pl.program_id(2), pl.num_programs(2) - 1
    g, count = group_ref[v], count_ref[0]
    live = v < count

    @pl.when(live & ((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g)))
    def _():
        for acc in sums:
            acc[...] = jnp.zeros_like(acc)

    @pl.when(live & (offset_ref[g + 1] > offset_ref[g]))
    def _():
        lhs = lhs_ref[...]
        lhs = jnp.where(_own_rows(walk, v, tile, lhs.shape[-1]), lhs,
                        jnp.zeros_like(lhs))
        for r, acc in zip(rhs, sums):
            acc[...] += lax.dot_general(lhs, r[...], _TN,
                                        preferred_element_type=_F32)

    @pl.when(live & ((v == count - 1)
                     | (group_ref[jnp.minimum(v + 1, last_v)] != g)))
    def _():
        for out, acc in zip(outs, sums):
            out[...] = acc[...].astype(out.dtype)


def _weights_call(name, walk, lhs, rhs, experts, tile, interpret,
                  block_lhs=None, block_rhs=None):
    """``[lhs[rows of e]^T r[rows of e] for r in rhs]``, each (E, lhs
    width, rhs width) in ``lhs``'s dtype; ``block_lhs`` / ``block_rhs``:
    the block of the width that is tiled, the other is whole."""
    rows, a = lhs.shape
    b = rhs[0].shape[1]
    ta, tb = block_lhs or a, block_rhs or b
    n = len(rhs)
    return pl.pallas_call(
        functools.partial(_weights_kernel, n=n, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(a // ta, b // tb,
                                         rows // tile + experts - 1),
            in_specs=[pl.BlockSpec((tile, ta),
                                   lambda i, j, v, g, t, *_: (t[v], i))]
            + [pl.BlockSpec((tile, tb),
                            lambda i, j, v, g, t, *_: (t[v], j))] * n,
            out_specs=[pl.BlockSpec((None, ta, tb),
                                    lambda i, j, v, g, *_: (g[v], i, j))] * n,
            scratch_shapes=[pltpu.VMEM((ta, tb), _F32)] * n),
        out_shape=[jax.ShapeDtypeStruct((experts, a, b), lhs.dtype)] * n,
        name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))(*walk, lhs, *rhs)


# ---------------------------------------------------------------------------
# the six calls
# ---------------------------------------------------------------------------
def up(buf, w1, w3, walk, tile, interpret=False):
    """``(gate, up, hid)``, each (rows, ff) in ``buf``'s dtype: ``buf
    W1[g]``, ``buf W3[g]`` and ``silu(gate) * up``."""
    ff = w1.shape[2]
    return _rows_call("moe_gmm_up_kernel", walk, [buf], [w1, w3],
                      [ff, ff, ff], (((0, 1),), ((0, 2),)), _NN, _gated, tile,
                      interpret)


def down(hid, w2, walk, tile, interpret=False):
    """``hid W2[g]``, (rows, hidden)."""
    return _rows_call("moe_gmm_down_kernel", walk, [hid], [w2],
                      [w2.shape[2]], (((0, 1),),), _NN, _sums, tile,
                      interpret)[0]


def down_backward(d_out, gate, up, w2, walk, tile, interpret=False):
    """``(d_gate, d_up, dW2)`` from the output's cotangent and the kept
    ``gate`` and ``up``: ``d_hid = d_out W2[g]^T`` through the gating's
    derivative, and ``hid^T d_out`` over each expert's rows."""
    ff = w2.shape[1]
    d_gate, d_up, hid = _rows_call(
        "moe_gmm_down_rows_kernel", walk, [d_out, gate, up], [w2],
        [ff, ff, ff], (((0, 3),),), _NT, _gating_backward, tile, interpret)
    dw2, = _weights_call(
        "moe_gmm_down_weights_kernel", walk, hid, [d_out], w2.shape[0], tile,
        interpret, block_rhs=column_block(d_out.shape[1]))
    return d_gate, d_up, dw2


def up_backward(buf, d_gate, d_up, w1, w3, walk, tile, interpret=False):
    """``(d_buf, dW1, dW3)``: ``d_gate W1[g]^T + d_up W3[g]^T`` in one
    float32 sum, and ``buf^T d_gate``, ``buf^T d_up`` over each expert's
    rows."""
    d_buf, = _rows_call(
        "moe_gmm_up_rows_kernel", walk, [d_gate, d_up], [w1, w3],
        [w1.shape[1]], (((0, 2), (1, 3)),), _NT, _sums, tile, interpret)
    dw1, dw3 = _weights_call(
        "moe_gmm_up_weights_kernel", walk, buf, [d_gate, d_up], w1.shape[0],
        tile, interpret, block_lhs=column_block(buf.shape[1]))
    return d_buf, dw1, dw3
