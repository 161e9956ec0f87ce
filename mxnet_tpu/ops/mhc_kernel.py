"""The passes over a token's residual streams as kernels (Pallas on
Mosaic), for ``ops.seq.mhc_read`` and ``ops.seq.mhc_post`` where their
program is lowered for a TPU and the streams are whole lane tiles: a
hyper-connected sublayer (manifold-constrained hyper-connections,
arXiv:2512.24880) reads its ``n`` streams ``X`` (tokens, n * C) once a
kernel, four kernels a sublayer, one ``custom_vjp`` a side.

``read`` (forward, read side): a block of ``X`` -> the product ``raw = phi
X^T`` (rows, block) float32 on the MXU, the mean square of a token's
streams, and ``u = sum_j H_pre[j] X_j`` with ``H_pre = sigmoid(raw_pre
rsqrt(mean_sq + eps) alpha_pre + bias_pre)`` formed for the block's tokens.

``post`` (forward, write side): ``X'_i = sum_j H_res[i, j] X_j + H_post[i]
y``, the sums in float32, rounded once.

``post_backward``: from ``dX'``, ``X``, ``y`` and both maps the streams'
partial cotangent ``dXp_j = sum_i H_res[i, j] dX'_i``, ``dy = sum_i
H_post[i] dX'_i`` and, a token, ``dH_res[i, j] = dX'_i . X_j`` and
``dH_post[i] = dX'_i . y`` in float32.

``read_backward``: from ``X``, ``dXp``, ``du`` and the cotangents of
``raw`` and ``mean_sq`` that XLA's backward of the per-token arithmetic
gave, ONE ``dX = dXp + H_pre[j] du + phi^T d(raw) + (2 / (n C)) d(mean_sq)
X``, summed in float32 and rounded once, ``d phi`` summed over the token
blocks in float32, and the cotangent of ``H_pre``'s logits a token (what
``alpha_pre`` and ``bias_pre`` take their gradients from). ``H_pre``'s way
back to ``raw`` and ``mean_sq`` is walked in the kernel.

The per-token arithmetic between them (scaling, sigmoids, the clamped
exponential, the Sinkhorn iterations) is not here: it stays in XLA on
(rows, tokens) arrays, tokens minor. The kernels read and write such
arrays as (rows, 128) tiles and turn a tile in VMEM (one 128 x 128
transposition a tile) to have a token's numbers beside its rows.

A grid step is a block of 128 tokens, whole in VMEM; inside it a loop
walks groups of 16 tokens (a bfloat16 sublane tile) and, inside that, the
slabs of a stream (``_over_slabs``: up to eight lane tiles a trip, one
after another), so that a group's float32 values are a few registers and
not a block-wide array in VMEM. The write side's maps, twenty a token at
four streams, are laid along the lanes once a block (``_along_lanes``) and
loaded where a product wants them. The products' operands are rounded
where the plain form rounds them: ``phi`` comes in the streams' dtype, and
a float32 cotangent that meets the streams or ``phi`` in a product is
rounded as XLA's default precision rounds it on a TPU.

What a call holds in VMEM is stated by ``held_bytes`` and held under
``_BUDGET_BYTES`` by ``takes``, the rule of shapes: shapes it does not
take stay the plain form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GAUGE = "mhc::kernel_sites"

_F32 = jnp.float32
_LANES = 128
#: the tokens a grid step works: a lane tile of the (rows, tokens) arrays
_BLOCK = 128
#: the tokens a pass of the inner loop works: a bfloat16 sublane tile
_GROUP = 16
#: the most lane tiles of a stream a trip of the innermost loop works
_TOGETHER = 8
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# of them, what the blocks and the scratch a call names may take; the rest
# is a group's values the compiler does not hold in registers
_BUDGET_BYTES = 48 * 1024 * 1024
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


# ---------------------------------------------------------------------------
# the rule of shapes
# ---------------------------------------------------------------------------
def _up(rows, to):
    return -(-rows // to) * to


def map_rows(n):
    """``(product's, write side's)`` rows of the (rows, tokens) arrays the
    kernels window: ``n (n + 2)`` to whole bfloat16 sublane tiles, ``n n +
    n`` to whole float32 ones."""
    return _up(n * (n + 2), 16), _up(n * n + n, 8)


def held_bytes(n, width, itemsize):
    """What the largest call names in VMEM at streams ``width`` = n C
    wide: ``read_backward``'s blocks twice, for the pipeline (the streams,
    their partial cotangent and the result, ``du``, ``phi`` in and ``d
    phi`` out), and its scratch (``phi^T d(raw)`` a block, float32)."""
    rows = map_rows(n)[0]
    blocks = _BLOCK * (3 * width + width // n) * itemsize \
        + rows * width * (itemsize + 4)
    return 2 * blocks + _BLOCK * width * 4


def takes(tokens, n, width, dtype):
    """Whether the kernels take ``tokens`` tokens of ``n`` streams, ``width``
    = n C wide together, in ``dtype``: 2 to 8 streams, each whole lane
    tiles of 128, the tokens whole blocks of 128, bfloat16 or float32, and
    what a call holds in VMEM under the budget. Shapes alone."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    if not 2 <= n <= 8 or width <= 0 or width % (n * _LANES):
        return False
    if tokens <= 0 or tokens % _BLOCK:
        return False
    return held_bytes(n, width, jnp.dtype(dtype).itemsize) <= _BUDGET_BYTES


# ---------------------------------------------------------------------------
# a block's pieces
# ---------------------------------------------------------------------------
def _columns(tile):
    """A (rows, 128) float32 tile of a (rows, tokens) array as (128, 128):
    column ``k`` holds row ``k``, a token a row."""
    rows = tile.shape[0]
    if rows < _BLOCK:
        tile = jnp.concatenate(
            [tile, jnp.zeros((_BLOCK - rows, _BLOCK), _F32)], axis=0)
    return tile.T


def _group(g):
    return pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)


def _over_slabs(rows, wide, body, carry=None):
    """``carry = body(window, tiles, carry)`` over the slabs of a stream
    ``wide`` lanes wide, for the tokens ``rows``: ``window(ref, start)``
    is the view of ``ref``'s slab of the stream that begins at column
    ``start`` (one address a slab: the scalar slots fill before the
    vector slots where every lane tile computes its own), and ``tiles``
    the lane tiles of a slab as slices of such a view, which the body
    works one after another. A loop and not all of a stream's tiles
    written out: that was 6 % fewer bundles in the compiled schedule and
    three times the operations to trace and lower at every start of a
    process."""
    lanes = slab_lanes(wide)
    tiles = [slice(k, k + _LANES) for k in range(0, lanes, _LANES)]

    def step(s, carry):
        def window(ref, start=0):
            return ref.at[rows, pl.ds(
                pl.multiple_of(start + s * lanes, _LANES), lanes)]

        return body(window, tiles, carry)

    return lax.fori_loop(0, wide // lanes, step, carry)


def slab_lanes(wide):
    """The lanes of a slab: the most lane tiles, at most ``_TOGETHER``,
    that divide a stream ``wide`` lanes wide."""
    tiles = wide // _LANES
    return _LANES * max(d for d in range(1, _TOGETHER + 1) if tiles % d == 0)


def _wide(col):
    """A (16, 1) column of floats along the lanes."""
    return jnp.broadcast_to(col, (_GROUP, _LANES))


def _packed(cols):
    """(16, 128): column ``k`` is ``cols[k]`` (16, 1) where that is given,
    zero elsewhere."""
    lane = lax.broadcasted_iota(jnp.int32, (_GROUP, _LANES), 1)
    out = jnp.zeros((_GROUP, _LANES), _F32)
    for k, col in cols.items():
        out = jnp.where(lane == k, col, out)
    return out


def _sum(terms):
    return functools.reduce(lambda a, b: a + b, terms)


def _pre_map(col, r, ab_ref, n):
    """``H_pre`` of a group's tokens, ``n`` (16, 1) columns: ``col`` holds
    ``raw``'s first rows as columns, ``r`` the root mean square's
    reciprocal, ``ab_ref`` ``alpha_pre`` and ``bias_pre``: the expression
    ``ops.seq.mhc_maps`` evaluates for its own output."""
    return [jax.nn.sigmoid(col[:, j:j + 1] * r * ab_ref[0] + ab_ref[1 + j])
            for j in range(n)]


# ---------------------------------------------------------------------------
# forward, read side
# ---------------------------------------------------------------------------
def _read_kernel(ab_ref, x_ref, phi_ref, raw_ref, ms_ref, u_ref, cols_ref,
                 stat_ref, *, n, c, eps):
    width = n * c
    raw = lax.dot_general(phi_ref[...], x_ref[...], _NT,
                          preferred_element_type=_F32)
    raw_ref[...] = raw
    cols_ref[...] = _columns(raw)

    def group(g, carry):
        rows = _group(g)

        def squares(window, tiles, parts):
            # four sums side by side: one would be a chain of n C / 128 adds
            x = window(x_ref)
            for tile in tiles:
                v = x[:, tile].astype(_F32)
                parts = parts[1:] + (parts[0] + v * v,)
            return parts

        parts = _over_slabs(rows, width, squares,
                            (jnp.zeros((_GROUP, _LANES), _F32),) * 4)
        ms = jnp.sum(_sum(parts), axis=1, keepdims=True) / width
        stat_ref[rows, :] = _wide(ms)
        pre = [_wide(p) for p in _pre_map(
            cols_ref[rows, :], lax.rsqrt(ms + eps), ab_ref, n)]

        def mix(window, tiles, carry):
            xs, u = [window(x_ref, j * c) for j in range(n)], window(u_ref)
            for tile in tiles:
                u[:, tile] = _sum(p * x[:, tile].astype(_F32)
                                  for p, x in zip(pre, xs)).astype(u.dtype)

        _over_slabs(rows, c, mix)
        return carry

    lax.fori_loop(0, _BLOCK // _GROUP, group, 0)
    ms_ref[...] = stat_ref[...].T[:1]


def _call(kernel, name, tokens, interpret, **specs):
    return pl.pallas_call(
        kernel, grid=(tokens // _BLOCK,), name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES), **specs)


def _rows_spec(wide):
    """A block of tokens of a (tokens, wide) array."""
    return pl.BlockSpec((_BLOCK, wide), lambda i: (i, 0))


def _tile_spec(rows):
    """A block of tokens of a (rows, tokens) array."""
    return pl.BlockSpec((rows, _BLOCK), lambda i: (0, i))


def _whole_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


_SCALARS = pl.BlockSpec(memory_space=pltpu.SMEM)
_SCRATCH = pltpu.VMEM((_BLOCK, _LANES), _F32)


def _padded_rows(a, rows):
    return a if a.shape[0] == rows else jnp.pad(
        a, ((0, rows - a.shape[0]), (0, 0)))


def _scalars(alpha_pre, bias_pre):
    return jnp.concatenate([alpha_pre.astype(_F32).reshape(1),
                            bias_pre.astype(_F32).reshape(-1)])


@functools.partial(jax.jit, static_argnames=("n", "eps", "interpret"))
def read(x, phi, alpha_pre, bias_pre, n, eps, interpret=False):
    """``(raw, mean_sq, u)`` from the streams ``x`` (tokens, n C):
    ``raw = phi x^T`` (n (n + 2), tokens) float32 with ``phi`` (n (n + 2),
    n C) in ``x``'s dtype, ``mean_sq`` (tokens,) float32, and ``u = sum_j
    H_pre[j] x_j`` (tokens, C) in ``x``'s dtype, ``H_pre`` from ``raw``'s
    first ``n`` rows, ``mean_sq``, the scalar ``alpha_pre`` and
    ``bias_pre`` (n,). (Jitted, as the three other calls are: a step's
    like sublayers and both passes of a recomputation unit then share one
    trace of the kernel.)"""
    tokens, width = x.shape
    rows = map_rows(n)[0]
    raw, ms, u = _call(
        functools.partial(_read_kernel, n=n, c=width // n, eps=eps),
        "mhc_read_kernel", tokens, interpret,
        in_specs=[_SCALARS, _rows_spec(width), _whole_spec((rows, width))],
        out_specs=[_tile_spec(rows), _tile_spec(1), _rows_spec(width // n)],
        out_shape=[jax.ShapeDtypeStruct((rows, tokens), _F32),
                   jax.ShapeDtypeStruct((1, tokens), _F32),
                   jax.ShapeDtypeStruct((tokens, width // n), x.dtype)],
        scratch_shapes=[_SCRATCH, _SCRATCH])(
            _scalars(alpha_pre, bias_pre), x, _padded_rows(phi, rows))
    return raw[:n * (n + 2)], ms[0], u


# ---------------------------------------------------------------------------
# forward, write side
# ---------------------------------------------------------------------------
def _along_lanes(h_ref, wide_ref, maps):
    """Every map of a block's tokens along the lanes: ``wide_ref[k]`` (128,
    128) holds row ``k`` of the tile ``h_ref`` (rows, 128), a token a row,
    the same number in all its lanes. Made once a block and loaded where a
    product wants it: twenty maps of a group held in registers across the
    lane tiles were forty registers of sixty-four, and four stores in
    five of the compiled schedule were spills."""
    cols = _columns(h_ref[...])
    for k in range(maps):
        wide_ref[k] = jnp.broadcast_to(cols[:, k:k + 1], (_BLOCK, _LANES))


def _post_kernel(x_ref, y_ref, h_ref, o_ref, wide_ref, *, n, c):
    _along_lanes(h_ref, wide_ref, n * n + n)

    def group(g, carry):
        rows = _group(g)

        def sums(window, tiles, carry):
            xs = [window(x_ref, j * c) for j in range(n)]
            outs = [window(o_ref, i * c) for i in range(n)]
            y = window(y_ref)
            for tile in tiles:
                x32 = [x[:, tile].astype(_F32) for x in xs]
                y32 = y[:, tile].astype(_F32)
                for i, out in enumerate(outs):
                    out[:, tile] = (
                        _sum(wide_ref[i * n + j, rows, :] * x32[j]
                             for j in range(n))
                        + wide_ref[n * n + i, rows, :] * y32
                    ).astype(out.dtype)

        _over_slabs(rows, c, sums)
        return carry

    lax.fori_loop(0, _BLOCK // _GROUP, group, 0)


def _wide_scratch(n):
    return pltpu.VMEM((n * n + n, _BLOCK, _LANES), _F32)


def _maps_rows(res, post, n):
    """``H_res`` (n n, tokens) over ``H_post`` (n, tokens), to whole
    sublane tiles."""
    return _padded_rows(jnp.concatenate([res, post], axis=0), map_rows(n)[1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def post(x, y, res, post, interpret=False):
    """``X'_i = sum_j res[i n + j] x_j + post[i] y``: ``x`` (tokens, n C),
    ``y`` (tokens, C), ``res`` (n n, tokens) and ``post`` (n, tokens)
    float32 -> (tokens, n C) in ``x``'s dtype."""
    tokens, width = x.shape
    n = post.shape[0]
    rows = map_rows(n)[1]
    return _call(
        functools.partial(_post_kernel, n=n, c=width // n),
        "mhc_post_kernel", tokens, interpret,
        in_specs=[_rows_spec(width), _rows_spec(width // n),
                  _tile_spec(rows)],
        out_specs=_rows_spec(width),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[_wide_scratch(n)])(x, y, _maps_rows(res, post, n))


# ---------------------------------------------------------------------------
# backward, write side
# ---------------------------------------------------------------------------
def _post_bwd_kernel(g_ref, x_ref, y_ref, h_ref, dxp_ref, dy_ref, dh_ref,
                     wide_ref, stat_ref, *, n, c):
    maps = n * n + n
    _along_lanes(h_ref, wide_ref, maps)

    def group(g, carry):
        rows = _group(g)

        # the mixes first, then the products a token: the second pass
        # holds its twenty sums a group in registers
        def mixes(window, tiles, carry):
            gs = [window(g_ref, i * c) for i in range(n)]
            dxps = [window(dxp_ref, j * c) for j in range(n)]
            dy = window(dy_ref)
            for tile in tiles:
                g32 = [g[:, tile].astype(_F32) for g in gs]
                for j, dxp in enumerate(dxps):
                    dxp[:, tile] = _sum(
                        wide_ref[i * n + j, rows, :] * g32[i]
                        for i in range(n)).astype(dxp.dtype)
                dy[:, tile] = _sum(
                    wide_ref[n * n + i, rows, :] * g32[i]
                    for i in range(n)).astype(dy.dtype)

        def products(window, tiles, dots):
            gs = [window(g_ref, i * c) for i in range(n)]
            xs = [window(x_ref, j * c) for j in range(n)] + [window(y_ref)]
            dots = list(dots)
            for tile in tiles:
                g32 = [g[:, tile].astype(_F32) for g in gs]
                x32 = [x[:, tile].astype(_F32) for x in xs]
                for i in range(n):
                    for j in range(n + 1):
                        k = i * n + j if j < n else n * n + i
                        dots[k] = dots[k] + g32[i] * x32[j]
            return tuple(dots)

        _over_slabs(rows, c, mixes)
        dots = _over_slabs(rows, c, products,
                           (jnp.zeros((_GROUP, _LANES), _F32),) * maps)
        stat_ref[rows, :] = _packed({
            k: jnp.sum(d, axis=1, keepdims=True) for k, d in enumerate(dots)})
        return carry

    lax.fori_loop(0, _BLOCK // _GROUP, group, 0)
    dh_ref[...] = stat_ref[...].T[:dh_ref.shape[0]]


@functools.partial(jax.jit, static_argnames=("interpret",))
def post_backward(g, x, y, res, post, interpret=False):
    """``post``'s cotangents from its output's, ``g`` (tokens, n C):
    ``(dxp (tokens, n C), dy (tokens, C), d_res (n n, tokens), d_post (n,
    tokens))``, the first two in ``x``'s dtype, the maps' in float32."""
    tokens, width = x.shape
    n = post.shape[0]
    rows = map_rows(n)[1]
    dxp, dy, dh = _call(
        functools.partial(_post_bwd_kernel, n=n, c=width // n),
        "mhc_post_bwd_kernel", tokens, interpret,
        in_specs=[_rows_spec(width), _rows_spec(width),
                  _rows_spec(width // n), _tile_spec(rows)],
        out_specs=[_rows_spec(width), _rows_spec(width // n),
                   _tile_spec(rows)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((rows, tokens), _F32)],
        scratch_shapes=[_wide_scratch(n), _SCRATCH],
        # the cotangent's blocks are read before the partial cotangent's
        # are written over them: one stream-wide buffer less at the peak
        input_output_aliases={0: 0})(g, x, y, _maps_rows(res, post, n))
    return dxp, dy, dh[:n * n], dh[n * n:n * n + n]


# ---------------------------------------------------------------------------
# backward, read side
# ---------------------------------------------------------------------------
def _read_bwd_kernel(ab_ref, x_ref, dxp_ref, du_ref, t_ref, draw_ref, phi_ref,
                     dx_ref, dphi_ref, dz_ref, cols_ref, stat_ref, mix_ref,
                     back_ref, *, n, c, eps):
    width = n * c
    rp = draw_ref.shape[0]
    # t_ref's rows: raw's first n, mean_sq, mean_sq's cotangent
    cols_ref[...] = _columns(t_ref[...])

    def products(g, carry):
        rows = _group(g)
        col = cols_ref[rows, :]

        def dots_of(window, tiles, dots):
            xs, du = [window(x_ref, j * c) for j in range(n)], window(du_ref)
            for tile in tiles:
                du32 = du[:, tile].astype(_F32)
                dots = tuple(d + du32 * x[:, tile].astype(_F32)
                             for d, x in zip(dots, xs))
            return dots

        dots = _over_slabs(rows, c, dots_of,
                           (jnp.zeros((_GROUP, _LANES), _F32),) * n)
        r = lax.rsqrt(col[:, n:n + 1] + eps)
        pre = _pre_map(col, r, ab_ref, n)
        # d sigmoid, then the logit's two ways back: raw and the mean square
        dz = [jnp.sum(d, axis=1, keepdims=True) * s * (1.0 - s)
              for d, s in zip(dots, pre)]
        d_r = _sum(z * col[:, j:j + 1] for j, z in enumerate(dz)) * ab_ref[0]
        d_ms = col[:, n + 1:n + 2] - 0.5 * d_r * (r * r * r)
        stat_ref[rows, :] = _packed({
            **{j: z * r * ab_ref[0] for j, z in enumerate(dz)},
            **{rp + j: z for j, z in enumerate(dz)}})
        mix_ref[rows, :] = _packed({
            **dict(enumerate(pre)), n: d_ms * (2.0 / width)})
        return carry

    lax.fori_loop(0, _BLOCK // _GROUP, products, 0)
    turned = stat_ref[...].T
    dz_ref[...] = turned[rp:rp + dz_ref.shape[0]]
    d_raw = (draw_ref[...] + turned[:rp]).astype(x_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    dphi_ref[...] += lax.dot_general(d_raw, x_ref[...], _NN,
                                     preferred_element_type=_F32)
    back_ref[...] = lax.dot_general(d_raw, phi_ref[...], _TN,
                                    preferred_element_type=_F32)

    def sums(g, carry):
        rows = _group(g)
        col = mix_ref[rows, :]
        pre = [_wide(col[:, j:j + 1]) for j in range(n)]
        scale = _wide(col[:, n:n + 1])

        def one(window, tiles, carry):
            du = window(du_ref)
            streams = [[window(ref, j * c)
                        for ref in (dxp_ref, back_ref, x_ref, dx_ref)]
                       for j in range(n)]
            for tile in tiles:
                du32 = du[:, tile].astype(_F32)
                for p, (dxp, back, x, dx) in zip(pre, streams):
                    dx[:, tile] = (
                        dxp[:, tile].astype(_F32) + p * du32 + back[:, tile]
                        + scale * x[:, tile].astype(_F32)).astype(dx.dtype)

        _over_slabs(rows, c, one)
        return carry

    lax.fori_loop(0, _BLOCK // _GROUP, sums, 0)


@functools.partial(jax.jit, static_argnames=("n", "eps", "interpret"))
def read_backward(x, dxp, du, raw, mean_sq, d_raw, d_mean_sq, phi, alpha_pre,
                  bias_pre, n, eps, interpret=False):
    """``read``'s cotangents, with the streams' partial cotangent from the
    write side, ``dxp``, added in: ``(dx (tokens, n C) in x's dtype, d_phi
    (n (n + 2), n C) float32, d_logits (n, tokens) float32)``. ``du``
    (tokens, C), ``d_raw`` (n (n + 2), tokens) and ``d_mean_sq``
    (tokens,) are the cotangents of ``read``'s outputs; ``d_logits`` is
    that of ``H_pre``'s logits ``raw_pre rsqrt(mean_sq + eps) alpha_pre +
    bias_pre``, from which the caller sums ``alpha_pre``'s and
    ``bias_pre``'s gradients."""
    tokens, width = x.shape
    rp = map_rows(n)[0]
    small = _padded_rows(jnp.concatenate(
        [raw[:n], mean_sq[None], d_mean_sq.astype(_F32)[None]], axis=0),
        _up(n + 2, 8))
    dx, dphi, dz = _call(
        functools.partial(_read_bwd_kernel, n=n, c=width // n, eps=eps),
        "mhc_read_bwd_kernel", tokens, interpret,
        in_specs=[_SCALARS, _rows_spec(width), _rows_spec(width),
                  _rows_spec(width // n), _tile_spec(small.shape[0]),
                  _tile_spec(rp), _whole_spec((rp, width))],
        out_specs=[_rows_spec(width), _whole_spec((rp, width)),
                   _tile_spec(8)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((rp, width), _F32),
                   jax.ShapeDtypeStruct((8, tokens), _F32)],
        scratch_shapes=[_SCRATCH, _SCRATCH, _SCRATCH,
                        pltpu.VMEM((_BLOCK, width), _F32)],
        input_output_aliases={2: 0})(     # dx over dxp, block by block
            _scalars(alpha_pre, bias_pre), x, dxp, du, small,
            _padded_rows(d_raw.astype(_F32), rp), _padded_rows(phi, rp))
    return dx, dphi[:n * (n + 2)], dz[:n]
