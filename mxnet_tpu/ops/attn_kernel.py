"""Causal grouped-query attention as fused kernels (Pallas on Mosaic):
one forward kernel and one backward kernel, for ``ops.seq
.causal_gq_attention`` where its program is lowered for a TPU and the
heads are whole lane tiles.

The arrays stay as the projection wrote them: ``q`` is (B, L, Hq * D),
``k`` and ``v`` (B, L, Hk * D), and a head is the (block, D) window at
column ``h`` of a block of rows, so nothing is transposed to (B, H, L, D)
on the way in or out. Query head ``h`` reads key/value head ``h // (Hq //
Hk)`` through the block index map; the keys' and values' gradients of a
shared head are summed over its query heads in the kernel's grid.

The arithmetic is the blocked recurrence's (``ops.seq._attention_block``):
``q k^T`` and every sum in float32, the scale applied to the float32
scores, ``-1e30`` for a masked score, the probabilities cast to ``v``'s
dtype for the weighted sum, one division by the denominator at the end.
The forward keeps a block's float32 scores, the running maximum, the
running sum and the float32 accumulator in VMEM and writes the output and
one float32 log-sum-exp a row. Blocks past the diagonal are never
computed, nor fetched (their index is clamped to the last block that is,
and an unchanged index moves nothing). A length that is no multiple of
the block is padded with zero rows: a padded key lies past every true
query's diagonal, and a padded query's gradient is zero.

The backward forms each score block once. Its grid is (batch, key/value
head, key block ``j``, the group's query heads times the query blocks
``i``): for a pair ``i >= j`` it forms ``S^T`` (keys along the sublanes,
so a query's log-sum-exp and ``delta`` are rows as they lie), ``P^T =
exp(S^T - lse)`` again from q, k and that log-sum-exp, ``dP^T = V dO^T``
and ``dS^T = P^T (dP^T - delta)``, rounds ``dS^T`` to the compute dtype
once, and takes all five products from them: ``dV += P^T dO`` and ``dK +=
dS^T Q`` into one block's float32 sums, which leave when the key block's
last query block is through, and ``dQ_i += dS K_j`` (``dS^T`` turned: a
product over its first dimension) into float32 sums over all the rows of
the group's heads, held in VMEM while the key/value head is worked. A
query block's sum is complete at its diagonal, where it is scaled, cast
once and written into the group's ``dQ`` block of all the rows, which
leaves when the key/value head changes: nothing of ``dQ`` goes through
memory in float32, and the order of addition is the key blocks'. ``delta
= sum(dO * O)`` is XLA's, outside the kernel.

A second part of the score (``extra``), for heads whose queries and keys
are wider than their values and share a part of the key (multi-head
latent attention: a rotary key of 64 beside 16 heads' own 128): ``q2``
(B, L, Hq * D2), a second query part a head, and ``k2`` (B, L, D2), one
key part that every head reads. ``q2 k2^T`` is added to the same float32
score block before the scale, the mask and the softmax, forward and
backward, and the same ``dS^T`` then also gives ``dk2 += dS^T q2`` and
``dq2_i += dS k2_j``: ``dq2`` is held and written like ``dQ``, and
``dk2``, which adds up over every head, is held over all the rows while
a sequence is worked, so with a second part the key/value heads run in
turn. ``k2`` is never repeated a head. D2 need be no lane tile: Mosaic
takes a block whose last dimension is the whole array's, so ``k2`` is
read as it lies and ``q2`` goes in head-major, (B, Hq, L, D2), one
transposition of a narrow array that XLA fuses into the rotation that
produced it; the gradient comes back the same way.

What the fused backward holds for all the rows (``resident_bytes``: a
group's ``dQ`` in float32 and its output block twice, 32 MiB for four
heads of 128 at 8192 rows) has to fit beside a grid step's blocks under
the kernels' VMEM limit. Where it does not (``_RESIDENT_LIMIT_BYTES``:
arithmetic on the length, the group, D and D2 when the program is
traced), the backward is two kernels that hold one block's sums whatever
the length: one over the queries' side for ``dQ`` (and ``dq2``), one
over the keys' side for ``dK``, ``dV`` (and ``dk2``), each forming the
score block for itself. The gauge ``attn::fused_bwd_sites`` counts the
sites that take the fused kernel, beside ``attn::kernel_sites``.

A window (``window=W``: query ``t`` sees keys ``t - W < j <= t``) gives a
query block a second edge, ``W`` keys behind the diagonal, and the kernels
a grid that walks the band alone: a query block meets ``band = 1 +
ceil((W - 1) / block)`` key blocks, its own last (``_band``), and a key
block as many query blocks, its own first; the grid's last dimension is
``band`` (times the heads where it walks the keys' side) and not the
number of blocks, so a block wholly before the window is neither formed
nor fetched, forward or backward. A band's step that falls before the
first block or after the last is clamped to it (an unchanged index moves
nothing) and computes nothing. A block pair that the window's edge
crosses (``(i - j + 1) block > W``) takes a second mask, ``t - j < W``,
beside the diagonal's; a row of such a block may be masked whole, which
the running maximum forgets at the diagonal, where every row sees
itself. ``dQ`` of a query block starts at the first block of its band and
is complete at its diagonal, ``dK`` and ``dV`` of a key block when the
last query block of its band is through. The block under a window is the
largest of 512, 256, 128 that divides the padded length
(``_WINDOW_BLOCKS``), chosen on the chip at ``W`` = 512, 36 heads on 4 and
8192 rows (PERF.md section 6, PR 50; forward + backward by side, ms): 512
gives two blocks a query block of which half is masked and read 1.37 +
4.42, 256 three of which a third is and read 2.41 + 5.99, 128 five of
which a fifth is and read 6.11 + 11.56, 1024 two of which three quarters
are and read 2.13 + 6.90: what a smaller block saves in masked pairs it
loses several times over in steps of too little work. A window that
reaches the whole length is no window. The fused backward holds ``dQ`` for all the
rows as it does without a window. The gauge ``attn::window_sites``
counts the sites whose kernels walk a band.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import ad, mlir

_F32 = jnp.float32
_NEG = -1e30
_LANES = 128
_BLOCKS = (1024, 512, 256, 128)
_WINDOW_BLOCKS = (512, 256, 128)    # under a window: the module's docstring
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# of them, what the fused backward may hold for all the rows, beside a
# grid step's blocks and about 24 MB of float32 (block, block) temporaries
_RESIDENT_LIMIT_BYTES = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _no_second_part(extra, window):
    """The second part's index maps walk all the blocks: a window with a
    second part is not written."""
    if extra is not None and window is not None:
        raise NotImplementedError("a window with a second score part")


def block_size(length, window=None):
    """The block of queries and of keys for a sequence of ``length``:
    the largest of 1024, 512, 256, 128 (under a ``window``, of
    ``_WINDOW_BLOCKS``) that divides the length padded to whole lane
    tiles. Returns ``(block, padded length)``."""
    padded = -(-int(length) // _LANES) * _LANES
    blocks = _BLOCKS if window is None else _WINDOW_BLOCKS
    return next(b for b in blocks if padded % b == 0), padded


def _band(window, blk):
    """How many key blocks a query block meets under ``window`` (and a
    key block query blocks): its own and those the window reaches over."""
    return 1 - (1 - int(window)) // blk


def _crossed(i, j, blk, window):
    """Whether the window's edge crosses query block ``i`` against key
    block ``j``: its last query is ``window`` or more past its first
    key."""
    return (i - j + 1) * blk > window


def _whole_blocks(blk, window):
    """Whether a band holds a block pair off the diagonal that the
    window's edge does not cross (none at ``window`` below two blocks)."""
    return 2 * blk <= window


def _on_diagonal(blk, window):
    """The window a diagonal block is masked by beside the causal order:
    none unless a block is longer than the window."""
    return window if window is not None and blk > window else None


def _band_step(i, step, blk, window):
    """Of a grid that brings key blocks to query block ``i``: the key
    block of ``step`` and whether it lies before the diagonal. Without a
    window the steps are the blocks; under one they are the band, which
    ends at ``i`` and may start before block 0."""
    if window is None:
        return step, step < i
    j = i - (_band(window, blk) - 1) + step
    return j, (j >= 0) & (j < i)


def _ahead(j, t, n, blk, window):
    """Of a grid that brings a head's query blocks to key block ``j``
    under a window, step ``t``: how far ahead of ``j`` its query block
    lies, whether there is such a block, and the block (the last where
    there is none)."""
    ahead = t % _band(window, blk)
    return ahead, j + ahead < n, jnp.minimum(j + ahead, n - 1)


def _lanes(x, width):
    """A (rows, 128) value whose lanes all hold the row's number, as
    (rows, ``width``)."""
    return x if width == _LANES else jnp.tile(x, (1, width // _LANES))


def _scores(rows, cols, i, j, blk, scale, diagonal, transposed=False,
            rows2=None, cols2=None, window=None):
    """The scaled float32 scores of query block ``i`` against key block
    ``j``, ``rows @ cols.T``, with ``rows2 @ cols2.T`` added where a
    second part is given: (queries, keys), or (keys, queries)
    ``transposed``; on the ``diagonal`` block masked by the causal
    order, and with ``window`` by the window's edge (a key ``window`` or
    more before its query)."""
    s = lax.dot_general(rows, cols, _NT, preferred_element_type=_F32)
    if rows2 is not None:
        s = s + lax.dot_general(rows2, cols2, _NT,
                                preferred_element_type=_F32)
    s = s * scale
    if not diagonal and window is None:
        return s
    query = i * blk + lax.broadcasted_iota(jnp.int32, s.shape,
                                           1 if transposed else 0)
    key = j * blk + lax.broadcasted_iota(jnp.int32, s.shape,
                                         0 if transposed else 1)
    if window is None:
        return jnp.where(query >= key, s, _NEG)
    seen = query - key < window
    return jnp.where((query >= key) & seen if diagonal else seen, s, _NEG)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, blk, window=None):
    q2_ref, k2_ref = refs[:-5] or (None, None)
    o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[-5:]
    i, j = pl.program_id(2), pl.program_id(3)
    width = acc_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(diagonal, window=None):
        v = v_ref[...]
        s = _scores(q_ref[...], k_ref[...], i, j, blk, scale, diagonal,
                    window=window, **_second(q2_ref, k2_ref))
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
        p = jnp.exp(s - _lanes(m_next, blk))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1)[:, None]
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * _lanes(alpha, width) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=_F32)

    j, before = _band_step(i, j, blk, window)
    _off_diagonal(block, before, i, j, blk, window)

    @pl.when(j == i)
    def _():
        block(True, _on_diagonal(blk, window))
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _lanes(l, width)).astype(o_ref.dtype)
        # a row's number sits in every lane: turned, one row of it is the
        # block's log-sum-exp along the lanes
        lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


def _off_diagonal(block, inside, i, j, blk, window):
    """``block(False)`` where ``inside`` holds, and under a ``window``
    ``block(False, window)`` where its edge crosses the block pair."""
    if window is None:
        pl.when(inside)(functools.partial(block, False))
        return
    crossed = _crossed(i, j, blk, window)
    if _whole_blocks(blk, window):
        pl.when(inside & ~crossed)(functools.partial(block, False))
    pl.when(inside & crossed)(functools.partial(block, False, window))


def _second(rows2_ref, cols2_ref):
    """The second part's blocks as ``_scores`` takes them; nothing where
    there is none."""
    return {} if rows2_ref is None else {"rows2": rows2_ref[...],
                                         "cols2": cols2_ref[...]}


def _head_spec(blk, dim, index):
    """A (block, D) window of one head of (B, L, H * D) rows."""
    return pl.BlockSpec((None, blk, dim), index)


def _row_spec(blk, index):
    """A block of one head's row numbers, (B, Hq, 1, L), along the lanes."""
    return pl.BlockSpec((None, None, 1, blk), index)


# grid (batch, query head, query block i, key block j): the queries' side
# at (i, h); the keys' side at the group's head, and past the diagonal at
# the diagonal's block again, which fetches nothing
def _q_at(b, h, i, j):
    return b, i, h


def _kv_at(group, b, h, i, j):
    return b, jnp.minimum(j, i), h // group


def _band_kv_at(group, band, b, h, i, j):
    """Step ``j`` of query block ``i``'s band; before the first block,
    the first block."""
    return b, jnp.maximum(i - (band - 1) + j, 0), h // group


def _row_at(b, h, i, j):
    return b, h, 0, i


def _call(kernel, name, grid, interpret,
          semantics=("parallel",) * 3 + ("arbitrary",), **specs):
    return pl.pallas_call(
        kernel, grid=grid, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_VMEM_LIMIT_BYTES), **specs)


def _padded(x, axis, length):
    """``x`` with zeros after its ``axis`` up to ``length``."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, length - x.shape[axis])
    return jnp.pad(x, pad) if pad[axis][1] else x


def _extra_specs(blk, d2, q2_at, k2_at):
    """Blocks of the second part: a head's (block, D2) of the head-major
    ``q2`` (B, Hq, L, D2), and (block, D2) of ``k2`` (B, L, D2)."""
    return [pl.BlockSpec((None, None, blk, d2), q2_at),
            pl.BlockSpec((None, blk, d2), k2_at)]


def _q2_at(b, h, i, j):
    return b, h, i, 0


def _k2_at(b, h, i, j):
    return b, jnp.minimum(j, i), 0


def _queries_side(n, blk, group, window):
    """Of a grid (batch, query head, query block, step) that brings the
    key blocks to a query block: its last dimension and the keys' and
    values' index map, over all ``n`` blocks up to the diagonal or, under
    a ``window``, over the band."""
    if window is None:
        return n, functools.partial(_kv_at, group)
    band = _band(window, blk)
    return band, functools.partial(_band_kv_at, group, band)


def _head_major(q2, hq, padded):
    """``q2`` (B, L, Hq * D2) as (B, Hq, padded L, D2)."""
    bsz, length, _ = q2.shape
    return _padded(q2.reshape(bsz, length, hq, -1).transpose(0, 2, 1, 3), 2,
                   padded)


def forward(q, k, v, num_heads, num_kv_heads, scale, interpret=False,
            extra=None, window=None):
    """``(out, lse)``: the attention's output (B, L, Hq * D) in ``q``'s
    dtype and every row's float32 log-sum-exp of its scaled, masked
    scores (B, Hq, L). ``extra``: ``(q2 (B, L, Hq * D2), k2 (B, L, D2))``,
    the scores' second part. ``window``: a query sees that many keys, its
    own the last (not with ``extra``)."""
    _no_second_part(extra, window)
    hq, group = int(num_heads), int(num_heads) // int(num_kv_heads)
    bsz, length, _ = q.shape
    dim = q.shape[-1] // hq
    blk, padded = block_size(length, window)
    q, k, v = (_padded(t, 1, padded) for t in (q, k, v))
    n = padded // blk
    steps, kv_at = _queries_side(n, blk, group, window)
    operands, specs = [q, k, v], [_head_spec(blk, dim, _q_at),
                                  _head_spec(blk, dim, kv_at),
                                  _head_spec(blk, dim, kv_at)]
    if extra is not None:
        q2, k2 = extra
        operands += [_head_major(q2, hq, padded), _padded(k2, 1, padded)]
        specs += _extra_specs(blk, k2.shape[-1], _q2_at, _k2_at)
    out, lse = _call(
        functools.partial(_fwd_kernel, scale=scale, blk=blk, window=window),
        _named("fwd_kernel", window), (bsz, hq, n, steps), interpret,
        in_specs=specs,
        out_specs=[_head_spec(blk, dim, _q_at), _row_spec(blk, _row_at)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bsz, hq, 1, padded), _F32)],
        scratch_shapes=[pltpu.VMEM((blk, _LANES), _F32),
                        pltpu.VMEM((blk, _LANES), _F32),
                        pltpu.VMEM((blk, dim), _F32)])(*operands)
    return out[:, :length], lse[:, :, 0, :length]


def _named(kernel, window):
    """``attn_<kernel>``, under a window ``attn_swa_<kernel>``."""
    return ("attn_" if window is None else "attn_swa_") + kernel


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _keys_side(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q2_ref,
               k2_ref, dk_acc, dv_acc, i, j, blk, scale, diagonal,
               window=None):
    """Key block ``j`` against query block ``i`` with the keys along the
    sublanes and the queries along the lanes, so that a query's
    log-sum-exp and delta are rows as they lie: ``P^T`` and ``dS^T``
    formed once, ``dV += P^T dO`` and ``dK += dS^T Q`` added to the
    block's sums. Returns ``dS^T`` rounded to the compute dtype."""
    q, do = q_ref[...], do_ref[...]
    st = _scores(k_ref[...], q, i, j, blk, scale, diagonal, transposed=True,
                 window=window, **_second(k2_ref, q2_ref))
    pt = jnp.exp(st - lse_ref[...])
    dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                           preferred_element_type=_F32)
    dpt = lax.dot_general(v_ref[...], do, _NT, preferred_element_type=_F32)
    dst = (pt * (dpt - delta_ref[...])).astype(q.dtype)
    dk_acc[...] += jnp.dot(dst, q, preferred_element_type=_F32)
    return dst


def _keys_side_at(n, head, kv_head, band=None):
    """Index maps of a grid (batch, h, key block ``j``, ``t``) whose step
    ``t`` brings query block ``t % n`` of query head ``head(h, t)`` to key
    block ``j`` of key/value head ``kv_head(h, t)``: ``(q_at, k_at,
    row_at, q2_at, k2_at)``. The steps before a key block's diagonal
    fetch the diagonal's block again and compute nothing. With ``band``
    (a window) a head's steps are the ``band`` query blocks from ``j``
    on, ``j + t % band``, and those past the last block fetch the last
    again."""
    if band is None:
        def at(j, t):
            return jnp.maximum(t % n, j)
    else:
        def at(j, t):
            return jnp.minimum(j + t % band, n - 1)

    def q_at(b, h, j, t):
        return b, at(j, t), head(h, t)

    def k_at(b, h, j, t):
        return b, j, kv_head(h, t)

    def row_at(b, h, j, t):
        return b, head(h, t), 0, at(j, t)

    def q2_at(b, h, j, t):
        return b, head(h, t), at(j, t), 0

    def k2_at(b, h, j, t):
        return b, j, 0

    return q_at, k_at, row_at, q2_at, k2_at


def _like(t):
    return jax.ShapeDtypeStruct(t.shape, t.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                scale, blk, n, window=None):
    # grid (batch, key/value head, key block j, t): a key block meets, for
    # each query head of its group in turn, the query blocks at or after it
    # (under a window: the ``band`` blocks from it on, those that exist)
    if len(refs) == 6:
        q2_ref = k2_ref = None
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    else:
        (q2_ref, k2_ref, dq_ref, dk_ref, dv_ref, dq2_ref, dk2_ref,
         dq_acc, dk_acc, dv_acc, dq2_acc, dk2_acc) = refs
    h, j, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    if window is None:
        g, i = t // n, t % n
    else:
        band = _band(window, blk)
        g = t // band
        ahead, there, i = _ahead(j, t, n, blk, window)
    group, dim = dq_acc.shape[0], dq_acc.shape[-1]
    keys = pl.ds(pl.multiple_of(j * blk, blk), blk)
    queries = pl.ds(pl.multiple_of(i * blk, blk), blk)

    @pl.when(t == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # a query block's first key block: block 0, or where its band starts
    @pl.when(j == 0 if window is None
             else there & ((ahead == band - 1) | (j == 0)))
    def _():
        dq_acc[g, queries] = jnp.zeros((blk, dim), _F32)
        if q2_ref is not None:
            dq2_acc[g, queries] = jnp.zeros((blk, dq2_acc.shape[-1]), _F32)

    if q2_ref is not None:
        @pl.when((h == 0) & (t == 0))
        def _():
            dk2_acc[keys] = jnp.zeros((blk, dk2_acc.shape[-1]), _F32)

    def block(diagonal, window=None):
        dst = _keys_side(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         q2_ref, k2_ref, dk_acc, dv_acc, i, j, blk, scale,
                         diagonal, window)
        dq_acc[g, queries] += _turned_product(dst, k_ref[...])
        if q2_ref is not None:
            dk2_acc[keys] += _product(dst, q2_ref[...])
            dq2_acc[g, queries] += _turned_product(dst, k2_ref[...])

    _off_diagonal(block, i > j if window is None else there & (ahead > 0),
                  i, j, blk, window)

    # a query block's last key block
    @pl.when(i == j if window is None else ahead == 0)
    def _():
        block(True, _on_diagonal(blk, window))
        dq = (dq_acc[g, queries] * scale).astype(dq_ref.dtype)
        for head in range(group):
            @pl.when(g == head)
            def _():
                dq_ref[queries, head * dim:(head + 1) * dim] = dq
        if q2_ref is not None:
            dq2_ref[g, queries] = (dq2_acc[g, queries] * scale).astype(
                dq2_ref.dtype)

    @pl.when(t == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if q2_ref is not None:
            @pl.when(h == pl.num_programs(1) - 1)
            def _():
                dk2_ref[keys] = (dk2_acc[keys] * scale).astype(dk2_ref.dtype)


def _turned_product(dst, cols):
    """``dst.T @ cols`` in float32: ``dS^T`` (keys, queries) against the
    keys' (keys, D) gives the queries' (queries, D). Where ``cols`` is
    narrower than a lane tile (the second part's 64) it would fill half
    the MXU's columns: the product is then taken as ``(cols.T @ dst).T``,
    which turns the two narrow sides and gives the MXU ``dst``'s columns."""
    if cols.shape[-1] % _LANES:
        return lax.dot_general(cols, dst, _TN, preferred_element_type=_F32).T
    return lax.dot_general(dst, cols, _TN, preferred_element_type=_F32)


def _product(dst, cols):
    """``dst @ cols`` in float32; for narrow ``cols``, as in
    ``_turned_product``, ``(cols.T @ dst.T).T``."""
    if cols.shape[-1] % _LANES:
        return lax.dot_general(cols, dst, (((0,), (1,)), ((), ())),
                               preferred_element_type=_F32).T
    return jnp.dot(dst, cols, preferred_element_type=_F32)


def resident_bytes(padded, group, dim, d2, itemsize):
    """What the fused backward holds in VMEM for all ``padded`` rows: the
    float32 sums of a group's ``dQ`` (and ``dq2``, and ``dk2``), lanes
    padded to whole tiles, and the two buffers of each of their output
    blocks."""
    lanes2 = -(-d2 // _LANES) * _LANES
    return padded * (group * (dim + lanes2) + lanes2) * (4 + 2 * itemsize)


def backward(q, k, v, out, lse, dout, num_heads, num_kv_heads, scale,
             interpret=False, extra=None, window=None):
    """``(dq, dk, dv)`` from what ``forward`` took and gave and the
    output's cotangent; with ``extra``, ``(dq, dk, dv, dq2, dk2)``. One
    fused kernel where a group's float32 ``dQ`` over all the rows fits in
    VMEM (``resident_bytes`` against ``_RESIDENT_LIMIT_BYTES``), else the
    kernel for the queries' side and the kernel for the keys' side.
    ``window``: ``forward``'s."""
    _no_second_part(extra, window)
    hq, group = int(num_heads), int(num_heads) // int(num_kv_heads)
    bsz, length, _ = q.shape
    dim = q.shape[-1] // hq
    blk, padded = block_size(length, window)
    delta = jnp.sum((dout.astype(_F32) * out.astype(_F32)).reshape(
        bsz, length, hq, dim), axis=-1).transpose(0, 2, 1)
    q, k, v, dout = (_padded(t, 1, padded) for t in (q, k, v, dout))
    lse, delta = (_padded(t, 2, padded)[:, :, None] for t in (lse, delta))
    if extra is not None:
        extra = (_head_major(extra[0], hq, padded),
                 _padded(extra[1], 1, padded))
    d2 = 0 if extra is None else extra[1].shape[-1]
    fused = resident_bytes(padded, group, dim, d2, q.dtype.itemsize) \
        <= _RESIDENT_LIMIT_BYTES
    if fused:
        dout = counted_site(dout, FUSED_BWD_GAUGE)
    grads = (_backward_fused if fused else _backward_by_side)(
        (q, k, v, dout, lse, delta), extra, group, dim, blk, scale, interpret,
        window)
    dq, dk, dv = (t[:, :length] for t in grads[:3])
    if extra is None:
        return dq, dk, dv
    dq2 = grads[3][:, :, :length].transpose(0, 2, 1, 3).reshape(
        bsz, length, -1)
    return dq, dk, dv, dq2, grads[4][:, :length]


def _backward_fused(operands, extra, group, dim, blk, scale, interpret,
                    window=None):
    """The fused kernel over ``backward``'s padded operands ``(q, k, v,
    dout, lse, delta)`` and second part ``(q2 head-major, k2)``: ``(dq,
    dk, dv)`` and with a second part ``dq2`` head-major and ``dk2``,
    padded."""
    q, k, v = operands[:3]
    bsz, padded, _ = q.shape
    n = padded // blk
    head, row = functools.partial(_head_spec, blk, dim), \
        functools.partial(_row_spec, blk)
    band = None if window is None else _band(window, blk)
    steps = band or n       # of a query head at a key block
    q_at, k_at, row_at, q2_at, k2_at = _keys_side_at(
        n, lambda h, t: h * group + t // steps, lambda h, t: h, band)
    in_specs = [head(q_at), head(k_at), head(k_at), head(q_at),
                row(row_at), row(row_at)]
    # a group's ``dQ`` is one block of all the rows, written a query
    # block at a time and leaving when the key/value head changes
    out_specs = [pl.BlockSpec((None, padded, group * dim),
                              lambda b, h, j, t: (b, 0, h)),
                 head(k_at), head(k_at)]
    outs = [q, k, v]
    scratch = [pltpu.VMEM((group, padded, dim), _F32),
               pltpu.VMEM((blk, dim), _F32), pltpu.VMEM((blk, dim), _F32)]
    if extra is not None:
        d2 = extra[1].shape[-1]
        in_specs += _extra_specs(blk, d2, q2_at, k2_at)
        out_specs += [pl.BlockSpec((None, group, padded, d2),
                                   lambda b, h, j, t: (b, h, 0, 0)),
                      pl.BlockSpec((None, padded, d2),
                                   lambda b, h, j, t: (b, 0, 0))]
        outs += extra
        scratch += [pltpu.VMEM((group, padded, d2), _F32),
                    pltpu.VMEM((padded, d2), _F32)]
    # ``dk2`` adds up over the heads, so with a second part they run in turn
    return _call(
        functools.partial(_bwd_kernel, scale=scale, blk=blk, n=n,
                          window=window),
        _named("bwd_kernel", window),
        (bsz, k.shape[-1] // dim, n, group * steps), interpret,
        semantics=("parallel", "parallel" if extra is None else "arbitrary",
                   "arbitrary", "arbitrary"),
        in_specs=in_specs, out_specs=out_specs,
        out_shape=[_like(t) for t in outs],
        scratch_shapes=scratch)(*operands, *(extra or ()))


# -- where all the rows' sums do not fit: a kernel for each side ----------------
# Each forms the score block, its ``exp``, ``dP`` and ``dS`` for itself
# (seven products and two passes of vector work where the fused kernel has
# five and one), and holds one block's sums whatever the length.
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
               scale, blk, window=None):
    if len(refs) == 2:
        (dq_ref, acc_ref), q2_ref, k2_ref = refs, None, None
    else:
        q2_ref, k2_ref, dq_ref, dq2_ref, acc_ref, acc2_ref = refs
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if q2_ref is not None:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    def block(diagonal, window=None):
        k, v = k_ref[...], v_ref[...]
        s = _scores(q_ref[...], k, i, j, blk, scale, diagonal,
                    window=window, **_second(q2_ref, k2_ref))
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = lax.dot_general(do_ref[...], v, _NT,
                             preferred_element_type=_F32)
        ds = (p * (dp - jnp.expand_dims(delta_ref[0], -1))).astype(k.dtype)
        acc_ref[...] += jnp.dot(ds, k, preferred_element_type=_F32)
        if q2_ref is not None:
            acc2_ref[...] += jnp.dot(ds, k2_ref[...],
                                     preferred_element_type=_F32)

    j, before = _band_step(i, j, blk, window)
    _off_diagonal(block, before, i, j, blk, window)

    @pl.when(j == i)
    def _():
        block(True, _on_diagonal(blk, window))
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)
        if q2_ref is not None:
            dq2_ref[...] = (acc2_ref[...] * scale).astype(dq2_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                scale, blk, n, span, window=None):
    # grid (batch, 1, key block j, t): a key block meets every query head
    # in turn, ``span`` steps to a key/value head, each at the query
    # blocks at or after it (under a window: the ``band`` blocks from it
    # on, those that exist)
    if len(refs) == 4:
        (dk_ref, dv_ref, dk_acc, dv_acc), q2_ref, k2_ref = refs, None, None
    else:
        q2_ref, k2_ref, dk_ref, dv_ref, dk2_ref, dk_acc, dv_acc, dk2_acc = refs
    j, t = pl.program_id(2), pl.program_id(3)
    if window is None:
        i = t % n
    else:
        ahead, there, i = _ahead(j, t, n, blk, window)

    @pl.when(t % span == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if q2_ref is not None:
        @pl.when(t == 0)
        def _():
            dk2_acc[...] = jnp.zeros_like(dk2_acc)

    def block(diagonal, window=None):
        dst = _keys_side(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         q2_ref, k2_ref, dk_acc, dv_acc, i, j, blk, scale,
                         diagonal, window)
        if q2_ref is not None:
            dk2_acc[...] += jnp.dot(dst, q2_ref[...],
                                    preferred_element_type=_F32)

    _off_diagonal(block, i > j if window is None else there & (ahead > 0),
                  i, j, blk, window)
    pl.when(i == j if window is None else ahead == 0)(functools.partial(
        block, True, _on_diagonal(blk, window)))

    @pl.when(t % span == span - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    if q2_ref is not None:
        @pl.when(t == pl.num_programs(3) - 1)
        def _():
            dk2_ref[...] = (dk2_acc[...] * scale).astype(dk2_ref.dtype)


def _backward_by_side(operands, extra, group, dim, blk, scale, interpret,
                      window=None):
    """``_backward_fused``'s results from the two kernels."""
    q, k, v = operands[:3]
    bsz, padded, _ = q.shape
    n, hq = padded // blk, q.shape[-1] // dim
    steps, kv_at = _queries_side(n, blk, group, window)
    head, row = functools.partial(_head_spec, blk, dim), \
        functools.partial(_row_spec, blk)
    extra = extra or ()
    d2 = extra[1].shape[-1] if extra else 0
    sums2 = [pltpu.VMEM((blk, d2), _F32)] if extra else []
    second = _extra_specs(blk, d2, _q2_at, _k2_at) if extra else []
    dq = _call(
        functools.partial(_dq_kernel, scale=scale, blk=blk, window=window),
        _named("bwd_dq_kernel", window), (bsz, hq, n, steps), interpret,
        in_specs=[head(_q_at), head(kv_at), head(kv_at), head(_q_at),
                  row(_row_at), row(_row_at)] + second,
        out_specs=[head(_q_at)] + second[:1],
        out_shape=[_like(q)] + [_like(t) for t in extra[:1]],
        scratch_shapes=[pltpu.VMEM((blk, dim), _F32)] + sums2)(
            *operands, *extra)
    span = group * steps
    q_at, k_at, row_at, q2_at, k2_at = _keys_side_at(
        n, lambda _, t: t // steps, lambda _, t: t // span,
        None if window is None else steps)
    second = _extra_specs(blk, d2, q2_at, k2_at) if extra else []
    dkv = _call(
        functools.partial(_dkv_kernel, scale=scale, blk=blk, n=n, span=span,
                          window=window),
        _named("bwd_dkv_kernel", window), (bsz, 1, n, hq * steps), interpret,
        in_specs=[head(q_at), head(k_at), head(k_at), head(q_at),
                  row(row_at), row(row_at)] + second,
        out_specs=[head(k_at), head(k_at)] + second[1:],
        out_shape=[_like(k), _like(v)] + [_like(t) for t in extra[1:]],
        scratch_shapes=[pltpu.VMEM((blk, dim), _F32),
                        pltpu.VMEM((blk, dim), _F32)] + sums2)(
            *operands, *extra)
    return dq[:1] + dkv[:2] + dq[1:] + dkv[2:]


# ---------------------------------------------------------------------------
# the count of call sites lowered to the kernel
# ---------------------------------------------------------------------------
# Which form a call site takes is decided when its program is lowered
# (``lax.platform_dependent``), so that is where a site is counted: an
# identity whose lowering rule, reached only inside the TPU branch, adds
# one to a gauge: ``attn::kernel_sites`` for a site that takes the
# kernels, ``attn::fused_bwd_sites`` for one whose backward is the fused
# kernel, ``attn::window_sites`` for one whose kernels walk a window's
# band. ``TrainStep`` sets the three to zero where it traces its step.
GAUGE = "attn::kernel_sites"
FUSED_BWD_GAUGE = "attn::fused_bwd_sites"
#: of the sites that take the kernels, those whose kernels walk a window's
#: band and skip the blocks before it
WINDOW_GAUGE = "attn::window_sites"

_site_p = Primitive("mx_attn_kernel_site")
_site_p.def_impl(lambda x, gauge: x)
_site_p.def_abstract_eval(lambda x, gauge: x)


def _site_lowering(ctx, x, gauge):
    from .. import telemetry
    telemetry.gauge(gauge).inc()
    return [x]


mlir.register_lowering(_site_p, _site_lowering)
# an identity to differentiation too: a site may stand where JAX
# differentiates (the hyper-connection's maps); the tangent is not counted
ad.defjvp(_site_p, lambda t, x, gauge: t)


def counted_site(x, gauge=GAUGE):
    """``x``; lowering it adds one to ``gauge``."""
    return _site_p.bind(x, gauge=gauge)
