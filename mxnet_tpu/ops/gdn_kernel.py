"""The gated delta rule and the gated norm after it as kernels (Pallas on
Mosaic), for ``ops.seq.gated_delta_net`` where its program is lowered for
a TPU and the heads are whole lane tiles: one forward kernel and one
backward kernel, under the mixer's one ``custom_vjp``
(``ops.seq._mixer_kernels``). What they compute is ``y = rmsnorm(o) * w *
silu(z)`` a value head, ``o`` ``ops.seq.gated_delta_rule``'s output.

The arrays stay as the mixer wrote them: ``q`` and ``k`` are (B, L, G * N),
``v`` (B, L, H * P), and a key head with the ``H / G`` value heads it
serves is the (rows, N) and (rows, H / G * P) window at column ``j`` of a
block of rows, so nothing is transposed to (B, H, L, .) on the way in or
out. The gate's ``z`` is read where the mixer's projection wrote it: the
last H * P columns of the packed rows (B, L, 2 G N + 2 H P), windowed at
their offset, a whole number of a key head's H / G * P columns (32 blocks
of 256 into 12288 at the Qwen3-Next cell's heads), so no slice of ``z`` is
copied out; ``y`` and its cotangent are (B, L, H * P) in the compute
dtype, as the output product reads and writes them. A value head's
``beta`` and the running sum ``G`` of its log decay inside a chunk (a
cumulative sum XLA makes of a (B, L, H) array) come as rows, (B, H, 1, L),
and are turned to columns in the kernel.

The grid is (batch, key head, blocks of ``step_rows`` rows: four chunks of
64), the blocks in turn. A value head's (N, P) float32 state lives in a VMEM scratch from
the first chunk to the last. For each chunk of ``chunk`` rows the forward
kernel loads ``q``, ``k``, ``v``, ``beta`` and ``G`` once and forms, in
VMEM and float32, the decays ``exp(G_i - G_j)``, the strictly lower system
``A = beta (k k^T) exp(G_i - G_j)`` and ``T = (I + A)^-1``; then ``[W | U]
= T [beta k exp(G) | beta v]`` in one float32 product, ``written = U - W
S``, ``out = (q exp(G)) S + tril(q k^T exp(G_i - G_j)) written`` and ``S <-
exp(G_last) S + (k exp(G_last - G))^T written``. What does not read the
state is formed for all the chunks of a block before the chain, so the
compiler has several chunks' products to interleave.

**The inverse**, by blocks and products: the diagonal blocks of 8 rows,
``D``, are nilpotent of order 8, so their inverses are ``(I - D)(I + D^2)
(I + D^4)`` (two squarings and two products, all diagonal blocks at once
in one masked (chunk, chunk) matrix; the powers of an 8-row block grow by
at most 35); then blocks of 8 are merged to 16, 32, ... by ``T <- T - T E
T``, ``E`` the part of ``A`` that joins two neighbours (two products a
level; no power of the whole ``A``, whose entries can reach ``C(62, 31)``,
is ever formed). Ten float32 products of (chunk, chunk) a chunk and value
head at chunk 64.

**A float32 product** (those ten, ``T [.|.]`` and, backward, ``T^T [.|.]``
and ``[.][W | U]^T``) is made as ``Precision.HIGHEST`` makes it on the
MXU, from three pieces an operand that a bfloat16 holds exactly
(``_split``: the top sixteen bits, those of the rest, the rest) and the
six products of pieces ``i``, ``j`` with ``i + j <= 4``, but as ONE
bfloat16 product over a longer contraction (``_left``, ``_right``,
``_exact``): six passes over float32 operands push the weights and pop
the results six times, 2.6 times the MXU's slots. A (chunk, chunk) matrix
that does not fill a lane tile is held twice side by side, ``[x | x]``
(``_twice``), and the inverse's products carry different pieces in the two
halves of the lanes (``_exact_twice``): a contraction of four chunks, not
six, whose halves are added into each other's place by one turn of the
lanes, which also leaves the result held twice; the operands are selects
between pieces, nothing else is moved across the lanes.

**The gated norm** is taken where a chunk's ``out`` (chunk, P) of one
value head is still in VMEM, exactly the rows and columns one norm reads:
``out rsqrt(mean_P(out^2) + eps) w silu(z)`` in float32, rounded once to
the compute dtype at the store (where the plain form rounds before the
output product); SiLU by one ``tanh``. ``out`` itself never goes to
memory.

Nothing a chunk forms goes to memory except ``y`` and what the backward
pass reads: each chunk's entering state (N, P) and its ``T`` (chunk,
chunk), both float32: the state because the chain cannot be run backwards
without it, ``T`` because it is ten float32 products to form and 16 KB to
hold. (A forward that no backward follows writes them all the same, a
tenth of its time: a second variant of the kernel would be a second trace
and a second Mosaic compile at every set-up, and a training step, where
JAX runs the forward rule in both passes of a recomputation unit, would
never run it.)

The backward kernel walks the blocks, and the chunks in them, in reverse
and carries ``dS`` in the same scratch. It reads a chunk's entering state
and ``T``, forms the decays, ``k k^T``, ``q k^T``, ``[W | U]``,
``written`` and ``out`` (two products a unit that the forward made too)
again, goes back through the gated norm where it loads ``y``'s cotangent
(``r = rsqrt(mean(out^2) + eps)``, ``o^ = out r``, ``t = dy w silu(z)``:
``d_out = r (t - o^ mean(t o^))``, rounded as the plain form's products
round the output's cotangent; ``dz = dy o^ w silu'(z)``, a block of its
own in ``z``'s dtype; the weight's gradient as row sums of ``dy o^
silu(z)``, added up over a sequence's steps in one float32 block a batch
entry and key head, which XLA sums), and gives ``dq``, ``dk`` (each summed
over the value heads that share the key head, in float32, rounded once),
``dv``, ``dbeta`` and ``dG``. The gradient through the inverse is ``dA = -T^T (dW
rhs_w^T + dU rhs_u^T) T^T = -([dRw | dRu]) [W | U]^T`` with ``[dRw | dRu] =
T^T [dW | dU]``: two float32 products, no second inverse.

The arithmetic is the plain form's (``ops.seq.gated_delta_rule``, then
``_gated_norm``): decays, system, inverse, state, the norm, SiLU, their
derivatives and every sum in float32; the operands of the products that
the plain form rounds to ``v``'s dtype are rounded here, exactly there, and
a cotangent that meets such an operand in a product is rounded as it (on a
TPU XLA's default precision does the same to the plain form's
derivative); what the plain form keeps in float32 (the solve, XLA's at
``Precision.HIGHEST``) is the same six products of pieces here. A padded tail (``beta = 0``, ``g = 0``)
writes nothing; a strong decay underflows to 0: only ``exp`` of
differences ``G_i - G_j <= 0`` is ever taken.

What a call holds in VMEM is stated by ``forward_bytes`` and
``backward_bytes`` and held under ``_BUDGET_BYTES`` by ``takes``, the rule
of shapes: shapes it does not take stay the plain form.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GAUGE = "gdn::kernel_sites"

_F32 = jnp.float32
_BF16 = jnp.bfloat16
_LANES = 128
_SUBLANES = 8
#: the chunks the rule of shapes takes: a diagonal block of 8 rows doubled
#: a few times, whole sublane tiles, at most a lane tile
_CHUNKS_TAKEN = (8, 16, 32, 64, 128)
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# of them, what the blocks, the scratch and a block's values that do not
# read the state may take; the rest is a chunk's float32 temporaries
_BUDGET_BYTES = 32 * 1024 * 1024
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


# ---------------------------------------------------------------------------
# the rule of shapes
# ---------------------------------------------------------------------------
def step_rows(chunk):
    """The rows a grid step works of a sequence longer than that: 256
    (four chunks of 64, two of 128), 128 for smaller chunks: whole lane
    tiles, as the rows of floats (1, rows) have to be. A shorter sequence
    is one step of all its chunks."""
    return 256 if chunk >= 64 else _LANES


def steps(length, chunk):
    """``(padded length, rows a step)`` for a sequence of ``length``: whole
    chunks in one step where that is at most ``step_rows``, else whole
    steps of ``step_rows``."""
    padded = -(-length // chunk) * chunk
    rows = step_rows(chunk)
    if padded <= rows:
        return padded, padded
    return -(-length // rows) * rows, rows


def forward_bytes(n, p, chunk, group, itemsize):
    """What the forward call names in VMEM for a step of ``step_rows``
    rows and ``group`` value heads a key head: every block twice, for the
    pipeline (q, k, v, the gate's ``z`` and the gated output in the
    compute dtype, the norm's weight, both rows of floats, the kept states
    and inverses), the states' scratch, and what a block's chunks hold
    before the chain (``W``, ``U`` in float32; the decayed queries and
    keys and the masked scores in the compute dtype)."""
    rows = step_rows(chunk)
    chunks = rows // chunk
    blocks = rows * (2 * n + 3 * group * p) * itemsize + p * 4 \
        + 2 * group * rows * 4 \
        + chunks * group * (n * p + chunk * chunk) * 4
    held = rows * group * ((n + p) * 4 + (2 * n + chunk) * itemsize)
    return 2 * blocks + group * n * p * 4 + held


def backward_bytes(n, p, chunk, group, itemsize):
    """What the backward call names in VMEM: every block twice (q, k, v,
    ``z``, the gated output's cotangent, the norm's weight, the rows, the
    states and inverses in; dq, dk, dv, dz, two rows and the weight's row
    sums out), ``dS``'s scratch, and a block's recomputed ``W``, ``U``,
    ``written``, ``out`` and scores."""
    rows = step_rows(chunk)
    chunks = rows // chunk
    blocks = rows * (4 * n + 5 * group * p) * itemsize \
        + (1 + _SUBLANES) * p * 4 + 4 * group * rows * 4 \
        + chunks * group * (n * p + chunk * chunk) * 4
    held = rows * group * ((n + 3 * p) * 4 + (2 * n + chunk) * itemsize)
    return 2 * blocks + group * n * p * 4 + held


def takes(n, p, chunk, dtype, group=1, gate_offset=0):
    """Whether the kernels take a rule whose keys are ``n`` wide and
    values ``p``, in chunks of ``chunk`` rows, ``group`` value heads to a
    key head, the products' operands in ``dtype``, the gate's ``z`` read
    ``gate_offset`` columns into the rows that hold it: ``n`` and ``p``
    whole lane tiles of 128, the chunk 8, 16, 32, 64 or 128 rows and whole
    sublane tiles of ``dtype`` (8 rows of float32, 16 of bfloat16), the
    offset whole blocks of a key head's ``group * p`` columns, and what
    the calls hold in VMEM under the budget. Shapes alone."""
    if jnp.dtype(dtype) not in (jnp.dtype(_BF16), jnp.dtype(_F32)):
        return False
    itemsize = jnp.dtype(dtype).itemsize
    if n <= 0 or p <= 0 or n % _LANES or p % _LANES:
        return False
    if group <= 0 or gate_offset % (group * p):
        return False
    if chunk not in _CHUNKS_TAKEN or chunk % (32 // itemsize):
        return False
    return max(forward_bytes(n, p, chunk, group, itemsize),
               backward_bytes(n, p, chunk, group, itemsize)) \
        <= _BUDGET_BYTES


# ---------------------------------------------------------------------------
# a chunk's decays, system and inverse
# ---------------------------------------------------------------------------
# Every stage below is written over all the units (a chunk and value head)
# of a grid step before the next stage begins: the compiler issues in the
# program's order, and a unit's ten dependent products one after another
# wait for the MXU's result each time (8.5 ms a layer forward on the chip
# against 5.5 stage by stage; PERF.md, PR 42). ``_TOGETHER`` units go
# through the inverse's stages side by side: all eight of a step, so that
# each link of a unit's chain (a product, the turn of its lanes, the next
# split) stands behind seven other units' (about 6000 bundles a step in
# the compiled schedule against 7200 by fours).
_TOGETHER = 8


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _split(x):
    """A float32 matrix as three float32 pieces that sum to it exactly,
    each of eight significant bits (a bfloat16 holds it exactly): the top
    sixteen bits of ``x``, of what is left, and what is left then."""
    def top(v):
        bits = lax.bitcast_convert_type(v, jnp.int32)
        return lax.bitcast_convert_type(bits & jnp.int32(-65536), _F32)

    hi = top(x)
    rest = x - hi
    mid = top(rest)
    return hi, mid, rest - mid


def _left(split, axis=1):
    """A split matrix as an exact product's left operand: its pieces along
    the contraction in the order hi, hi, mid, hi, mid, low."""
    hi, mid, low = split
    return jnp.concatenate([hi, hi, mid, hi, mid, low], axis=axis)


def _right(split, axis=0):
    """A split matrix as an exact product's right operand: its pieces along
    the contraction in the order hi, mid, hi, low, mid, hi, so that piece
    ``i`` of the left meets piece ``j`` for ``i + j <= 4``."""
    hi, mid, low = split
    return jnp.concatenate([hi, mid, hi, low, mid, hi], axis=axis)


def _exact(left, right, dims=_NN):
    """A product of float32 matrices at float32's precision from
    ``_left`` and ``_right`` of them: the six products of bfloat16 pieces
    that ``Precision.HIGHEST`` makes of it on the MXU, as ONE product over
    a contraction six times as long (six passes over float32 operands
    push the weights and pop the results six times: 2.6 times the MXU's
    slots by the compiled schedule; PERF.md, PR 42). The operands are
    rounded to bfloat16, exactly, here, where the product is their only
    reader: the compiler then packs them in the MXU's tiling at once
    (pieces rounded one by one and put together after took 2.5 times the
    vector slots)."""
    return _dot(left.astype(_BF16), right.astype(_BF16), dims)


def _each(left, right):
    """``_exact`` of every unit's operands, (units, rows, .) each."""
    return jnp.stack([_exact(left[i], right[i])
                      for i in range(left.shape[0])])


def _first_half(shape):
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) \
        < shape[-1] // 2


def _left_twice(split):
    """``_left`` of a (chunk, chunk) matrix from the split ``[x | x]``, held
    twice side by side to fill a lane tile: three whole tiles ``[hi | hi]``,
    ``[mid | hi]``, ``[mid | low]`` by two selects, no piece moved across
    the lanes."""
    hi, mid, low = split
    first = _first_half(hi.shape)
    return jnp.concatenate([hi, jnp.where(first, mid, hi),
                            jnp.where(first, mid, low)], axis=1)


def _exact_twice(a, b):
    """``[a b | a b]`` at float32's precision from the splits of ``[a | a]``
    and ``[b | b]``, every unit's (chunk, chunk) matrices held twice side
    by side to fill a lane tile, (units, chunk, 2 chunk). The two halves
    of the lanes carry different pieces: the left operand is ``[hi | mid]``
    and ``[hi | low]``, the right one, below one another, ``[hi | mid]``
    twice, ``[low | 0]`` and ``[0 | hi]``, so the product's left half is
    ``hi hi + mid hi + hi low`` and its right half ``hi mid + mid mid +
    low hi``: the six products over a contraction of four chunks, not six,
    and the halves added to each other's place by a turn of the lanes. No
    piece is moved across the lanes."""
    (a_hi, a_mid, a_low), (b_hi, b_mid, b_low) = a, b
    first = _first_half(a_hi.shape)
    left = jnp.concatenate([jnp.where(first, a_hi, a_mid),
                            jnp.where(first, a_hi, a_low)], axis=2)
    both = jnp.where(first, b_hi, b_mid)
    right = jnp.concatenate([both, both, jnp.where(first, b_low, 0.0),
                             jnp.where(first, 0.0, b_hi)], axis=1)
    halves = _each(left, right)
    return halves + pltpu.roll(halves, halves.shape[2] // 2, 2)


def _twice(chunk):
    """Whether a chunk's (chunk, chunk) matrices are held twice side by
    side: where one does not fill a lane tile. A product whose right
    operand is held so gives its result so, at no cost on the MXU."""
    return chunk % _LANES != 0


def _masks(chunk, twice=False):
    """``(row, col)`` (chunk, chunk) int32; ``twice``: of ``[x | x]``."""
    shape = (chunk, 2 * chunk if twice else chunk)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lax.broadcasted_iota(jnp.int32, shape, 0),
            jnp.where(col >= chunk, col - chunk, col))


def _column(row_vec, row, col):
    """A (1, chunk) row of floats as a (chunk, 1) column."""
    return jnp.sum(jnp.where(row == col, row_vec, 0.0), axis=1,
                   keepdims=True)


def _as_row(col_vec, row, col):
    """A (chunk, 1) column of floats as a (1, chunk) row."""
    return jnp.sum(jnp.where(row == col, col_vec, 0.0), axis=0,
                   keepdims=True)


def _decays(g_row, row, col, wide):
    """From a chunk's running sums ``G`` (1, chunk): ``G`` as a column,
    ``exp(G_i - G_j)`` for ``j <= i`` and 0 above the diagonal, and the
    same with a zero diagonal, both over the masks ``wide``."""
    g_col = _column(g_row, row, col)
    if wide[0].shape != row.shape:
        g_row = jnp.concatenate([g_row, g_row], axis=1)
    row, col = wide
    upto = jnp.exp(jnp.where(row >= col, g_col - g_row, -jnp.inf))
    return g_col, upto, jnp.where(row > col, upto, 0.0)


def _inverses(systems, row, col):
    """``(I + A)^-1`` of each strictly lower (chunk, chunk) float32 ``A``
    of ``systems``, by blocks and products (the module's docstring); the
    matrices and the masks twice side by side where ``_twice``. The
    matrices go through every stage as one (units, chunk, .) array: a
    stage is then a handful of operations to trace and lower, not a
    handful a unit (the step's lowering is part of every set-up)."""
    if len(systems) > _TOGETHER:
        return [t for i in range(0, len(systems), _TOGETHER)
                for t in _inverses(systems[i:i + _TOGETHER], row, col)]
    chunk = row.shape[0]
    eye = (row == col).astype(_F32)

    def same(size):         # a power of two
        shift = size.bit_length() - 1
        return (row >> shift) == (col >> shift)

    twice = row.shape[1] != chunk

    def product(a, b):      # of two split stacks of matrices
        if twice:
            return _exact_twice(a, b)
        return _each(_left(a, axis=2), _right(b, axis=1))

    system = jnp.stack(systems)
    size = min(8, chunk)
    power = jnp.where(same(size), system, 0.0)
    t = eye - power
    split = _split(power)
    order = 2
    while order < size:             # (I - D)(I + D^2)(I + D^4)
        split = _split(product(split, split))
        t = t + product(_split(t), split)
        order *= 2
    while size < chunk:             # neighbours joined: T - T E T
        join = same(2 * size) & jnp.logical_not(same(size))
        split = _split(t)
        half = product(split, _split(jnp.where(join, system, 0.0)))
        t = t - product(_split(half), split)
        size *= 2
    return [t[i] for i in range(len(systems))]


#: what both kernels form of a chunk ``c`` and value head ``h`` before
#: anything reads a state: the chunk's ``rows``, ``q`` and ``k`` as loaded
#: and in float32, ``k k^T`` and ``q k^T``; the head's ``v32``, the running
#: sum ``G`` as a row (``g_row``) and a column (``g``), ``beta`` as a column
#: (``b``), ``exp(G_i - G_j)`` up to the diagonal (``upto``) and below it
#: (``below``), ``grow = exp(G)``, ``to_end = exp(G_last - G)`` and
#: ``leave = exp(G_last)``. The (chunk, chunk) matrices twice side by side
#: where ``_twice``.
_Unit = collections.namedtuple(
    "_Unit", "c h rows q k q32 k32 kk qk v32 g_row g b upto below grow "
    "to_end leave")


def _units(q_ref, k_ref, v_ref, b_ref, g_ref, chunk, group, p):
    """A block's ``_Unit`` s, formed stage by stage, and the masks ``(row,
    col)`` of their (chunk, chunk) matrices."""
    twice = _twice(chunk)
    row, col = _masks(chunk)
    wide = _masks(chunk, twice)
    chunks = q_ref.shape[0] // chunk
    rows = [slice(c * chunk, (c + 1) * chunk) for c in range(chunks)]
    heads = [(c, h) for c in range(chunks) for h in range(group)]
    qs, ks = [q_ref[r, :] for r in rows], [k_ref[r, :] for r in rows]
    keys = [jnp.concatenate([k, k], axis=0) if twice else k for k in ks]
    kks = [_dot(k, k2, _NT) for k, k2 in zip(ks, keys)]
    qks = [_dot(q, k2, _NT) for q, k2 in zip(qs, keys)]
    q32s, k32s = ([x.astype(_F32) for x in xs] for xs in (qs, ks))
    g_rows = [g_ref[h, :, rows[c]] for c, h in heads]
    b_cols = [_column(b_ref[h, :, rows[c]], row, col) for c, h in heads]
    decays = [_decays(g, row, col, wide) for g in g_rows]
    v32s = [v_ref[rows[c], h * p:(h + 1) * p].astype(_F32)
            for c, h in heads]
    grows = [jnp.exp(g) for g, _, _ in decays]
    lasts = [g[:, chunk - 1:] for g in g_rows]
    to_ends = [jnp.exp(last - g) for last, (g, _, _) in zip(lasts, decays)]
    leaves = [jnp.exp(last) for last in lasts]
    return [_Unit(c, h, rows[c], qs[c], ks[c], q32s[c], k32s[c], kks[c],
                  qks[c], v32s[i], g_rows[i], decays[i][0], b_cols[i],
                  decays[i][1], decays[i][2], grows[i], to_ends[i], leaves[i])
            for i, (c, h) in enumerate(heads)], wide


# ---------------------------------------------------------------------------
# the gated norm
# ---------------------------------------------------------------------------
def _normed(out, eps):
    """``(out r, r)`` with ``r = rsqrt(mean(out^2) + eps)`` over the lanes:
    a chunk's rows of one value head, each over its ``p`` numbers."""
    r = lax.rsqrt(jnp.mean(out * out, axis=1, keepdims=True) + eps)
    return out * r, r


def _sigmoid(z):
    """By one ``tanh``, as ``gdn_conv_kernel`` takes its SiLU."""
    return 0.5 + 0.5 * jnp.tanh(0.5 * z)


def _head(ref, u, p):
    """A unit's rows and columns of a block of value heads, float32."""
    return ref[u.rows, u.h * p:(u.h + 1) * p].astype(_F32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, b_ref, g_ref, z_ref, w_ref, o_ref,
                states_ref, inverses_ref, s_ref, *, chunk, group, n, p, eps):
    dtype = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    units, wide = _units(q_ref, k_ref, v_ref, b_ref, g_ref, chunk, group, p)
    # what does not read the state
    ts = _inverses([u.b * u.kk * u.below for u in units], *wide)
    for u, t in zip(units, ts):
        inverses_ref[u.h, u.c] = t[:, :chunk]
    left = _left_twice if _twice(chunk) else _left
    wus = [_exact(left(_split(t)), _right(_split(
        jnp.concatenate([u.k32 * (u.b * u.grow), u.v32 * u.b], axis=1))))
           for t, u in zip(ts, units)]
    ws = [wu[:, :n].astype(dtype) for wu in wus]
    inside = [(u.qk * u.upto)[:, :chunk].astype(dtype) for u in units]
    q_in = [(u.q32 * u.grow).astype(dtype) for u in units]
    k_out = [(u.k32 * u.to_end).astype(dtype) for u in units]
    weight = w_ref[...]
    # the chain, a chunk's value heads side by side
    for first in range(0, len(units), group):
        mine = range(first, first + group)
        states = [s_ref[units[i].h] for i in mine]
        for i, state in zip(mine, states):
            states_ref[units[i].h, units[i].c] = state
        ss = [state.astype(dtype) for state in states]
        reads = [_dot(ws[i], s) for i, s in zip(mine, ss)]
        outs = [_dot(q_in[i], s) for i, s in zip(mine, ss)]
        written = [(wus[i][:, n:] - read).astype(dtype)
                   for i, read in zip(mine, reads)]
        outs = [out + _dot(inside[i], w)
                for i, out, w in zip(mine, outs, written)]
        for i, out, w, state in zip(mine, outs, written, states):
            u = units[i]
            # the gated norm where the head's rows are: rounded once, here
            z = _head(z_ref, u, p)
            o_ref[u.rows, u.h * p:(u.h + 1) * p] = (
                _normed(out, eps)[0] * weight * (z * _sigmoid(z))).astype(
                    o_ref.dtype)
            s_ref[u.h] = u.leave * state + _dot(k_out[i], w, _TN)


def _specs(chunk, chunks, group, n, p, at, gate):
    """Block specs by what they window, for a grid (batch, key head,
    block) whose step ``i`` works the block ``at(i)``: a key head's rows,
    the rows of its value heads, the same of the gate's ``z`` (``gate``
    blocks into the rows that hold it), the norm's weight, their rows of
    floats, their chunks' (a, b) float32 matrices, and a key head's row
    sums for the weight (one block for all the steps of a sequence)."""
    rows = chunk * chunks
    return {
        "key": pl.BlockSpec((None, rows, n), lambda b, j, i: (b, at(i), j)),
        "value": pl.BlockSpec((None, rows, group * p),
                              lambda b, j, i: (b, at(i), j)),
        "gate": pl.BlockSpec((None, rows, group * p),
                             lambda b, j, i: (b, at(i), gate + j)),
        "weight": pl.BlockSpec((1, p), lambda b, j, i: (0, 0)),
        "sums": pl.BlockSpec((None, None, _SUBLANES, p),
                             lambda b, j, i: (b, j, 0, 0)),
        "floats": pl.BlockSpec((None, group, 1, rows),
                               lambda b, j, i: (b, j, 0, at(i))),
        "chunks": lambda a, b_: pl.BlockSpec(
            (None, group, chunks, a, b_),
            lambda b, j, i: (b, j, at(i), 0, 0)),
    }


def _call(kernel, name, grid, interpret, **specs):
    return pl.pallas_call(
        kernel, grid=grid, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES), **specs)


def _rows_of_floats(x, heads):
    """(B, L, H) float32 as (B, H, 1, L)."""
    return x.astype(_F32).transpose(0, 2, 1)[:, :, None]


def _operands(q, k, v, beta, g, z, weight, chunk):
    """The kernels' operands from the rule's and the gate's: ``q``, ``k``
    (B, L, G * N) and ``v`` (B, L, H * P) padded to whole steps (``steps``;
    a padded row has ``beta = 0`` and ``g = 0``), ``beta`` and the running
    sum of ``g`` inside each chunk as rows of floats, the rows that hold
    ``z`` in their last H * P columns as they are (a padded tail: those
    columns alone, padded) and the norm's weight as a float32 row; the
    chunks of a step; and the block of a key head's columns at which ``z``
    begins."""
    bsz, length, h, p = v.shape
    padded, rows = steps(length, chunk)
    if padded != length:
        z = z[..., z.shape[-1] - h * p:]
        q, k, v, beta, g, z = (
            jnp.pad(t, ((0, 0), (0, padded - length)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, beta, g, z))
    run = jnp.cumsum(g.astype(_F32).reshape(bsz, padded // chunk, chunk, h),
                     axis=2).reshape(bsz, padded, h)
    gate = (z.shape[-1] - h * p) // (h // k.shape[2] * p)
    return (q.reshape(bsz, padded, -1), k.reshape(bsz, padded, -1),
            v.reshape(bsz, padded, -1),
            _rows_of_floats(beta, h), _rows_of_floats(run, h), z,
            weight.astype(_F32).reshape(1, p)), rows // chunk, gate


@functools.partial(jax.jit, static_argnames=("chunk", "eps", "interpret"))
def forward(q, k, v, beta, g, z, weight, chunk, eps, interpret=False):
    """``(y, states, inverses)``: ``rmsnorm(o) * weight * silu(z)`` a value
    head, (B, L, H * P) in ``v``'s dtype, with ``o`` ``gated_delta_rule``'s
    output of ``q``, ``k``, ``v``, ``beta``, ``g``, the norm over a head's
    ``P`` numbers with ``eps``; and what ``backward`` reads: every chunk's
    entering state (B, H, chunks, N, P) and inverse (B, H, chunks, chunk,
    chunk), float32, ``chunks`` those of the padded length (``steps``).
    ``z`` is the last H * P columns of the rows it comes in, (B, L, >= H *
    P), which are read where they are (``takes``' ``gate_offset`` is what
    stands before them); ``weight`` (P,). (Jitted, as ``backward`` is: a
    step's like layers and both passes of a recomputation unit then share
    one trace of the kernel.)"""
    bsz, length, h, p = v.shape
    gk, n = k.shape[2], k.shape[3]
    group = h // gk
    operands, step, gate = _operands(q, k, v, beta, g, z, weight, chunk)
    chunks = operands[0].shape[1] // chunk
    specs = _specs(chunk, step, group, n, p, lambda i: i, gate)
    out, states, inverses = _call(
        functools.partial(_fwd_kernel, chunk=chunk, group=group, n=n, p=p,
                          eps=eps),
        "gdn_fwd_kernel", (bsz, gk, chunks // step), interpret,
        in_specs=[specs["key"], specs["key"], specs["value"],
                  specs["floats"], specs["floats"], specs["gate"],
                  specs["weight"]],
        out_specs=[specs["value"], specs["chunks"](n, p),
                   specs["chunks"](chunk, chunk)],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, chunks * chunk, h * p), v.dtype),
            jax.ShapeDtypeStruct((bsz, h, chunks, n, p), _F32),
            jax.ShapeDtypeStruct((bsz, h, chunks, chunk, chunk), _F32)],
        scratch_shapes=[pltpu.VMEM((group, n, p), _F32)])(*operands)
    return out[:, :length], states, inverses


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_kernel(q_ref, k_ref, v_ref, b_ref, g_ref, z_ref, w_ref, s_ref,
                t_ref, dy_ref, dq_ref, dk_ref, dv_ref, db_ref, dg_ref, dz_ref,
                dw_ref, ds_ref, *, chunk, group, n, p, eps):
    dtype = v_ref.dtype
    row, col = _masks(chunk)
    last = (lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            == chunk - 1).astype(_F32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def total(x):
        return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                       keepdims=True)

    def lanes(x):
        return jnp.sum(x, axis=1, keepdims=True)

    units, _ = _units(q_ref, k_ref, v_ref, b_ref, g_ref, chunk, group, p)
    # the (chunk, chunk) matrices once: this kernel is their left operand
    # in no exact product
    units = [u._replace(kk=u.kk[:, :chunk], qk=u.qk[:, :chunk],
                        upto=u.upto[:, :chunk], below=u.below[:, :chunk])
             for u in units]
    count = range(len(units))
    # the forward's values again, from the kept states and inverses
    ts = [t_ref[u.h, u.c] for u in units]
    states = [s_ref[u.h, u.c] for u in units]
    k_in = [u.k32 * u.grow for u in units]
    t_split = [_split(t) for t in ts]
    if _twice(chunk):
        lefts = [_left_twice(tuple(jnp.concatenate([x, x], axis=1)
                                   for x in split)) for split in t_split]
    else:
        lefts = [_left(split) for split in t_split]
    wus = [_exact(lefts[i], _right(_split(jnp.concatenate(
        [k_in[i] * u.b, u.v32 * u.b], axis=1))))
           for i, u in enumerate(units)]
    ws = [wu[:, :n].astype(dtype) for wu in wus]
    ss = [state.astype(dtype) for state in states]
    reads = [_dot(ws[i], ss[i]) for i in count]
    written = [(wus[i][:, n:] - reads[i]).astype(dtype) for i in count]
    scores = [u.qk * u.upto for u in units]
    inside = [x.astype(dtype) for x in scores]
    q_in = [u.q32 * u.grow for u in units]
    q_ins = [x.astype(dtype) for x in q_in]
    k_out = [u.k32 * u.to_end for u in units]
    outs = [_dot(q_ins[i], ss[i]) + _dot(inside[i], written[i])
            for i in count]
    # the gated norm, backwards: y = (out r) w silu(z), a row's r over its
    # head's lanes; what reaches the rule is rounded as the plain form's
    # products round the output's cotangent
    weight = w_ref[...]
    dos, d_weight = [], jnp.zeros(dw_ref.shape, _F32)
    for u, out in zip(units, outs):
        z, dy = _head(z_ref, u, p), _head(dy_ref, u, p)
        normed, r = _normed(out, eps)
        sig = _sigmoid(z)
        silu = z * sig
        t = dy * weight * silu
        dos.append((r * (t - normed * jnp.mean(t * normed, axis=1,
                                               keepdims=True))).astype(dtype))
        dyn = dy * normed
        dz_ref[u.rows, u.h * p:(u.h + 1) * p] = (
            dyn * weight * (sig * (1.0 + z * (1.0 - sig)))).astype(
                dz_ref.dtype)
        # a chunk's rows to one sublane tile: whole registers added up
        sums = dyn * silu
        for first in range(0, chunk, _SUBLANES):
            d_weight = d_weight + sums[first:first + _SUBLANES]
    dw_ref[...] += d_weight
    # what of the backward does not read dS (above the diagonal d_inside
    # is read by nothing: every use below is times ``upto``)
    d_inside = [_dot(dos[i], written[i], _NT) for i in count]
    d_q_in = [_dot(dos[i], ss[i], _NT) for i in count]
    from_out = [_dot(inside[i], dos[i], _TN) for i in count]
    to_state = [_dot(q_ins[i], dos[i], _TN) for i in count]
    k_outs = [x.astype(dtype) for x in k_out]
    # the chain, backwards, a chunk's value heads side by side
    d_states, d_written, d_k_out = ([None] * len(units) for _ in range(3))
    for first in reversed(range(0, len(units), group)):
        mine = range(first, first + group)
        for i in mine:
            d_states[i] = ds_ref[units[i].h]
        ds = [d_states[i].astype(dtype) for i in mine]
        for i, d in zip(mine, ds):
            d_written[i] = from_out[i] + _dot(k_outs[i], d)
        for i, d in zip(mine, ds):
            ds_ref[units[i].h] = units[i].leave * d_states[i] + to_state[i] \
                - _dot(ws[i], d_written[i].astype(dtype), _TN)
            d_k_out[i] = _dot(written[i], d, _NT)
    # the solve, backwards: [dRw | dRu] = T^T [dW | dU], dA = -[.][W | U]^T
    dwr = [x.astype(dtype) for x in d_written]
    d_w = [_dot(dwr[i], ss[i], _NT) for i in count]
    d_rhs = [_exact(_left(t_split[i], axis=0), _right(_split(jnp.concatenate(
        [-d_w[i], d_written[i]], axis=1))), _TN) for i in count]
    d_system = [-_exact(_left(_split(d_rhs[i])), _right(_split(wus[i]), 1),
                        _NT) for i in count]
    dq = dk = None
    for i, u in enumerate(units):
        d_rw, d_ru = d_rhs[i][:, :n], d_rhs[i][:, n:]
        decayed = u.kk * u.below
        through = d_system[i] * u.b * decayed + d_inside[i] * scores[i]
        kept_k = lanes(d_k_out[i] * k_out[i])
        d_g = lanes(through) + lanes(d_rw * k_in[i]) * u.b \
            + lanes(d_q_in[i] * q_in[i]) - kept_k \
            + last * (jnp.sum(kept_k, axis=0, keepdims=True)
                      + u.leave * total(d_states[i] * states[i]))
        dg_ref[u.h, :, u.rows] = _as_row(d_g, row, col) \
            - jnp.sum(through, axis=0, keepdims=True)
        db_ref[u.h, :, u.rows] = _as_row(
            lanes(d_system[i] * decayed) + lanes(d_rw * k_in[i])
            + lanes(d_ru * u.v32), row, col)
        dv_ref[u.rows, u.h * p:(u.h + 1) * p] = (d_ru * u.b).astype(
            dv_ref.dtype)
        d_kk = (d_system[i] * u.b * u.below).astype(dtype)
        d_qk = (d_inside[i] * u.upto).astype(dtype)
        dq_h = d_q_in[i] * u.grow + _dot(d_qk, u.k)
        dk_h = d_rw * (u.b * u.grow) + d_k_out[i] * u.to_end \
            + _dot(d_kk, u.k) + _dot(d_kk, u.k, _TN) + _dot(d_qk, u.q, _TN)
        dq = dq_h if u.h == 0 else dq + dq_h
        dk = dk_h if u.h == 0 else dk + dk_h
        if u.h == group - 1:
            dq_ref[u.rows, :] = dq.astype(dq_ref.dtype)
            dk_ref[u.rows, :] = dk.astype(dk_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "eps", "interpret"))
def backward(q, k, v, beta, g, z, weight, states, inverses, dy, chunk, eps,
             interpret=False):
    """``(dq, dk, dv, dbeta, dg, dz, dweight)`` from ``forward``'s
    operands, what it kept and the cotangent of its ``y`` (B, L, H * P):
    ``dz`` (B, L, H * P) in ``z``'s dtype, the gate's columns alone."""
    bsz, length, h, p = v.shape
    gk, n = k.shape[2], k.shape[3]
    group = h // gk
    operands, step, gate = _operands(q, k, v, beta, g, z, weight, chunk)
    padded = operands[0].shape[1]
    chunks = padded // chunk
    dy = dy.astype(v.dtype)
    if padded != length:
        dy = jnp.pad(dy, ((0, 0), (0, padded - length), (0, 0)))
    blocks = chunks // step
    specs = _specs(chunk, step, group, n, p, lambda i: blocks - 1 - i, gate)
    floats = jax.ShapeDtypeStruct((bsz, h, 1, padded), _F32)
    dq, dk, dv, d_beta, d_run, dz, d_weight = _call(
        functools.partial(_bwd_kernel, chunk=chunk, group=group, n=n, p=p,
                          eps=eps),
        "gdn_bwd_kernel", (bsz, gk, blocks), interpret,
        in_specs=[specs["key"], specs["key"], specs["value"],
                  specs["floats"], specs["floats"], specs["gate"],
                  specs["weight"], specs["chunks"](n, p),
                  specs["chunks"](chunk, chunk), specs["value"]],
        out_specs=[specs["key"], specs["key"], specs["value"],
                   specs["floats"], specs["floats"], specs["value"],
                   specs["sums"]],
        out_shape=[jax.ShapeDtypeStruct(operands[0].shape, q.dtype),
                   jax.ShapeDtypeStruct(operands[1].shape, k.dtype),
                   jax.ShapeDtypeStruct(operands[2].shape, v.dtype),
                   floats, floats,
                   jax.ShapeDtypeStruct(operands[2].shape, z.dtype),
                   jax.ShapeDtypeStruct((bsz, gk, _SUBLANES, p), _F32)],
        scratch_shapes=[pltpu.VMEM((group, n, p), _F32)])(
            *operands, states, inverses, dy)
    # a step's log decay is in every later running sum of its chunk
    d_run = d_run[:, :, 0].transpose(0, 2, 1).reshape(bsz, chunks, chunk, h)
    d_g = jnp.flip(jnp.cumsum(jnp.flip(d_run, 2), axis=2), 2).reshape(
        bsz, padded, h)
    d_beta = d_beta[:, :, 0].transpose(0, 2, 1)
    return (dq[:, :length].reshape(q.shape), dk[:, :length].reshape(k.shape),
            dv[:, :length].reshape(v.shape),
            d_beta[:, :length].astype(beta.dtype),
            d_g[:, :length].astype(g.dtype), dz[:, :length],
            jnp.sum(d_weight, axis=(0, 1, 2)).astype(weight.dtype))
