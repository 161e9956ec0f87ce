"""The routed experts' row kernels (``ops/moe_rows_kernel.py``) against the
plain moves, ``jnp.take`` into the buffer and the float32 scatter-add out
of it, and JAX's own derivative of both, interpreted on the CPU; where a
tile of tokens finds its rows; and which form ``_dispatch``,
``_dispatch_pooled`` and ``_combine`` take: the kernels where the tiling
rule takes the shapes and the program is lowered for a TPU, the plain
moves everywhere else, with the gauge ``moe::rows_kernel_sites`` counting
the sites. Nothing here is a time."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import moe_rows_kernel, seq

import numerics

TOKENS, EXPERTS, HELD, TOP_K = 256, 16, 4, 3
IDS = (1, 4, 5, 9)          # the held experts, of 16

#: what a routing has to get right, as ``(buffer rows, expert left empty)``
ROUTINGS = {
    "a pool with room": (384, None),
    "an expert with no rows": (384, 4),
    "pairs beyond the buffer": (128, None),
}


def _routing(empty, seed=0):
    """``(gate_all, chosen_all)`` over 16 experts for 256 tokens: three
    chosen a token, so a token holds none, one, two or all three of its
    pairs among the four held experts; ``empty`` is chosen by nobody."""
    logits = jax.random.normal(jax.random.PRNGKey(seed), (TOKENS, EXPERTS))
    if empty is not None:
        logits = logits.at[:, empty].set(-1e9)
    kth = jax.lax.top_k(logits, TOP_K)[0][:, -1:]
    chosen = logits >= kth
    return jnp.where(chosen, jax.nn.sigmoid(logits), 0.0), chosen


def _moves(slices, rows_in_buffer, chosen, mix):
    """``(rows, gate_all) -> (buffer, routed)``: the rows into the buffer
    (one pool, or a slice an expert), the buffer's rows times ``mix`` back
    by token under their gates."""
    def fn(rows, gate_all):
        buf, token, row_gate, starts = _dispatched(
            slices, rows, gate_all, chosen, rows_in_buffer)
        out_buf = (buf.astype(jnp.float32) * mix.reshape(buf.shape)
                   ).astype(buf.dtype)
        return buf, seq._combine(out_buf, row_gate, token, TOKENS, starts)
    return fn


def _dispatched(slices, rows, gate_all, chosen, rows_in_buffer):
    """``(buffer, token, row_gate, starts)`` of a slice an expert, or of
    one pool."""
    if slices:
        buf, token, row_gate, _, _, cap = seq._dispatch(
            rows, gate_all, chosen, IDS, rows_in_buffer)
        return buf, token, row_gate, seq._slice_starts(HELD, cap)
    buf, token, row_gate, _, _, sizes = seq._dispatch_pooled(
        rows, gate_all, chosen, IDS, rows_in_buffer)
    return buf, token, row_gate, jnp.cumsum(sizes) - sizes


@pytest.fixture()
def kernels_here(monkeypatch):
    """``ops.seq`` takes its TPU branches on this backend, the row kernels
    interpreted."""
    monkeypatch.setattr(seq, "lax", numerics.LoweredForATpu())
    for name in ("rows_by_index", "rows_by_token"):
        monkeypatch.setattr(moe_rows_kernel, name, functools.partial(
            getattr(moe_rows_kernel, name), interpret=True))


def _both(fn, args, cot, monkeypatch_kernels):
    """``fn``'s value and gradients by the plain moves, then by the
    kernels."""
    plain = numerics.traced(fn, args, cot, (0, 1))
    monkeypatch_kernels()
    return numerics.traced(fn, args, cot, (0, 1)), plain


@pytest.mark.parametrize("dtype,width", [
    ("float32", 1024), ("bfloat16", 1024), ("bfloat16", 2048),
    ("bfloat16", 3584)])
@pytest.mark.parametrize("slices", [False, True], ids=["pool", "slices"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_kernels_are_the_plain_moves_and_their_derivative(
        routing, slices, dtype, width, request):
    """The buffer to the bit (a row is moved, not computed); the routed
    sum, the rows' gradient and the gates' within the rounding of one
    output (the kernels add a token's rows in float32 in the pool's order,
    the plain moves in the order XLA's scatter takes, and the rows'
    gradient in the compute dtype)."""
    rows_in_buffer, empty = ROUTINGS[routing]
    gate_all, chosen = _routing(empty)
    dtype = jnp.dtype(dtype)
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    rows = jax.random.normal(k[0], (TOKENS, width), dtype)
    mix = jax.random.normal(k[1], (rows_in_buffer, width))
    cot = (jax.random.normal(k[2], (rows_in_buffer, width), dtype).reshape(
               (HELD, -1, width) if slices else (-1, width)),
           jax.random.normal(k[3], (TOKENS, width), dtype))
    fn = _moves(slices, rows_in_buffer, chosen, mix)
    assert moe_rows_kernel.takes(rows_in_buffer, TOKENS, width, dtype) == 128
    (out, grads), (want, want_grads) = _both(
        fn, (rows, gate_all), cot,
        lambda: request.getfixturevalue("kernels_here"))
    tol = numerics.kernel_tol(1e-5 if dtype == jnp.float32 else 2 ** -7)
    numerics.close(out[0], want[0], numerics.TO_THE_BIT, "buffer",
                   same_dtype=True)
    numerics.close(out[1], want[1], tol, "routed", same_dtype=True)
    numerics.close(grads, want_grads, tol, "gradient", same_dtype=True)
    held = np.asarray(chosen)[:, list(IDS)].sum(1)
    assert {0, 1, 2}.issubset(set(held.tolist()))   # tokens with no pair too
    if routing == "pairs beyond the buffer":
        assert held.sum() > rows_in_buffer


def test_a_token_s_rows_are_added_in_float32(kernels_here):
    """1 + 2^-8 + 2^-8 is 1 + 2^-7 in float32 and rounds to itself; a
    running sum in bfloat16 rounds each 2^-8 away (to even) and stays 1.
    Both by-token moves: the routed sum under gates of one, and the
    dispatch's gradient."""
    width, dtype = 1024, jnp.bfloat16
    chosen = jnp.zeros((TOKENS, EXPERTS), bool).at[:, IDS[:3]].set(True)
    gate_all = chosen.astype(jnp.float32)
    # token t's rows lie in the pool at t, 256 + t and 512 + t
    parts = jnp.asarray([1.0, 2.0 ** -8, 2.0 ** -8], dtype)
    cot_buf = jnp.repeat(parts, TOKENS)[:, None] * jnp.ones((1, width), dtype)
    rows = jnp.ones((TOKENS, width), dtype)

    def fn(rows, mix):
        buf, token, row_gate, starts = _dispatched(
            False, rows, gate_all, chosen, 3 * TOKENS)
        return buf, seq._combine(buf * mix, row_gate, token, TOKENS, starts)

    (_, routed), (d_rows, _) = numerics.traced(
        fn, (rows, cot_buf),
        (cot_buf, jnp.zeros((TOKENS, width), dtype)), (0, 1))
    exact = np.float32(1 + 2.0 ** -7)
    assert float(jnp.asarray(exact, dtype)) == exact
    np.testing.assert_array_equal(np.asarray(routed, np.float32), exact)
    np.testing.assert_array_equal(np.asarray(d_rows, np.float32), exact)
    running = parts[0]
    for p in parts[1:]:
        running = (running + p).astype(dtype)
    assert float(running) == 1.0


@pytest.mark.parametrize("routing", ["an expert with no rows",
                                     "pairs beyond the buffer"])
@pytest.mark.parametrize("slices", [False, True], ids=["pool", "slices"])
def test_a_tile_of_tokens_finds_its_rows(slices, routing):
    """Every row that holds a pair lies in exactly one stretch, its
    token's tile's and its expert's; a row that holds none lies in no
    stretch."""
    tile = 128
    rows_in_buffer, empty = ROUTINGS[routing]
    gate_all, chosen = _routing(empty, seed=3)
    _, token, _, starts = _dispatched(slices, jnp.zeros((TOKENS, 8)),
                                      gate_all, chosen, rows_in_buffer)
    token = np.asarray(token).reshape(-1)
    at = np.asarray(moe_rows_kernel.stretches(
        jnp.asarray(token), TOKENS, starts, tile)).reshape(-1, HELD)
    first = np.append(np.asarray(starts), rows_in_buffer)
    owner = np.full(token.shape, -1)
    for i in range(TOKENS // tile):
        for r in range(HELD):
            stretch = slice(at[i, r], at[i + 1, r])
            assert first[r] <= at[i, r] <= at[i + 1, r] <= first[r + 1]
            assert (owner[stretch] == -1).all()
            owner[stretch] = i
    np.testing.assert_array_equal(
        owner, np.where(token < TOKENS, token // tile, -1))


def _lowered(width, tokens, platform):
    """The text of both moves' value and gradients lowered for
    ``platform``, and what the gauge counted."""
    gate_all, chosen = _routing(None)
    gate_all, chosen = gate_all[:tokens], chosen[:tokens]
    rows = jnp.ones((tokens, width), jnp.bfloat16)

    def loss(rows, gate_all):
        buf, token, row_gate, starts = _dispatched(
            False, rows, gate_all, chosen, 128)
        return jnp.sum(seq._combine(buf, row_gate, token, tokens, starts
                                    ).astype(jnp.float32) ** 2)

    mx.telemetry.gauge(moe_rows_kernel.GAUGE).set(0)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
        rows, gate_all).lower(lowering_platforms=(platform,)).as_text()
    return text, mx.telemetry.gauge(moe_rows_kernel.GAUGE).get()


def _scatters(text):
    return text.count('"stablehlo.scatter"(')


@pytest.mark.parametrize("width,tokens,platform,sites,calls", [
    (1024, 256, "tpu", 1, 3),   # the gather forward, the sum by token twice
    (1024, 256, "cpu", 0, 0),   # another platform: the plain moves
    (1000, 256, "tpu", 0, 0),   # a width the tiling rule refuses: the same
    (1024, 200, "tpu", 0, 0)])  # tokens that are no whole tiles: the same
def test_kernel_sites_follow_the_platform_and_the_tiling_rule(
        width, tokens, platform, sites, calls):
    text, counted = _lowered(width, tokens, platform)
    assert counted == sites
    assert text.count("tpu_custom_call") == calls
    # the two scatter-adds of rows are gone (what stays scatters numbers:
    # the gates' gradient and the last expert's size)
    plain, _ = _lowered(width, tokens, "cpu")
    assert _scatters(plain) - _scatters(text) == (2 if calls else 0)
    for name, least in (("moe_rows_by_index_kernel", 1),
                        ("moe_rows_by_token_kernel", 2)):
        assert text.count(name) >= (least if calls else 0)


def test_the_tiling_rule_reads_shapes_alone():
    """The width whole lane tiles, pool and tokens whole tiles of rows,
    the larger source whole in VMEM: the five expert cells' shapes are
    taken (the LFM2 cell's 16,384 tokens of 2048 are 64 MiB there), twice
    those tokens are not."""
    bf16 = jnp.bfloat16
    for pool, tokens, width in ((12288, 16384, 2048), (8192, 8192, 2048),
                                (7680, 8192, 2048), (3072, 4096, 3584),
                                (4096, 8192, 1024)):
        assert moe_rows_kernel.takes(pool, tokens, width, bf16) == 256
    assert moe_rows_kernel.takes(12288, 32768, 2048, bf16) is None
    assert moe_rows_kernel.takes(12288, 16384, 2048, jnp.float32) is None
    assert moe_rows_kernel.takes(4096 + 128, 8192, 1024, bf16) == 128
    assert moe_rows_kernel.takes(4096 + 64, 8192, 1024, bf16) is None
    assert moe_rows_kernel.takes(4096, 8192, 1000, bf16) is None
    assert moe_rows_kernel.takes(32768, 1024, 128, bf16) is None  # scalars
    # 3584 is 28 lane tiles, padded to 32 sublanes in the scratch
    assert moe_rows_kernel.resident_bytes(4096, 3584, 2) == 4096 * 4096 * 2


def test_the_cpu_form_is_the_plain_moves_to_the_bit():
    """Where the tiling rule takes the shapes and the platform is not a
    TPU, value and gradients are those of ``jnp.take`` and the float32
    scatter-add as JAX differentiates them."""
    gate_all, chosen = _routing(None)
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    rows = jax.random.normal(k[0], (TOKENS, 1024), jnp.bfloat16)
    mix = jax.random.normal(k[1], (256, 1024))
    cot = (jax.random.normal(k[2], (256, 1024), jnp.bfloat16),
           jax.random.normal(k[3], (TOKENS, 1024), jnp.bfloat16))

    def plain(rows, gate_all):
        ids = jnp.asarray(IDS)
        order = jnp.argsort(jnp.logical_not(chosen[:, ids].T.reshape(-1)),
                            stable=True)[:256]
        held = jnp.arange(256) < jnp.sum(chosen[:, ids])
        token = jnp.where(held, order % TOKENS, TOKENS)
        buf = jnp.take(rows, token, axis=0, mode="fill", fill_value=0)
        row_gate = jnp.take(gate_all[:, ids].T.reshape(-1),
                            jnp.where(held, order, HELD * TOKENS),
                            mode="fill", fill_value=0)
        weighted = (buf.astype(jnp.float32) * mix).astype(buf.dtype
                                                          ).astype(jnp.float32)
        return buf, jnp.zeros((TOKENS, 1024), jnp.float32).at[token].add(
            weighted * row_gate[:, None], mode="drop").astype(buf.dtype)

    numerics.agree(_moves(False, 256, chosen, mix), plain, (rows, gate_all),
                   cot, (0, 1), value=numerics.TO_THE_BIT, same_dtype=True)
