"""The routed experts' grouped-product kernels (``ops/gmm_kernel.py``)
against ``lax.ragged_dot`` and JAX's own derivative of it, interpreted on
the CPU; the walk they follow; and which form ``pooled_gated_product``
takes: the kernels where the tiling rule takes the shapes and the program
is lowered for a TPU, the ragged products everywhere else, with the gauge
``moe::gmm_kernel_sites`` counting the sites. Nothing here is a time."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import gmm_kernel, seq

import numerics

ROWS, HIDDEN, FF, EXPERTS, TILE = 512, 256, 128, 4, 128

#: rows an expert has in the pool, by what the walk has to get right
SIZES = {
    "a group end inside a tile": (100, 156, 200, 56),
    "group ends on tiles' edges": (128, 256, 0, 128),
    "an expert with no rows": (130, 0, 300, 82),
    "the last expert holds the pool's empty rows": (60, 70, 50, 332),
    "all rows one expert's": (0, 512, 0, 0),
}


def _operands(dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    buf = jax.random.normal(k[0], (ROWS, HIDDEN), dtype)
    w1, w3 = (jax.random.normal(k[i], (EXPERTS, HIDDEN, FF), dtype) * 0.1
              for i in (1, 2))
    w2 = jax.random.normal(k[3], (EXPERTS, FF, HIDDEN), dtype) * 0.1
    cot = jax.random.normal(k[4], (ROWS, HIDDEN), dtype)
    return buf, w1, w3, w2, cot


def _ragged_parts(buf, w1, w3, w2, sizes):
    gate, up, hid = seq._ragged_up(buf, w1, w3, sizes)
    return gate, up, seq._ragged(hid, w2, sizes)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(SIZES))
def test_kernels_are_the_ragged_products_and_their_derivative(case, dtype):
    """``gate``, ``up``, ``out``, ``d_buf``, ``dW1``, ``dW3``, ``dW2``: in
    float32 to 1e-5 of the largest value, in bfloat16 within the rounding
    of one output."""
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    buf, w1, w3, w2, cot = _operands(jnp.dtype(dtype))

    def kernels(buf, w1, w3, w2):
        walk = gmm_kernel.visits(sizes, ROWS, TILE)
        gate, up, hid = gmm_kernel.up(buf, w1, w3, walk, TILE, interpret=True)
        out = gmm_kernel.down(hid, w2, walk, TILE, interpret=True)
        d_gate, d_up, dw2 = gmm_kernel.down_backward(cot, gate, up, w2, walk,
                                                     TILE, interpret=True)
        d_buf, dw1, dw3 = gmm_kernel.up_backward(buf, d_gate, d_up, w1, w3,
                                                 walk, TILE, interpret=True)
        return dict(gate=gate, up=up, out=out, d_buf=d_buf, dW1=dw1, dW3=dw3,
                    dW2=dw2)

    def ragged(buf, w1, w3, w2):
        (gate, up, out), vjp = jax.vjp(
            lambda *a: _ragged_parts(*a, sizes), buf, w1, w3, w2)
        d_buf, dw1, dw3, dw2 = vjp((jnp.zeros_like(gate), jnp.zeros_like(up),
                                    cot))
        return dict(gate=gate, up=up, out=out, d_buf=d_buf, dW1=dw1, dW3=dw3,
                    dW2=dw2)

    got, _ = numerics.traced(kernels, (buf, w1, w3, w2))
    with jax.default_matmul_precision("highest"):
        want, _ = numerics.traced(ragged, (buf, w1, w3, w2))
    numerics.close(got, want, numerics.kernel_tol(
        1e-5 if dtype == "float32" else 2.0 ** -7), same_dtype=True)
    # an expert with no rows gets zeros, not what the memory held
    for e, rows in enumerate(SIZES[case]):
        if rows == 0:
            for dw in ("dW1", "dW3", "dW2"):
                assert not np.asarray(got[dw][e], np.float32).any()


@pytest.mark.parametrize("sizes", list(SIZES.values()) + [
    tuple(int(n) for n in np.diff(np.sort(np.random.RandomState(seed).randint(
        0, ROWS + 1, EXPERTS - 1)), prepend=0, append=ROWS))
    for seed in range(5)])
def test_the_walk_visits_every_row_once_in_the_pool_s_order(sizes):
    """Every row is some visit's own, once; the visits stand in the
    pool's order, a group's together; an empty group has one; what the
    static grid has beyond the routing's need repeats the last visit."""
    group, tile, offsets, count = (np.asarray(t) for t in gmm_kernel.visits(
        jnp.asarray(sizes, jnp.int32), ROWS, TILE))
    n = int(count[0])
    assert group.shape == tile.shape == (ROWS // TILE + EXPERTS - 1,)
    assert EXPERTS <= n <= group.shape[0]
    assert list(offsets) == [0] + list(np.cumsum(sizes))
    owned = np.zeros(ROWS, int)
    for g, t in zip(group[:n], tile[:n]):
        rows = np.arange(t * TILE, (t + 1) * TILE)
        owned[rows[(rows >= offsets[g]) & (rows < offsets[g + 1])]] += 1
    assert (owned == 1).all()
    assert (np.diff(group[:n]) >= 0).all() and (np.diff(tile[:n]) >= 0).all()
    assert sorted(set(group[:n])) == list(range(EXPERTS))
    assert (group[n:] == group[n - 1]).all() and (tile[n:] == tile[n - 1]).all()


def _lowered(ff, platform):
    """The text of ``pooled_gated_product``'s value and gradients lowered
    for ``platform`` at experts ``ff`` wide, and what the gauge counted."""
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    buf = jax.random.normal(k[0], (ROWS, HIDDEN), jnp.bfloat16)
    w1 = w3 = jax.random.normal(k[1], (EXPERTS, HIDDEN, ff), jnp.bfloat16)
    w2 = jax.random.normal(k[2], (EXPERTS, ff, HIDDEN), jnp.bfloat16)
    sizes = jnp.asarray(SIZES["a group end inside a tile"], jnp.int32)

    def loss(buf, w1, w3, w2):
        return jnp.sum(seq.pooled_gated_product(buf, w1, w3, w2, sizes
                                                ).astype(jnp.float32) ** 2)

    mx.telemetry.gauge(gmm_kernel.GAUGE).set(0)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).trace(
        buf, w1, w3, w2).lower(lowering_platforms=(platform,)).as_text()
    return text, mx.telemetry.gauge(gmm_kernel.GAUGE).get()


@pytest.mark.parametrize("ff,platform,sites,calls", [
    (128, "tpu", 1, 6),     # the kernels: two forward, four backward
    (128, "cpu", 0, 0),     # another platform: the ragged products
    (96, "tpu", 0, 0)])     # a width the tiling rule refuses: the same
def test_kernel_sites_follow_the_platform_and_the_tiling_rule(ff, platform,
                                                              sites, calls):
    text, counted = _lowered(ff, platform)
    assert counted == sites
    assert text.count("tpu_custom_call") == calls
    if platform == "tpu":   # for a CPU ``ragged_dot`` lowers to plain products
        assert ("ragged_dot" in text) == (calls == 0)


def test_the_tiling_rule_reads_shapes_alone():
    """Both widths whole lane tiles, the pool whole tiles of rows, the
    blocks under the VMEM budget: the Moonlight cell's shapes are taken,
    experts of twice both widths, whose two matrices would not fit twice,
    are not."""
    assert gmm_kernel.tile_rows(8192, 2048, 1408, jnp.bfloat16) == 256
    assert gmm_kernel.tile_rows(8192, 4096, 2816, jnp.bfloat16) is None
    assert gmm_kernel.tile_rows(8192 + 128, 2048, 1408, jnp.bfloat16) == 128
    assert gmm_kernel.tile_rows(8192 + 64, 2048, 1408, jnp.bfloat16) is None
    assert gmm_kernel.tile_rows(8192, 2048, 1400, jnp.bfloat16) is None
    assert gmm_kernel.tile_rows(8192, 2000, 1408, jnp.bfloat16) is None
    held = gmm_kernel.rows_bytes(256, 2048, 1408, 2)
    assert 25e6 < held < gmm_kernel._BUDGET_BYTES


def test_the_cpu_form_is_the_ragged_products_to_the_bit():
    """Where the tiling rule takes the shapes and the platform is not a
    TPU, value and gradients are those of the three ragged products as
    JAX differentiates them."""
    sizes = jnp.asarray(SIZES["an expert with no rows"], jnp.int32)
    buf, w1, w3, w2, cot = _operands(jnp.bfloat16, seed=3)
    numerics.agree(lambda *a: seq.pooled_gated_product(*a, sizes),
                   lambda *a: _ragged_parts(*a, sizes)[2], (buf, w1, w3, w2),
                   cot, (0, 1, 2, 3), value=numerics.TO_THE_BIT)
