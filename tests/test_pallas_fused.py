"""Correctness of the fused BN-apply(+ReLU)+matmul Pallas kernels
(mxnet_tpu/ops/pallas_fused.py). Runs the real kernels on TPU and
interpret mode elsewhere; the graph-level rewrite that routes BN→ReLU→1×1-conv
subgraphs onto them is covered by tests/test_fusion_pass.py."""
import numpy as np
import pytest


def _inputs(m=512, k=64, n=256):
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, k).astype(np.float32))
    w = jnp.asarray(rng.randn(k, n).astype(np.float32) * 0.1)
    scale = jnp.asarray(rng.rand(k).astype(np.float32) + 0.5)
    shift = jnp.asarray(rng.randn(k).astype(np.float32) * 0.1)
    return x, w, scale, shift


def unfused(x, w, scale, shift):
    """The plain ``jax.numpy`` reference the kernels are held to."""
    import jax.numpy as jnp
    xhat = jnp.maximum(x * scale + shift, 0.0).astype(x.dtype)
    return jnp.dot(xhat, w, preferred_element_type=jnp.float32).astype(
        x.dtype)


def test_bn_relu_matmul_matches_unfused():
    import jax
    from jax.experimental import pallas as pl
    from mxnet_tpu.ops.pallas_fused import _make_kernel, interpret_mode

    m, k, n = 512, 64, 256
    x, w, scale, shift = _inputs(m, k, n)
    bm, bn = 256, 128
    out = pl.pallas_call(
        _make_kernel(relu=True),
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret_mode(),
    )(x, w, scale.reshape(1, k), shift.reshape(1, k))
    ref = unfused(x, w, scale, shift)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bn_relu_matmul_api_and_grad():
    """The promoted public API: auto tile selection, the custom VJP's
    gradients against autodiff of the unfused expression."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_fused import bn_relu_matmul

    x, w, scale, shift = _inputs()
    out = bn_relu_matmul(x, w, scale, shift)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(unfused(x, w, scale, shift)),
                               rtol=2e-5, atol=2e-5)

    def loss_f(*a):
        return jnp.sum(bn_relu_matmul(*a) ** 2)

    def loss_u(*a):
        return jnp.sum(unfused(*a).astype(jnp.float32) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2, 3))(x, w, scale, shift)
    gu = jax.grad(loss_u, argnums=(0, 1, 2, 3))(x, w, scale, shift)
    for name, a, b in zip(("x", "w", "scale", "shift"), gf, gu):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"grad {name}")


def test_bn_relu_matmul_rejects_bad_tiles():
    from mxnet_tpu.ops.pallas_fused import bn_relu_matmul
    x, w, scale, shift = _inputs()
    with pytest.raises(ValueError, match="M % bm"):
        bn_relu_matmul(x, w, scale, shift, bm=100, bn=128)


def test_nchw_kernel_tiled_interpret_matches_reference():
    """The NCHW-native tiled kernel (the TPU lowering of the graph op),
    exercised with a real grid in interpret mode, against the plain
    composition."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from mxnet_tpu.ops.pallas_fused import (_make_nchw_kernel,
                                            interpret_mode,
                                            select_conv_tiles)

    B, C, H, W, O = 2, 8, 4, 8, 16
    s = H * W
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, C, H, W).astype(np.float32))
    w = jnp.asarray(rng.randn(O, C).astype(np.float32) * 0.1)
    scale = jnp.asarray(rng.rand(C).astype(np.float32) + 0.5)
    shift = jnp.asarray(rng.randn(C).astype(np.float32) * 0.1)
    bo, bs = select_conv_tiles(O, s, C)
    assert (bo, bs) == (16, 32)
    out = pl.pallas_call(
        _make_nchw_kernel(relu=True),
        grid=(B, O // bo, s // bs),
        in_specs=[
            pl.BlockSpec((bo, C), lambda g, i, j: (i, 0)),
            pl.BlockSpec((1, C, bs), lambda g, i, j: (g, 0, j)),
            pl.BlockSpec((C, 1), lambda g, i, j: (0, 0)),
            pl.BlockSpec((C, 1), lambda g, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bo, bs), lambda g, i, j: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, O, s), x.dtype),
        interpret=interpret_mode(),
    )(w, x.reshape(B, C, s), scale.reshape(C, 1), shift.reshape(C, 1))
    ref = jnp.einsum(
        "oc,bcs->bos", w,
        jnp.maximum(x.reshape(B, C, s) * scale[:, None]
                    + shift[:, None], 0.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _mosaic_legal(block, full, unit):
    """The block-dimension rule of the Pallas TPU lowering: a multiple
    of the native tile extent, or the whole array dimension."""
    return block == full or (block % unit == 0 and full % block == 0)


@pytest.mark.parametrize("dtype,sublane", [("float32", 8),
                                           ("bfloat16", 16)])
def test_select_conv_tiles_returns_only_mosaic_legal_blocks(dtype, sublane):
    """Every ResNet-50 stage (56², 28², 14², 7²) against every channel
    width of the model, both directions of the bottleneck: the selector
    answers with a block Mosaic accepts for the dtype, or None."""
    from mxnet_tpu.ops.pallas_fused import select_conv_tiles
    widths = (64, 128, 256, 512, 1024, 2048)
    answered = 0
    for spatial in (3136, 784, 196, 49):
        for n_in in widths:
            for n_out in widths:
                tiles = select_conv_tiles(n_out, spatial, n_in, dtype)
                if tiles is None:
                    continue
                answered += 1
                bo, bs = tiles
                assert _mosaic_legal(bs, spatial, 128), \
                    (dtype, spatial, n_in, n_out, tiles)
                assert _mosaic_legal(bo, n_out, sublane), \
                    (dtype, spatial, n_in, n_out, tiles)
    assert answered, "no shape tiled at all"


def test_select_conv_tiles_bails_what_vmem_cannot_hold():
    """A whole-row block that does not fit the VMEM budget is a None
    from the selector (the pass's bail-out), not a compile error."""
    from mxnet_tpu.ops.pallas_fused import (conv_tile_failure,
                                            select_conv_tiles)
    # 224² = 1024 * 49 tiles by 1024 lanes; 225² is odd, so its row
    # must be taken whole, and 512 channels of it do not fit
    assert select_conv_tiles(64, 224 * 224, 64) is not None
    assert select_conv_tiles(512, 225 * 225, 512) is None
    assert "VMEM" in conv_tile_failure(512, 225 * 225, 512)
    assert "divisible by 8" in conv_tile_failure(12, 49, 64)
    assert select_conv_tiles(12, 49, 64) is None


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_nchw_kernel_compiles_at_resnet50_shapes(dtype):
    """Mosaic compiles and runs the graph op's kernel at every distinct
    BN→ReLU→1×1-conv shape of ResNet-50 at 224² (both directions of the
    bottleneck in all four stages), and the result matches the plain
    composition. Chip only: off-TPU the op never takes this kernel
    (``MXTPU_TEST_PLATFORM=tpu`` runs it where it means something)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_fused import bn_relu_conv_nchw, interpret_mode
    if interpret_mode():
        pytest.skip("needs a TPU backend: the tiled kernel is Mosaic-only")
    rng = np.random.RandomState(0)
    # the MXU multiplies f32 operands in bf16 passes at default
    # precision, inside Mosaic as in XLA: f32 is not tighter than bf16
    # here against a HIGHEST-precision reference (measured 2.7e-3)
    tol = 2e-2
    for c, hw, o in [(256, 56, 64), (64, 56, 256), (512, 28, 128),
                     (128, 28, 512), (1024, 14, 256), (256, 14, 1024),
                     (2048, 7, 512), (512, 7, 2048)]:
        x = jnp.asarray(rng.randn(4, c, hw, hw), dtype)
        w = jnp.asarray(rng.randn(o, c) / np.sqrt(c), dtype)
        scale = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
        shift = jnp.asarray(rng.randn(c) * 0.1, jnp.float32)
        out, xhat = jax.jit(bn_relu_conv_nchw)(x, w, scale, shift)
        assert xhat is None and out.shape == (4, o, hw, hw)
        z = jnp.maximum(x.astype(jnp.float32) * scale[:, None, None]
                        + shift[:, None, None], 0.0).astype(dtype)
        ref = jnp.einsum("oc,bchw->bohw", w, z,
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
                    / jnp.max(jnp.abs(ref)))
        assert err < tol, (dtype, c, hw, o, err)
