"""The step of ``xing4.0-29b-a4b-train-4k`` compiled for a v5e that is
described and not attached, at the sizes the cell times, and held to what
one chip gives a program; the kernels in it counted by their names, at
least as many as the layers ask for (a later kernel of another name turns
nothing red here): every latent-attention layer's softmax is the fused
forward and backward kernel at 4 heads of 192 / 128, every expert layer's
grouped products are the six grouped-matmul kernels at hidden 3584 and
experts 1024 wide, and no token's 4 x 4 matrix stands as a padded tile of
its own. Nothing runs here, so nothing here is a time or a result. The
topology is described inside a fixture only (one process at a time may
load the TPU's library: the on-chip-measurement guide, section 2)."""
import collections
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402

CELL = "xing4.0-29b-a4b-train-4k"
#: what one v5e gives a program: ``bytes_limit`` of the device's memory
#: statistics (my chip run, PR 41), 15.75 GiB
CHIP_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_jax_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_step_fits_one_v5e_and_its_kernels_are_there_by_name(
        one_chip, no_jax_cache):
    """656.1 M parameters with Adam's moments, 4096 tokens of four streams
    through a dense and four expert layers, recomputation by layer: the
    step's arguments, outputs and temporaries on one described v5e."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attn_kernel, gmm_kernel, seq
    from mxnet_tpu.parallel import TrainStep
    cell = harness.load_cell(CELL)
    sizes = cell.sizes
    net = cell.model._net(sizes)
    net.initialize(mx.init.Zero())
    opt = dict(cell.config["optimizer"])
    step = TrainStep(net, loss="softmax_ce", optimizer=opt.pop("name"),
                     optimizer_params=opt,
                     compute_dtype=cell.config["compute_dtype"],
                     remat="layer")

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pvals = tuple(spec(p.shape) for p in step.param_list)
    state = tuple((spec(p.shape),) * 2 if t else ()
                  for p, t in zip(step.param_list, step._trainable))
    trained = sum(int(jnp.prod(jnp.asarray(p.shape)))
                  for p, t in zip(step.param_list, step._trainable) if t)
    assert trained == 656_126_990
    tokens = sizes["batch"] * sizes["seq_len"]
    step._build_step()
    gauges = {g: mx.telemetry.gauge(g) for g in (
        attn_kernel.GAUGE, attn_kernel.FUSED_BWD_GAUGE, gmm_kernel.GAUGE,
        seq.MHC_GAUGE)}
    for gauge in gauges.values():
        gauge.set(0)
    compiled = step._step_jit.lower(
        pvals, state, spec((sizes["batch"], sizes["seq_len"]), jnp.int32),
        spec((tokens,), jnp.int32), spec((), jnp.uint32),
        spec(())).compile()
    # sites of one shape are one lowered program, which is what a gauge
    # counts: above 0 is what says that the branch was taken
    assert all(v.get() >= 1 for v in gauges.values()), \
        {g: v.get() for g, v in gauges.items()}
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    kept = {k.rsplit("::", 1)[1]: v["value"] for k, v in
            mx.telemetry.snapshot(prefix="remat::saved_bytes::").items()}
    cost = compiled.cost_analysis()
    print(f"xing4.0-29b-a4b step, {tokens} tokens: {peak / 1e9:.2f} GB "
          f"({m.argument_size_in_bytes / 1e9:.2f} of state, "
          f"{m.temp_size_in_bytes / 1e9:.2f} of temporaries), "
          f"{sum(kept.values()) / 1e9:.3f} GB kept by {len(kept)} units "
          f"({ {k: round(v / 1e6, 1) for k, v in sorted(kept.items())} } "
          f"MB), {cost['flops'] / 1e12:.2f} TFLOP and "
          f"{cost['bytes accessed'] / 1e9:.1f} GB accessed by XLA's count")
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    # one program, not what else the process keeps on the device
    assert 0.25 * hbm < peak < 15.0e9 < CHIP_BYTES, peak
    # the state is donated: no second copy of it in the outputs
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    # a unit keeps, of its hyper-connection, the 24-wide product and the
    # mean square (25 floats a token) and the sublayer's output y
    units = sizes["hidden_size"]
    (mlp,) = [v for k, v in kept.items() if k.endswith("_l1_")]
    assert mlp == tokens * (25 * 4 + 4 + units * 2)
    hlo = compiled.as_text()
    calls = collections.Counter(
        name.rsplit(".", 1)[0] for name in re.findall(
            r'%?([\w.\-]+) = [^\n]*?custom_call_target="tpu_custom_call"',
            hlo))
    layers = sizes["num_hidden_layers"]
    expert = layers - sizes["first_k_dense_replace"]
    for kernel in ("attn_fwd_kernel", "attn_bwd_kernel"):
        assert calls[kernel] >= layers, calls
    for side in ("up", "down"):
        for part in ("", "_rows", "_weights"):
            assert calls[f"moe_gmm_{side}{part}_kernel"] >= expert, calls
    assert "ragged-dot" not in hlo
    heads, length = sizes["num_attention_heads"], sizes["seq_len"]
    blk, _ = attn_kernel.block_size(length)
    for scores in (f"f32[{heads},{length},{length}]",
                   f"f32[1,{heads},{length},{length}]",
                   f"f32[{heads},{blk},{blk}]",
                   f"f32[{heads},{blk},{length}]"):
        assert scores not in hlo, scores
    # the maps are computed with the tokens minor: no (tokens, 4, 4) or
    # (tokens, 24) value whose short axes would each pad to a tile
    n = sizes["hc_mult"]
    for padded in (f"f32[{tokens},{n},{n}]", f"f32[1,{tokens},{n},{n}]",
                   f"f32[{tokens},{n * (n + 2)}]{{1,0"):
        assert padded not in hlo, padded
