"""The step of ``qwen3-next-80b-a3b-train-8k`` compiled for a v5e that is
described and not attached, at the sizes the cell times, and held to what
one chip gives a program; the kernels in it counted by their names: the
gated attention's softmax is the fused forward kernel and the two
by-side backward kernels (a group of eight heads of 256 at 8192 rows is
past what the fused backward holds), every expert layer's grouped
products are the six grouped-matmul kernels, and no block of float32
scores is among the program's values. Nothing runs here, so nothing here
is a time or a result. The topology is described inside a fixture only
(one process at a time may load the TPU's library: the
on-chip-measurement guide, section 2)."""
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

CELL = "qwen3-next-80b-a3b-train-8k"
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
    """625.7 M parameters with Adam's moments, 8192 tokens through three
    delta-rule layers, one gated-attention layer and four expert layers,
    recomputation by layer: the step's arguments, outputs and temporaries
    on one described v5e."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attn_kernel, gmm_kernel
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
    assert trained == 625_667_136
    tokens = sizes["batch"] * sizes["seq_len"]
    step._build_step()
    gauges = {g: mx.telemetry.gauge(g) for g in (
        attn_kernel.GAUGE, attn_kernel.FUSED_BWD_GAUGE, gmm_kernel.GAUGE)}
    for gauge in gauges.values():
        gauge.set(0)
    compiled = step._step_jit.lower(
        pvals, state, spec((sizes["batch"], sizes["seq_len"]), jnp.int32),
        spec((tokens,), jnp.int32), spec((), jnp.uint32),
        spec(())).compile()
    # one attention site, whose backward is not the fused kernel; the four
    # expert layers have one shape: one lowered program, called four times
    assert {g: v.get() for g, v in gauges.items()} == {
        attn_kernel.GAUGE: 1, attn_kernel.FUSED_BWD_GAUGE: 0,
        gmm_kernel.GAUGE: 1}
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    kept = {k.rsplit("::", 1)[1]: v["value"] for k, v in
            mx.telemetry.snapshot(prefix="remat::saved_bytes::").items()}
    print(f"qwen3-next-80b-a3b step, {tokens} tokens: {peak / 1e9:.2f} GB "
          f"({m.argument_size_in_bytes / 1e9:.2f} of state, "
          f"{m.temp_size_in_bytes / 1e9:.2f} of temporaries), "
          f"{sum(kept.values()) / 1e9:.3f} GB kept by {len(kept)} units "
          f"({ {k: round(v / 1e6, 1) for k, v in sorted(kept.items())} } MB)")
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    # one program, not what else the process keeps on the device (the
    # seeded weights beside the net's copy at set-up: 15.69 GB in all on
    # the chip, my chip run, PR 41)
    assert 0.25 * hbm < peak < 14.5e9 < CHIP_BYTES, peak
    # the state is donated: no second copy of it in the outputs
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    # a delta-rule unit keeps both input products and the gated norm's
    # statistics, not the 8192-wide convolution nor anything of the rule
    rows = tokens * 2
    (first,) = [v for k, v in kept.items() if k.endswith("_l0_")]
    assert first == rows * (12288 + 64) + tokens * 32 * 4 + tokens * 4
    hlo = compiled.as_text()
    calls = collections.Counter(
        name.rsplit(".", 1)[0] for name in re.findall(
            r'%?([\w.\-]+) = [^\n]*?custom_call_target="tpu_custom_call"',
            hlo))
    layers = sizes["num_hidden_layers"]
    assert calls == {
        "attn_fwd_kernel": 1, "attn_bwd_dq_kernel": 1,
        "attn_bwd_dkv_kernel": 1,
        **{f"moe_gmm_{side}{part}_kernel": layers
           for side in ("up", "down") for part in ("", "_rows", "_weights")}}
    assert "ragged-dot" not in hlo
    heads, length = sizes["num_attention_heads"], sizes["seq_len"]
    blk, _ = attn_kernel.block_size(length)
    for scores in (f"f32[{heads},{length},{length}]",
                   f"f32[1,{heads},{length},{length}]",
                   f"f32[{heads},{blk},{blk}]",
                   f"f32[1,{heads},{blk},{blk}]",
                   f"f32[{heads},{blk},{length}]"):
        assert scores not in hlo, scores
    # the delta rule is chained by scans: each linear-attention layer's
    # forward, its recomputation and its backward
    linear = layers - layers // sizes["full_attention_interval"]
    assert len(re.findall(r" while\(", hlo)) == 3 * linear
