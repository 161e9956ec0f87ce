"""The step of ``moonlight-16b-a3b-train-8k`` compiled for a v5e that is
described and not attached, at the sizes the cell times, and held to one
chip's 16 GB; every latent-attention layer's softmax is the fused
kernels, with no block of float32 scores among the program's values.
Nothing runs here, so nothing here is a time or a result. The topology is
described inside a fixture only (one process at a time may load the TPU's
library: the on-chip-measurement guide, section 2)."""
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

CELL = "moonlight-16b-a3b-train-8k"


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


def test_step_fits_one_v5e_and_every_mla_layer_runs_the_kernels(
        one_chip, no_jax_cache):
    """669 M parameters with Adam's moments, 8192 tokens through a dense
    and five expert layers, recomputation by layer: the step's arguments,
    outputs and temporaries on one described v5e; three Mosaic calls a
    latent-attention layer and no (heads, block, block) float32 value;
    nine ragged products an expert layer."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attn_kernel
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
    layers = sizes["num_hidden_layers"]
    assert trained == {6: 668_890_112, 5: 568_484_352}[layers]
    tokens = sizes["batch"] * sizes["seq_len"]
    step._build_step()
    mx.telemetry.gauge(attn_kernel.GAUGE).set(0)
    compiled = step._step_jit.lower(
        pvals, state, spec((sizes["batch"], sizes["seq_len"]), jnp.int32),
        spec((tokens,), jnp.int32), spec((), jnp.uint32),
        spec(())).compile()
    # the layers' call sites have one shape: one lowered program, which is
    # what the gauge counts (PERF.md, PR 34), called once a layer
    assert mx.telemetry.gauge(attn_kernel.GAUGE).get() == 1
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    kept = {k.rsplit("::", 1)[1]: v["value"] for k, v in
            mx.telemetry.snapshot(prefix="remat::saved_bytes::").items()}
    cost = compiled.cost_analysis()
    print(f"moonlight-16b-a3b step, {tokens} tokens, {layers} layers: "
          f"{peak / 1e9:.2f} GB ({m.argument_size_in_bytes / 1e9:.2f} of "
          f"state, {m.temp_size_in_bytes / 1e9:.2f} of temporaries), "
          f"{sum(kept.values()) / 1e9:.3f} GB kept by {len(kept)} units "
          f"({ {k: round(v / 1e6, 1) for k, v in sorted(kept.items())} } "
          f"MB), {cost['flops'] / 1e12:.2f} TFLOP and "
          f"{cost['bytes accessed'] / 1e9:.1f} GB accessed by XLA's count")
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < peak < 15.0e9, peak
    # the state is donated: no second copy of it in the outputs
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    hlo = compiled.as_text()
    calls = re.findall(r'%?([\w.\-]+) = [^\n]*?'
                       r'custom_call_target="tpu_custom_call"', hlo)
    # the routed experts' grouped products are XLA's own kernel: three
    # forward, and for each of them both gradients, an expert layer
    ragged = [c for c in calls if c.startswith("ragged-dot-none")]
    assert len(ragged) == 9 * (layers - 1), len(ragged)
    calls = [c for c in calls if not c.startswith("ragged-dot")]
    assert len(calls) == 3 * layers, len(calls)
    heads, length = sizes["num_attention_heads"], sizes["seq_len"]
    blk, _ = attn_kernel.block_size(length)
    for scores in (f"f32[{heads},{length},{length}]",
                   f"f32[1,{heads},{length},{length}]",
                   f"f32[{heads},{blk},{blk}]",
                   f"f32[1,{heads},{blk},{blk}]",
                   f"f32[{heads},{blk},{length}]"):
        assert scores not in hlo, scores
    # a head's two parts stay apart: no key or query is padded to 256 or
    # joined to 192 a head (the rotary key would be repeated 16 times)
    for wide in (f"bf16[1,{length},{heads},256]",
                 f"bf16[1,{length},{heads},192]"):
        assert wide not in hlo, wide
