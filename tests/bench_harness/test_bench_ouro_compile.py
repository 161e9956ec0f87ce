"""The step of ``ouro-2.6b-train-4k`` compiled for a v5e that is
described and not attached, at the sizes the cell times, and held to one
chip's 16 GB. Nothing runs here, so nothing here is a time or a result.
The topology is described inside a fixture only (one process at a time
may load the TPU's library: the on-chip-measurement guide, section 2)."""
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

CELL = "ouro-2.6b-train-4k"


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


def test_step_fits_one_v5e_and_holds_one_copy_of_the_stack(one_chip,
                                                           no_jax_cache):
    """407 M parameters with Adam's moments, 4096 tokens through 4 layers
    4 times, recomputation by layer: the step's arguments, outputs and
    temporaries on one described v5e, and the stack's matrix products
    once forward and once backward in the program's text. Nothing
    runs."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import TrainStep, exit_weighted_loss
    cell = harness.load_cell(CELL)
    sizes = cell.sizes
    net = cell.model._net(sizes)
    net.initialize(mx.init.Zero())
    opt = dict(cell.config["optimizer"])
    step = TrainStep(net, loss=exit_weighted_loss(sizes["exit_entropy_beta"]),
                     optimizer=opt.pop("name"), optimizer_params=opt,
                     compute_dtype=cell.config["compute_dtype"],
                     remat="layer")

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pvals = tuple(spec(p.shape) for p in step.param_list)
    state = tuple((spec(p.shape),) * 2 if t else ()
                  for p, t in zip(step.param_list, step._trainable))
    tokens = sizes["batch"] * sizes["seq_len"]
    step._build_step()
    lowered = step._step_jit.lower(
        pvals, state, spec((sizes["batch"], sizes["seq_len"]), jnp.int32),
        spec((tokens,), jnp.int32), spec((), jnp.uint32), spec(()))
    # one body for the four passes: each layer's five products (q k v, W_o,
    # gate and up, W_down; the block's two for scores and sums aside) are
    # in the lowered text once a layer, not once a layer and pass
    assert net.stack.body_traces == 1
    text = lowered.as_text()
    assert len(re.findall(r"stablehlo\.while", text)) >= 2
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"ouro-2.6b step, {tokens} tokens: {peak / 1e9:.2f} GB "
          f"({m.argument_size_in_bytes / 1e9:.2f} of state, "
          f"{m.temp_size_in_bytes / 1e9:.2f} of temporaries)")
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < peak < 14.5e9, peak
    # the state is donated: no second copy of it in the outputs
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    # an exit's float32 logits exist one exit at a time, never stacked
    hlo = compiled.as_text()
    assert "f32[4096,49152]" in hlo and "f32[4,4096,49152]" not in hlo
