"""The step of ``nemotron3-super-train-8k`` compiled for a v5e that is
described and not attached, at the sizes the cell times, and held to one
chip's 16 GB. Nothing runs here, so nothing here is a time or a result.
The topology is described inside a fixture only (one process at a time
may load the TPU's library: the on-chip-measurement guide, section 2)."""
import os
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402

CELL = "nemotron3-super-train-8k"


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


def test_step_fits_one_v5e_at_the_timed_sizes(one_chip, no_jax_cache):
    """508 M parameters with Adam's moments (16 B a parameter with the
    gradient), 8192 tokens, recomputation by layer: the step's arguments,
    outputs and temporaries on one described v5e. Nothing runs."""
    import mxnet_tpu as mx
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
    tokens = sizes["batch"] * sizes["seq_len"]
    step._build_step()
    compiled = step._step_jit.lower(
        pvals, state, spec((sizes["batch"], sizes["seq_len"]), jnp.int32),
        spec((tokens,), jnp.int32), spec((), jnp.uint32),
        spec(())).compile()
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"nemotron3-super step, {tokens} tokens: {peak / 1e9:.2f} GB "
          f"({m.argument_size_in_bytes / 1e9:.2f} of state, "
          f"{m.temp_size_in_bytes / 1e9:.2f} of temporaries)")
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < peak < hbm, peak
    # the state is donated: no second copy of it in the outputs
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    # the grouped product is over the whole static buffer
    text = compiled.as_text()
    assert "bf16[8,512,2688]" in text and "bf16[8,512,1024]" in text
