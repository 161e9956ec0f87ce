"""Rehearsal compiles: the two training steps of the benchmark, at the
sizes the cells time, compiled for a v5e that is described and not
attached, and held to one chip's 16 GB.

The only file that describes the topology, and only inside a fixture
(one process at a time may load the TPU's library: see the
on-chip-measurement guide, section 2). Nothing runs here, so nothing
here is a time or a result; the step is traced on the CPU backend, so
the program's backend-dependent choices are the CPU's (no Pallas pass:
the same as the v5e's default, where the bytes gate rejects both).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402

HBM_BYTES = harness.peaks_for("TPU v5 lite")["hbm_bytes"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_jax_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _peak_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_resnet50_fit_step_fits_one_v5e(one_chip, no_jax_cache):
    cell = harness.load_cell("resnet50-train")
    sizes = cell.sizes
    module = cell.model.build(cell.config, sizes, "fit",
                              cell.model.make_weights(sizes, 0))
    opt = dict(cell.config["optimizer"])
    module.init_optimizer(kvstore=None, optimizer=opt.pop("name"),
                          optimizer_params=opt)
    fused = module._fused
    fused._build()
    img = (sizes["batch"], 3, sizes["image"], sizes["image"])
    feed = {"data": jnp.zeros(img, jnp.float32),
            "softmax_label": jnp.zeros((sizes["batch"],), jnp.float32)}
    args = fused._state_args() + (
        tuple(feed[n] for n in fused.input_names), fused._t_dev,
        jnp.asarray(0.01, jnp.float32), fused._base_key)
    compiled = fused._step_jit.lower(*_abstract(args, one_chip)).compile()
    peak = _peak_bytes(compiled)
    print(f"resnet50 fit step, batch {sizes['batch']}: {peak / 1e9:.2f} GB")
    # the ring's two staged batches and the outputs live beside the step
    staged = 2 * int(np.prod(img)) * 4
    assert peak + staged < HBM_BYTES, (peak, staged)


def test_lstm_lm_step_fits_one_v5e(one_chip, no_jax_cache):
    cell = harness.load_cell("lstm-lm-train")
    sizes = cell.sizes
    system = cell.model.build(cell.config, sizes, "step",
                              cell.model.make_weights(sizes, 0))
    step = system.step
    step._init_state()
    step._build_step()
    x = jnp.zeros((sizes["batch"], sizes["bptt"]), jnp.int32)
    y = jnp.zeros((sizes["batch"] * sizes["bptt"],), jnp.int32)
    args = (step._pvals, step._opt_state, x, y, step._t_dev,
            jnp.asarray(0.1, jnp.float32))
    compiled = step._step_jit.lower(*_abstract(args, one_chip)).compile()
    peak = _peak_bytes(compiled)
    print(f"lstm-lm step, batch {sizes['batch']}: {peak / 1e9:.2f} GB")
    assert peak < HBM_BYTES, peak
