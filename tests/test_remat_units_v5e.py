"""The step of ``nemotron3-super-train-8k`` with what its units keep
(``ops.remat``), compiled for a v5e that is described and not attached,
at the sizes the cell times: it fits the chip, it multiplies once, it
chooses and sorts once. Nothing runs here: counts by XLA, not times.
(On the pattern of ``tests/bench_harness/test_bench_nemotron_compile.py``;
the topology is described inside a fixture only.)"""
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402

CELL = "nemotron3-super-train-8k"

#: XLA's count for the same step with no recomputation at all
#: (``remat=None``), and with units that keep their input alone (the
#: parent of the PR that brought ``ops.remat``): compiled as below
FLOPS_NO_RECOMPUTATION = 11.93e12
FLOPS_INPUT_ALONE = 13.85e12


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


def _sorts(text, scope):
    """The sort instructions of a compiled program under ``scope``."""
    return [line for line in text.splitlines()
            if re.search(r"\bsort\(", line) and scope in line]


def test_units_keep_what_is_dear_and_the_step_fits(one_chip, no_jax_cache):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import TrainStep
    cell = harness.load_cell(CELL)
    sizes = cell.sizes
    pattern = sizes["hybrid_override_pattern"]
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
    snap = mx.telemetry.snapshot(prefix="remat::")
    kept = {k.rsplit("::", 1)[1]: v["value"] for k, v in snap.items()
            if k.startswith("remat::saved_bytes::")}
    cost = compiled.cost_analysis()
    print(f"{CELL} step, {tokens} tokens: {peak / 1e9:.2f} GB "
          f"({m.argument_size_in_bytes / 1e9:.2f} of state, "
          f"{m.temp_size_in_bytes / 1e9:.2f} of temporaries), "
          f"{sum(kept.values()) / 1e9:.3f} GB kept by {len(kept)} units "
          f"({ {k: round(v / 1e6, 1) for k, v in sorted(kept.items())} } "
          f"MB), {cost['flops'] / 1e12:.2f} TFLOP and "
          f"{cost['bytes accessed'] / 1e9:.1f} GB accessed by XLA's count")
    # it fits one chip, with room for what the runtime reserves beside
    # XLA's count (2.3 GB on the chip: PERF.md, section 4)
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    assert peak < hbm - 3e9, peak
    # every unit keeps something, a Mamba-2 layer the most (its scan),
    # and together no more than the chip has room for
    assert snap["remat::units"]["value"] == len(pattern) == len(kept)
    by_kind = {kind: kept[next(k for k in kept if k.endswith(f"_l{i}_"))]
               for i, kind in enumerate(pattern)}
    assert 0 < by_kind["*"] < by_kind["E"] < by_kind["M"] < 200e6
    assert 1.2e9 < sum(kept.values()) < 1.6e9
    # the matrix products run once forward: XLA's count is the count of
    # the step that recomputes nothing (what is left over it: the
    # attention's scores and weighted sums, 0.1 TFLOP)
    assert cost["flops"] < 1.02 * FLOPS_NO_RECOMPUTATION < FLOPS_INPUT_ALONE
    # one choice and one sort an expert layer, not two
    text = compiled.as_text()
    experts = pattern.count("E")
    assert len(_sorts(text, "mx_moe_dispatch/jit(argsort)")) == experts
    assert len(_sorts(text, "mx_moe_route/top_k")) == experts
