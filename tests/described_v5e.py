"""A cell's training step compiled for a v5e that is described and not
attached, at the sizes the cell times: the one place of ``tests/`` that
describes the chip, builds the step and reads a compiled program's bytes
and Mosaic calls. Nothing runs there, so nothing read off it is a time or
a result. One process at a time may load the TPU's library (the
on-chip-measurement guide, section 2): the topology is described where a
test asks for it, never at import."""
import contextlib
import functools
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402

#: what one v5e gives a program: ``bytes_limit`` of the device's memory
#: statistics (a chip run of PR 41), 15.75 GiB
CHIP_BYTES = 16_909_336_064


@functools.cache
def chip():
    """The sharding of one chip of a described ``v5e:2x2``; the test that
    asks is skipped where no such topology can be described."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def jax_cache_off():
    """JAX's compile cache off while a program is compiled for the
    described chip, and as it was after."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip():
    return chip()


@pytest.fixture()
def no_jax_cache():
    with jax_cache_off():
        yield


def peak_bytes(compiled):
    """Arguments, outputs and temporaries of a compiled program, what is
    aliased counted once."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def custom_calls(hlo):
    """``{instruction name: kernel name}`` of a compiled program's Mosaic
    calls."""
    return {name: name.rsplit(".", 1)[0] for name in re.findall(
        r'%?([\w.\-]+) = [^\n]*?custom_call_target="tpu_custom_call"', hlo)}


#: the routed experts' row kernels (``ops/moe_rows_kernel.py``)
ROW_KERNELS = ("moe_rows_by_index_kernel", "moe_rows_by_token_kernel")


def row_kernels_stand(step, layers, width):
    """The rows' way into the experts' buffer and back is the two row
    kernels in ``step``, ``layers`` expert layers ``width`` wide:
    ``rows_by_index`` once a layer under ``mx_moe_dispatch`` (the gather
    forward), ``rows_by_token`` once under ``mx_moe_combine`` (the sum
    forward; the unit's backward does not form the combine again, nothing
    reads it there) and once under ``mx_moe_dispatch`` (the gather's
    backward), no scatter of rows under either scope, no float32 value of
    all the tokens' rows written by a scatter anywhere, and the gauge
    reads what ``moe_rows_kernel_sites.train`` will."""
    import collections
    from mxnet_tpu.ops import moe_rows_kernel
    # like layers share one lowered program: the gauge counts programs
    assert step.gauges[moe_rows_kernel.GAUGE] == 1, step.gauges
    under = collections.Counter(
        (k, re.search(r"(^|/)(mx_moe_\w+)$", step.paths[i]).group(2))
        for i, k in step.calls.items() if k in ROW_KERNELS)
    assert under == {(ROW_KERNELS[0], "mx_moe_dispatch"): layers,
                     (ROW_KERNELS[1], "mx_moe_dispatch"): layers,
                     (ROW_KERNELS[1], "mx_moe_combine"): layers}, under
    tokens = step.sizes["batch"] * step.sizes["seq_len"]
    for line in step.text.splitlines():
        found = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                         r"scatter\(", line)
        if not found:
            continue
        name, dtype, shape = found.groups()
        assert (dtype, shape) != ("f32", f"{tokens},{width}"), line[:200]
        if re.search(r"(^|/)mx_moe_(dispatch|combine)$",
                     step.paths.get(name, "")):
            assert not shape.endswith(f",{width}"), line[:200]


class Step:
    """A cell's step compiled for the described chip: the cell and its
    timed sizes, the compiled program, every kernel gauge as read at that
    lowering and the bytes each recomputation unit keeps; and, read once
    each (the text of a whole step is tens of megabytes), the program's
    text, its Mosaic calls and its instructions' scope paths."""

    def __init__(self, cell, compiled, gauges, kept):
        self.cell, self.sizes = cell, cell.sizes
        self.compiled, self.gauges, self.kept = compiled, gauges, kept

    @functools.cached_property
    def text(self):
        return self.compiled.as_text()

    @functools.cached_property
    def calls(self):
        """``{instruction name: kernel name}`` of the Mosaic calls."""
        return custom_calls(self.text)

    @functools.cached_property
    def paths(self):
        """``{instruction name: "mx_a/mx_b"}``, the program's own scopes
        from the outermost in."""
        from mxnet_tpu.telemetry.trace import hlo_scopes
        return hlo_scopes(self.text, path=True)


@functools.cache
def compiled_step(name):
    """The ``Step`` of the ``PatternLM`` cell ``name``, as its
    configuration module builds it, compiled from shapes alone: once a
    process, since a step takes a minute or two to compile."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import (attn_kernel, gdn_conv_kernel, gdn_kernel,
                               gmm_kernel, mhc_kernel, moe_rows_kernel, seq)
    from mxnet_tpu.parallel import TrainStep, exit_weighted_loss
    one_chip = chip()
    cell = harness.load_cell(name)
    sizes = cell.sizes
    net = cell.model._net(sizes)
    net.initialize(mx.init.Zero())
    opt = dict(cell.config["optimizer"])
    loss = exit_weighted_loss(sizes["exit_entropy_beta"]) \
        if "exit_entropy_beta" in sizes else "softmax_ce"
    step = TrainStep(net, loss=loss, optimizer=opt.pop("name"),
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
    with jax_cache_off():
        compiled = step._step_jit.lower(
            pvals, state, spec((sizes["batch"], sizes["seq_len"]), jnp.int32),
            spec((tokens,), jnp.int32), spec((), jnp.uint32),
            spec(())).compile()
    gauges = {g: mx.telemetry.gauge(g).get() for g in (
        attn_kernel.GAUGE, attn_kernel.FUSED_BWD_GAUGE,
        attn_kernel.WINDOW_GAUGE, gmm_kernel.GAUGE,
        gdn_kernel.GAUGE, gdn_conv_kernel.GAUGE, mhc_kernel.GAUGE,
        moe_rows_kernel.GAUGE, seq.MHC_GAUGE)}
    kept = {k.rsplit("::", 1)[1]: v["value"] for k, v in
            mx.telemetry.snapshot(prefix="remat::saved_bytes::").items()}
    return Step(cell, compiled, gauges, kept)
