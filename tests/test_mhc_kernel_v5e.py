"""The step of ``xing4.0-29b-a4b-train-4k`` compiled for a v5e that is
described and not attached, at the sizes the cell times: Mosaic takes the
hyper-connections' kernels (``ops/mhc_kernel.py``) at the cell's shapes
(4096 tokens of 4 streams of 3584, bfloat16), the four of them by name for
each of the ten sublayers, each under ``mx_mhc_pre`` or ``mx_mhc_post``
and no longer path; nothing under an ``mx_mhc_*`` scope writes a value as
wide as the streams in float32 any more (the plain form's cotangents of
its three sublayer scopes); a
unit keeps of its hyper-connection what it kept before the kernels, and
the step fits what one chip gives a program. Nothing runs here, so nothing
here is a time or a result. The topology is described inside a fixture
only (one process at a time may load the TPU's library: the
on-chip-measurement guide, section 2)."""
import collections
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402

CELL = "xing4.0-29b-a4b-train-4k"
#: what one v5e gives a program: ``bytes_limit`` of the device's memory
#: statistics (a chip run of PR 41), 15.75 GiB
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


@pytest.fixture(scope="module")
def compiled_step(one_chip):
    """``(sizes, compiled, gauges, kept)``: the cell's step compiled for
    the described chip from shapes alone, what the gauges counted at its
    lowering, and the bytes each recomputation unit keeps."""
    from jax.experimental.compilation_cache import compilation_cache
    import mxnet_tpu as mx
    from mxnet_tpu.ops import mhc_kernel, seq
    from mxnet_tpu.parallel import TrainStep
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
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
        gauges = {g: mx.telemetry.gauge(g).get()
                  for g in (mhc_kernel.GAUGE, seq.MHC_GAUGE)}
        kept = {k.rsplit("::", 1)[1]: v["value"] for k, v in
                mx.telemetry.snapshot(prefix="remat::saved_bytes::").items()}
        return sizes, compiled, gauges, kept
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _custom_calls(hlo):
    """``{instruction name: kernel name}`` of a compiled program's Mosaic
    calls."""
    return {name: name.rsplit(".", 1)[0] for name in re.findall(
        r'%?([\w.\-]+) = [^\n]*?custom_call_target="tpu_custom_call"', hlo)}


def _sublayers(sizes):
    return 2 * sizes["num_hidden_layers"]


def test_mosaic_takes_the_four_kernels_of_every_sublayer(compiled_step):
    """The read side's forward runs in both passes of a recomputation unit
    (``u`` is as wide as a stream and not kept); the write side's forward
    runs once, since its backward reads nothing the forward made; each
    side's backward once. The ten sublayers are one shape, so each gauge
    counts lowered programs, not sublayers: above 0 says that the branch
    was taken."""
    from mxnet_tpu.ops import mhc_kernel, seq
    sizes, compiled, gauges, _ = compiled_step
    assert gauges[mhc_kernel.GAUGE] >= 1 and gauges[seq.MHC_GAUGE] >= 1, \
        gauges
    calls = collections.Counter(
        k for k in _custom_calls(compiled.as_text()).values()
        if k.startswith("mhc_"))
    sub = _sublayers(sizes)
    assert calls == {"mhc_read_kernel": 2 * sub, "mhc_post_kernel": sub,
                     "mhc_post_bwd_kernel": sub, "mhc_read_bwd_kernel": sub}


def test_every_kernel_is_filed_under_the_scope_its_roofline_reads(
        compiled_step):
    """``mhc_roofline.train`` reads ``^mx_mhc_(maps|pre|post)$``, anchored
    at both ends: the read side's kernels, forward and backward, stand
    under ``mx_mhc_pre``, the write side's under ``mx_mhc_post``, and no
    instruction of the step has one of the three twice in its path or
    inside another."""
    from mxnet_tpu.telemetry import trace
    _, compiled, _, _ = compiled_step
    hlo = compiled.as_text()
    paths = trace.hlo_scopes(hlo, path=True)
    mine = collections.defaultdict(set)
    for name, kernel in _custom_calls(hlo).items():
        if kernel.startswith("mhc_"):
            mine[kernel].add(paths.get(name))
    assert dict(mine) == {
        "mhc_read_kernel": {"mx_mhc_pre"},
        "mhc_read_bwd_kernel": {"mx_mhc_pre"},
        "mhc_post_kernel": {"mx_mhc_post"},
        "mhc_post_bwd_kernel": {"mx_mhc_post"}}
    for path in set(paths.values()):
        parts = path.split("/")
        if any(p.startswith("mx_mhc_") for p in parts):
            assert len(parts) == 1, path


def test_nothing_under_the_streams_scopes_is_as_wide_as_they_in_float32(
        compiled_step):
    """The plain form's backward wrote a float32 cotangent as wide as the
    streams for each of their uses (235 MB each); the kernels write one
    ``dX`` in the compute dtype. Nor does a (tokens, 24) or (tokens, 4, 4)
    value stand anywhere: the maps go in and out tokens minor."""
    from mxnet_tpu.telemetry import trace
    sizes, compiled, _, _ = compiled_step
    hlo = compiled.as_text()
    tokens = sizes["batch"] * sizes["seq_len"]
    n = sizes["hc_mult"]
    width = n * sizes["hidden_size"]
    wide = (f"f32[{tokens},{width}]", f"f32[1,{tokens},{width}]",
            f"f32[{sizes['batch']},{sizes['seq_len']},{width}]")
    paths = trace.hlo_scopes(hlo, path=True)
    written = {}
    for name, shape in re.findall(r"%?([\w.\-]+) = \(?((?:f32|bf16)\[[\d,]*\])",
                                  hlo):
        written[name] = shape
    # (the streams' sum at the stack's end, ``mx_mhc_out``, is not a
    # sublayer's and keeps XLA's backward)
    under = {name: shape for name, shape in written.items()
             if re.match(r"mx_mhc_(maps|pre|post)$", paths.get(name, ""))
             and shape in wide}
    assert not under, under
    for padded in (f"f32[{tokens},{n},{n}]", f"f32[1,{tokens},{n},{n}]",
                   f"f32[{tokens},{n * (n + 2)}]{{1,0"):
        assert padded not in hlo, padded


def test_step_fits_one_v5e_and_a_unit_keeps_what_it_kept(compiled_step):
    """656.1 M parameters with Adam's moments, 4096 tokens of four
    streams, recomputation by layer: arguments, outputs and temporaries on
    one described v5e, under the 15.0e9 bytes the accepted compile test
    holds the step to; a unit keeps of its hyper-connection the 24-wide
    product, the mean square and ``y``: not ``u``, not a map."""
    sizes, compiled, _, kept = compiled_step
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"xing4.0-29b-a4b step: {peak / 1e9:.2f} GB "
          f"({m.argument_size_in_bytes / 1e9:.2f} of state, "
          f"{m.temp_size_in_bytes / 1e9:.2f} of temporaries), "
          f"{sum(kept.values()) / 1e9:.3f} GB kept by {len(kept)} units")
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < peak < 15.0e9 < CHIP_BYTES, peak
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    tokens = sizes["batch"] * sizes["seq_len"]
    units = sizes["hidden_size"]
    (mlp,) = [v for k, v in kept.items() if k.endswith("_l1_")]
    assert mlp == tokens * (25 * 4 + 4 + units * 2)


def test_what_the_kernels_hold_in_vmem_is_under_their_budget():
    """At the cell's shapes, by the module's own statement."""
    from mxnet_tpu.ops import mhc_kernel
    sz = harness.load_cell(CELL).sizes
    n, width = sz["hc_mult"], sz["hc_mult"] * sz["hidden_size"]
    assert (n, width) == (4, 14336)
    assert mhc_kernel.takes(sz["batch"] * sz["seq_len"], n, width,
                            jnp.bfloat16)
    assert 2e7 < mhc_kernel.held_bytes(n, width, 2) \
        < mhc_kernel._BUDGET_BYTES < mhc_kernel._VMEM_LIMIT_BYTES
