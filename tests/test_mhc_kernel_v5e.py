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
here is a time or a result. The chip is described and the step compiled,
once a process, by ``tests/described_v5e.py``."""
import collections
import re

import jax.numpy as jnp

from described_v5e import (CHIP_BYTES, compiled_step, harness, peak_bytes,
                           row_kernels_stand)

CELL = "xing4.0-29b-a4b-train-4k"


def _sublayers(sizes):
    return 2 * sizes["num_hidden_layers"]


def test_mosaic_takes_the_four_kernels_of_every_sublayer():
    """The read side's forward runs in both passes of a recomputation unit
    (``u`` is as wide as a stream and not kept); the write side's forward
    runs once, since its backward reads nothing the forward made; each
    side's backward once. The ten sublayers are one shape, so each gauge
    counts lowered programs, not sublayers: above 0 says that the branch
    was taken."""
    from mxnet_tpu.ops import mhc_kernel, seq
    step = compiled_step(CELL)
    gauges = step.gauges
    assert gauges[mhc_kernel.GAUGE] >= 1 and gauges[seq.MHC_GAUGE] >= 1, \
        gauges
    calls = collections.Counter(
        k for k in step.calls.values()
        if k.startswith("mhc_"))
    sub = _sublayers(step.sizes)
    assert calls == {"mhc_read_kernel": 2 * sub, "mhc_post_kernel": sub,
                     "mhc_post_bwd_kernel": sub, "mhc_read_bwd_kernel": sub}


def test_every_kernel_is_filed_under_the_scope_its_roofline_reads():
    """``mhc_roofline.train`` reads ``^mx_mhc_(maps|pre|post)$``, anchored
    at both ends: the read side's kernels, forward and backward, stand
    under ``mx_mhc_pre``, the write side's under ``mx_mhc_post``, and no
    instruction of the step has one of the three twice in its path or
    inside another."""
    step = compiled_step(CELL)
    paths = step.paths
    mine = collections.defaultdict(set)
    for name, kernel in step.calls.items():
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


def test_nothing_under_the_streams_scopes_is_as_wide_as_they_in_float32():
    """The plain form's backward wrote a float32 cotangent as wide as the
    streams for each of their uses (235 MB each); the kernels write one
    ``dX`` in the compute dtype. Nor does a (tokens, 24) or (tokens, 4, 4)
    value stand anywhere: the maps go in and out tokens minor."""
    step = compiled_step(CELL)
    sizes, hlo, paths = step.sizes, step.text, step.paths
    tokens = sizes["batch"] * sizes["seq_len"]
    n = sizes["hc_mult"]
    width = n * sizes["hidden_size"]
    wide = (f"f32[{tokens},{width}]", f"f32[1,{tokens},{width}]",
            f"f32[{sizes['batch']},{sizes['seq_len']},{width}]")
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


def test_step_fits_one_v5e_and_a_unit_keeps_what_it_kept():
    """656.1 M parameters with Adam's moments, 4096 tokens of four
    streams, recomputation by layer: arguments, outputs and temporaries on
    one described v5e, under the 15.0e9 bytes the accepted compile test
    holds the step to; a unit keeps of its hyper-connection the 24-wide
    product, the mean square and ``y``: not ``u``, not a map; and of its
    gated MLP the 2 f wide first product."""
    step = compiled_step(CELL)
    sizes, compiled, kept = step.sizes, step.compiled, step.kept
    m = compiled.memory_analysis()
    peak = peak_bytes(compiled)
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
    assert mlp == tokens * (25 * 4 + 4 + units * 2
                            + 2 * sizes["intermediate_size"] * 2)


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


def test_rows_travel_by_the_row_kernels():
    """Rows of 3584: 28 lane tiles, 32 sublanes a row in the scratch."""
    step = compiled_step(CELL)
    sizes = step.sizes
    row_kernels_stand(
        step, sizes["num_hidden_layers"] - sizes["first_k_dense_replace"],
        sizes["hidden_size"])
