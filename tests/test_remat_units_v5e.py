"""Training steps compiled for a v5e that is described and not
attached, at the sizes their cells time (``tests/described_v5e.py``).
``nemotron3-super-train-8k`` with what its units keep (``ops.remat``): it
fits the chip, it multiplies once, it chooses and sorts once; its
attention and ``ouro-2.6b-train-4k``'s are the fused kernels; Mosaic takes
those kernels at other shapes and on both sides of the backward's rule.
(``moonlight-16b-a3b-train-8k``'s pooled experts are in
``tests/test_moonlight_v5e.py`` and ``lstm-lm-train``'s loops in
``tests/test_lstm_lm_v5e.py``: a file is one worker's, and a step takes a
minute or two to compile.) Nothing runs here: counts by XLA, not times."""
import re

import pytest

import jax
import jax.numpy as jnp

from described_v5e import (compiled_step, harness, no_jax_cache,  # noqa: F401
                           one_chip, peak_bytes, row_kernels_stand)

CELL = "nemotron3-super-train-8k"

#: XLA's count for the same step with no recomputation at all
#: (``remat=None``), and with units that keep their input alone (the
#: parent of the PR that brought ``ops.remat``): compiled as below
FLOPS_NO_RECOMPUTATION = 11.93e12
FLOPS_INPUT_ALONE = 13.85e12


def _sorts(text, scope):
    """The sort instructions of a compiled program under ``scope``."""
    return [line for line in text.splitlines()
            if re.search(r"\bsort\(", line) and scope in line]


def test_units_keep_what_is_dear_and_the_step_fits():
    step = compiled_step(CELL)
    sizes, compiled, kept = step.sizes, step.compiled, step.kept
    pattern = sizes["hybrid_override_pattern"]
    tokens = sizes["batch"] * sizes["seq_len"]
    m = compiled.memory_analysis()
    peak = peak_bytes(compiled)
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
    assert len(pattern) == len(kept)
    by_kind = {kind: kept[next(k for k in kept if k.endswith(f"_l{i}_"))]
               for i, kind in enumerate(pattern)}
    assert 0 < by_kind["*"] < by_kind["E"] < by_kind["M"] < 200e6
    assert 1.2e9 < sum(kept.values()) < 1.6e9
    # the matrix products run once forward: XLA's count is the count of
    # the step that recomputes nothing (what is left over it: the
    # attention's scores and weighted sums, 0.1 TFLOP)
    assert cost["flops"] < 1.02 * FLOPS_NO_RECOMPUTATION < FLOPS_INPUT_ALONE
    # one choice and one sort an expert layer, not two
    text = step.text
    experts = pattern.count("E")
    assert len(_sorts(text, "mx_moe_dispatch/jit(argsort)")) == experts
    assert len(_sorts(text, "mx_moe_route/top_k")) == experts


def test_rows_travel_by_the_row_kernels():
    """The experts work in the latent: rows of 1024 into eight slices of
    512, which the kernels see as one pool of 4096 (``bf16[8,512,1024]``
    is the same bytes)."""
    step = compiled_step(CELL)
    sizes = step.sizes
    row_kernels_stand(step, sizes["hybrid_override_pattern"].count("E"),
                      sizes["moe_latent_size"])
    assert "bf16[8,512,1024]" in step.text


# -- the attention of both ``PatternLM`` cells is the kernel --------------------
@pytest.mark.parametrize("name,heads", [("nemotron3-super-train-8k", 4),
                                        ("ouro-2.6b-train-4k", 16)])
def test_attention_is_the_kernel_forward_and_backward(name, heads):
    """Compiled for the described v5e from this CPU host, the step holds
    the attention as Mosaic calls under ``mx_attn_fwd``: one forward
    kernel a layer (the unit keeps its output and log-sum-exp, so the
    backward loop holds none), one fused backward kernel a layer, which
    the gauge ``attn::fused_bwd_sites`` counts, and no float32 (heads,
    block, block) score value anywhere."""
    from mxnet_tpu.ops import attn_kernel
    step = compiled_step(name)
    text = step.text
    calls = {k: [i for i, kernel in step.calls.items() if kernel == k]
             for k in ("attn_fwd_kernel", "attn_bwd_kernel")}
    counts = {k: len(v) for k, v in calls.items()}
    print(f"{name}: {counts}, attn::kernel_sites "
          f"{step.gauges[attn_kernel.GAUGE]}")
    # like layers share one lowered program: the gauge counts programs
    assert step.gauges[attn_kernel.GAUGE] == 1
    assert step.gauges[attn_kernel.FUSED_BWD_GAUGE] == 1
    assert counts["attn_fwd_kernel"] >= 1
    assert len(set(counts.values())) == 1, counts   # no second forward
    for kernel, found in calls.items():
        for instruction in found:
            assert re.search(r"(^|/)mx_attn_fwd$", step.paths[instruction]), \
                (kernel, instruction)
    assert f"f32[{heads},1024,1024]" not in text
    assert not re.search(rf"f32\[1,{heads},1024,1024\]", text)


@pytest.mark.parametrize("length,hq,hk,dim,dtype,theta", [
    (200, 4, 1, 128, "bfloat16", None),      # padded to one block of 256
    (1100, 2, 1, 128, "bfloat16", 1e4),      # padded to nine blocks of 128
    (1536, 4, 2, 256, "bfloat16", 1e4),      # heads of two lane tiles
    (2048, 8, 8, 128, "float32", None)])     # blocks of 1024 in float32
def test_mosaic_takes_the_kernels_at_other_shapes(one_chip, no_jax_cache,
                                                  length, hq, hk, dim, dtype,
                                                  theta):
    """Any ``head_dim`` that is a multiple of 128 takes the kernels on a
    TPU, whatever the length, the grouping or the dtype: Mosaic has to
    compile them all (tiling, VMEM), forward and backward."""
    from mxnet_tpu.ops import seq

    def loss(data):
        out = seq.causal_gq_attention(data, num_heads=hq, num_kv_heads=hk,
                                      head_dim=dim, rope_theta=theta)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    data = jax.ShapeDtypeStruct((2, length, (hq + 2 * hk) * dim), dtype,
                                sharding=one_chip)
    text = jax.jit(jax.grad(loss)).lower(data).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("length,hq,hk,d2,fused", [
    (8192, 16, 16, 64, True),    # Moonlight's: a head's dQ and dq2, and dk2
    (8192, 4, 1, 0, True),       # Nemotron's: a group of four heads' dQ
    (8192, 8, 1, 0, True),       # 64 MiB for all the rows: the rule's edge
    (16384, 8, 1, 0, False),     # twice that: a kernel for each side
    (16384, 2, 2, 64, True),     # a second part on the fused side ...
    (65536, 2, 2, 64, False)])   # ... and past the rule
def test_the_backward_fits_vmem_on_both_sides_of_the_rule(
        one_chip, no_jax_cache, length, hq, hk, d2, fused):
    """The fused backward holds a group's float32 ``dQ`` over all the rows
    in VMEM: at the cells' shapes and up to the rule's edge Mosaic takes
    it under the kernels' VMEM limit; past the edge the backward is the
    two kernels that hold one block's sums, and the gauge says which."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attn_kernel
    bf16 = jnp.bfloat16

    def rows(width, dtype=bf16):
        return jax.ShapeDtypeStruct((1, length, width), dtype,
                                    sharding=one_chip)

    args = [rows(hq * 128), rows(hk * 128), rows(hk * 128), rows(hq * 128),
            jax.ShapeDtypeStruct((1, hq, length), jnp.float32,
                                 sharding=one_chip), rows(hq * 128)]
    extra = [rows(hq * d2), rows(d2)] if d2 else []
    assert (attn_kernel.resident_bytes(length, hq // hk, 128, d2, 2)
            <= attn_kernel._RESIDENT_LIMIT_BYTES) == fused

    def backward(q, k, v, out, lse, dout, *extra):
        return attn_kernel.backward(q, k, v, out, lse, dout, hq, hk, 0.08,
                                    extra=extra or None)

    mx.telemetry.gauge(attn_kernel.FUSED_BWD_GAUGE).set(0)
    text = jax.jit(backward).lower(*args, *extra).compile().as_text()
    assert mx.telemetry.gauge(attn_kernel.FUSED_BWD_GAUGE).get() == fused
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [k for k in ("attn_bwd_kernel", "attn_bwd_dq_kernel",
                        "attn_bwd_dkv_kernel")
            for line in calls if f"/{k}/" in line] \
        == (["attn_bwd_kernel"] if fused
            else ["attn_bwd_dq_kernel", "attn_bwd_dkv_kernel"])
