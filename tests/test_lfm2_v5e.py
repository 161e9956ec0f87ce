"""The step of ``lfm2-24b-a2b-train-8k`` compiled for a v5e that is
described and not attached (``tests/described_v5e.py``), at the sizes the
cell times: it fits the chip with room to spare, its routed experts are
this repo's grouped-product kernels at 1536 x 2048, and its 64-wide heads
take no attention kernel. Nothing runs here: counts by XLA, not times."""
import re

from described_v5e import (CHIP_BYTES, ROW_KERNELS, compiled_step, peak_bytes,
                           row_kernels_stand)
from test_moonlight_v5e import MOE_KERNELS

CELL = "lfm2-24b-a2b-train-8k"


def test_the_step_fits_the_chip_with_a_gigabyte_to_spare():
    step = compiled_step(CELL)
    peak = peak_bytes(step.compiled)
    kept = sum(step.kept.values())
    print(f"lfm2 step: {peak / 1e9:.2f} GB, {kept / 1e9:.4f} GB kept by "
          f"{len(step.kept)} units")
    assert peak < CHIP_BYTES - 1e9, peak
    # ten units; a conv mixer's keeps its packed projection, 3 x 2048 wide
    # in bfloat16 at 16,384 tokens
    assert len(step.kept) == 10
    packed = 16_384 * 3 * 2048 * 2
    assert sum(v >= packed for v in step.kept.values()) >= 4


def test_experts_are_the_kernels_and_attention_is_none():
    """Each of the four expert layers holds each of the six grouped-product
    kernels once, under ``mx_moe_gmm_up`` or ``mx_moe_gmm_down``; no other
    Mosaic call but the experts' row kernels is in the step: heads of 64 are no lane tile, so the
    attention is the blocked recurrence in plain JAX under
    ``mx_attn_fwd``, and the gauges read what ``attn_kernel_sites.train``
    and ``moe_gmm_kernel_sites.train`` will."""
    from mxnet_tpu.ops import attn_kernel, gmm_kernel
    step = compiled_step(CELL)
    calls = {k: [i for i, kernel in step.calls.items() if kernel == k]
             for k in MOE_KERNELS}
    for kernel, found in calls.items():
        assert len(found) == 4, (kernel, len(found))
        for instruction in found:
            assert re.search(rf"(^|/){MOE_KERNELS[kernel]}$",
                             step.paths[instruction]), (kernel, instruction)
    assert set(step.calls.values()) == set(MOE_KERNELS) | set(ROW_KERNELS)
    assert "ragged-dot" not in step.text
    assert step.gauges[attn_kernel.GAUGE] == 0
    assert step.gauges[attn_kernel.FUSED_BWD_GAUGE] == 0
    # like layers share one lowered program: the gauge counts programs
    assert step.gauges[gmm_kernel.GAUGE] == 1
    paths = set(step.paths.values())
    assert {"mx_attn_fwd", "mx_attn_qk_norm", "mx_rope", "mx_sconv_proj",
            "mx_sconv_gate", "mx_sconv_conv"} <= paths
    assert not [p for p in paths if "mx_moe_shared" in p]


def test_rows_travel_by_the_row_kernels():
    """12,288 pool rows and 16,384 tokens of 2048: the tokens, the larger
    source, are 64 MiB in VMEM."""
    step = compiled_step(CELL)
    row_kernels_stand(step, 4, step.sizes["hidden_size"])
