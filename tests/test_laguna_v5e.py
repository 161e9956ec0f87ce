"""The step of ``laguna-s-2.1-train-8k`` compiled for a v5e that is
described and not attached (``tests/described_v5e.py``), at the sizes the
cell times: it fits the chip with a gigabyte and a half to spare, its
three sliding layers take the attention kernels that walk the window's
band (the backward by side: nine heads a group at 8192 rows), its two
full layers the plain ones (the backward fused: six a group), and its
routed experts the grouped-product and the row kernels at 1024 x 3072.
Nothing runs here: counts by XLA, not times."""
import collections
import re

from described_v5e import (CHIP_BYTES, ROW_KERNELS, compiled_step, peak_bytes,
                           row_kernels_stand)
from test_moonlight_v5e import MOE_KERNELS

CELL = "laguna-s-2.1-train-8k"
FULL = ("attn_fwd_kernel", "attn_bwd_kernel")
SLIDING = ("attn_swa_fwd_kernel", "attn_swa_bwd_dq_kernel",
           "attn_swa_bwd_dkv_kernel")


def test_the_step_fits_the_chip_with_room_for_the_2_way_head_share():
    """ISSUE 50's rule: under 1.5 GB to spare and the heads would be
    shared 4-way. The 2-way share stands."""
    step = compiled_step(CELL)
    peak = peak_bytes(step.compiled)
    kept = sum(step.kept.values())
    print(f"laguna step: {peak / 1e9:.2f} GB, {kept / 1e9:.4f} GB kept by "
          f"{len(step.kept)} units")
    assert peak < CHIP_BYTES - 1.5e9, peak
    assert len(step.kept) == 10
    # beside its input an attention unit keeps the packed rows (the
    # gates' logits among them), the kernels' output, a float32
    # log-sum-exp a row and head and the norm's sum of squares a row; a
    # sliding one at 36 heads, a full one at 24
    tokens = 8192
    for unit, heads in (("_l0_", 24), ("_l2_", 36), ("_l8_", 24)):
        packed = (heads + 8) * 128 + heads
        # (the net's number counts every PatternLM of the process)
        (held,) = [v for k, v in step.kept.items() if k.endswith(unit)]
        assert held == tokens * (
            (packed + heads * 128) * 2 + heads * 4 + 4), unit


def test_both_kinds_of_attention_are_the_kernels_by_name():
    """Forward and backward of every sliding layer under ``mx_swa_fwd``
    alone, of every full layer under ``mx_attn_fwd`` alone; the gauges
    read what ``attn_kernel_sites.train``, ``swa_kernel_sites.train`` and
    the fused backward's count will (like layers share one lowered
    program: a gauge counts programs, one a kind)."""
    from mxnet_tpu.ops import attn_kernel
    step = compiled_step(CELL)
    under = collections.Counter(
        (kernel, re.search(r"(^|/)(mx_\w+)$", step.paths[i]).group(2))
        for i, kernel in step.calls.items() if kernel.startswith("attn_"))
    assert under == {**{(k, "mx_attn_fwd"): 2 for k in FULL},
                     **{(k, "mx_swa_fwd"): 3 for k in SLIDING}}, under
    assert step.gauges[attn_kernel.GAUGE] == 2
    assert step.gauges[attn_kernel.WINDOW_GAUGE] == 1
    assert step.gauges[attn_kernel.FUSED_BWD_GAUGE] == 1
    # no block of scores over all the keys is in the step: a sliding
    # head's (8192, 8192) float32 scores would be 268 MB
    assert not re.search(r"f32\[(\d+,)*8192,8192\]", step.text)
    paths = set(step.paths.values())
    assert {"mx_attn_gate", "mx_rope", "mx_attn_proj"} <= paths


def test_experts_are_the_kernels_at_1024_by_3072():
    from mxnet_tpu.ops import gmm_kernel
    step = compiled_step(CELL)
    calls = collections.Counter(step.calls.values())
    for kernel, scope in MOE_KERNELS.items():
        assert calls[kernel] == 4, (kernel, calls[kernel])
        for i, k in step.calls.items():
            if k == kernel:
                assert re.search(rf"(^|/){scope}$", step.paths[i]), (k, i)
    assert set(calls) == set(MOE_KERNELS) | set(ROW_KERNELS) | set(FULL) \
        | set(SLIDING)
    assert "ragged-dot" not in step.text
    assert step.gauges[gmm_kernel.GAUGE] == 1
    row_kernels_stand(step, 4, step.sizes["hidden_size"])
