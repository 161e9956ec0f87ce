"""The step of ``moonlight-16b-a3b-train-8k`` compiled for a v5e that is
described and not attached (``tests/described_v5e.py``), at the sizes the
cell times: the routed experts' pooled products are this repo's kernels
(``ops/gmm_kernel.py``). Nothing runs here: counts by XLA, not times."""
import re

from described_v5e import compiled_step, peak_bytes, row_kernels_stand

MOE_KERNELS = {"moe_gmm_up_kernel": "mx_moe_gmm_up",
               "moe_gmm_down_kernel": "mx_moe_gmm_down",
               "moe_gmm_down_rows_kernel": "mx_moe_gmm_down",
               "moe_gmm_down_weights_kernel": "mx_moe_gmm_down",
               "moe_gmm_up_rows_kernel": "mx_moe_gmm_up",
               "moe_gmm_up_weights_kernel": "mx_moe_gmm_up"}


def test_pooled_experts_are_the_kernels_forward_and_backward():
    """The step of ``moonlight-16b-a3b-train-8k`` compiled for the
    described v5e: no ``ragged-dot`` is left and no expert weight is
    copied to another layout for a backward product; an expert layer holds
    each of the six grouped-product kernels once (its unit keeps ``gate``,
    ``up`` and the result, so the backward pass runs no forward kernel
    again), every one under ``mx_moe_gmm_up`` or ``mx_moe_gmm_down`` by the
    table the roofline's reader uses; the gauge reads what the metric
    ``moe_gmm_kernel_sites.train`` will; the walk the units keep is bytes
    beside the 115 MB of an expert layer; the step fits the chip."""
    from mxnet_tpu.ops import gmm_kernel
    step = compiled_step("moonlight-16b-a3b-train-8k")
    cell, sizes, gauges, text = step.cell, step.sizes, step.gauges, step.text
    layers = sizes["num_hidden_layers"] - 1         # the expert layers
    experts = len(cell.model.held_experts(sizes))
    hidden, ff = sizes["hidden_size"], sizes["moe_intermediate_size"]
    calls = {k: [i for i, kernel in step.calls.items() if kernel == k]
             for k in MOE_KERNELS}
    kept = sum(step.kept.values())
    peak = peak_bytes(step.compiled)
    print(f"moonlight step: {peak / 1e9:.2f} GB, {kept / 1e9:.6f} GB kept, "
          f"{ {k: len(v) for k, v in calls.items()} }, "
          f"{gmm_kernel.GAUGE} {gauges[gmm_kernel.GAUGE]}")
    assert "ragged-dot" not in text
    weight = rf"bf16\[{experts},({hidden},{ff}|{ff},{hidden})\]"
    assert not [line for line in text.splitlines()
                if re.search(rf"= {weight}\S* copy\(", line)]
    for kernel, found in calls.items():
        # once a layer: a forward kernel is not run again for the backward
        assert len(found) == layers, (kernel, len(found))
        for instruction in found:
            assert re.search(rf"(^|/){MOE_KERNELS[kernel]}$",
                             step.paths[instruction]), (kernel, instruction)
    # like layers share one lowered program: the gauge counts programs
    assert gauges[gmm_kernel.GAUGE] == 1
    # ``sizes`` and the walk: a few hundred bytes a layer beside 2.0217 GB
    # (1.1912 GB and, since PR 49, the gated MLPs' first products: 2 f of
    # 22,528 in the dense layer and of 5632 in five layers' shared experts)
    tokens = sizes["batch"] * sizes["seq_len"]
    wide = 2 * (sizes["intermediate_size"]
                + layers * sizes["n_shared_experts"] * ff)
    assert 1.19120e9 < kept - tokens * wide * 2 < 1.19125e9, kept
    assert peak < 15.0e9, peak


def test_rows_travel_by_the_row_kernels():
    step = compiled_step("moonlight-16b-a3b-train-8k")
    row_kernels_stand(step, step.sizes["num_hidden_layers"] - 1,
                      step.sizes["hidden_size"])
