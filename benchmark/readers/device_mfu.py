"""Model FLOPs of the window's items (from shapes, 2 per
multiply-accumulate, recomputation not counted: ``flops.py`` through the
configuration's ``flops_per_item``) over the device's busy time times the
chip's peak from ``peaks.json``."""
import harness


def read(params, facts):
    cell, busy = facts["cell"], facts["busy_s"]
    items = facts["window"].get("items")
    if not items or busy <= 0 or cell.rehearsal:
        return None
    peak = harness.peaks_for(facts["device"]["kind"])[params["peak"]]
    flops = cell.model.flops_per_item(cell.sizes, params["mode"]) * items
    return 100.0 * flops / (busy * peak * cell.chips)
