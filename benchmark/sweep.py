"""Find the knee of an open-loop cell once, on the chip: the same
session under a list of rates, one window each.

    python3 benchmark/sweep.py --workload <name> --seed 1 --rates 400,800,1600 --seconds 8

The knee is the highest rate at which nothing is shed or fails and the
queue at the end of the window is no deeper than at its middle (give or
take one request of the largest size, which is what a queue that is not
growing holds at a random moment). The
cell's traffic file then fixes four fifths of it as ``rate_rps``; the
benchmark's own runs never search. One JSON object a rate, last line
the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, bool(args.rehearsal))
    harness.find_device(cell.chips, bool(args.rehearsal))
    session = cell.driver.setup(cell, args.seed)
    knee = None
    largest = max(int(k) for k in cell.traffic["rows_mix"])
    for rate in (float(r) for r in args.rates.split(",")):
        result = cell.driver.window(cell, session, args.seconds,
                                    rate_rps=rate)
        f = result["facts"]
        held = (result["failed"] == 0 and f["shed"] == 0
                and f["queue_depth_end"] <= f["queue_depth_mid"] + largest)
        if held:
            knee = rate if knee is None else max(knee, rate)
        print(json.dumps({
            "rate_rps": rate, "held": held, "failed": result["failed"],
            **{k: v["value"] if isinstance(v, dict) else v
               for k, v in result["metrics"].items()},
            **{k: f[k] for k in ("rows_per_s", "queue_depth_mid",
                                 "queue_depth_end", "generator_lag_p95_ms",
                                 "batches", "rows_real", "rows_launched")}}),
            flush=True)
    cell.driver.close(session)
    print(json.dumps({"knee_rps": knee,
                      "memory_peak_bytes": harness.memory_peak_bytes()}))


if __name__ == "__main__":
    main()
