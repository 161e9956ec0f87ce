"""Read the numbers that ``correct`` compares, over several seeds in one
process: the control's (the plain reference computed in the precision
below the configuration's, put in the program's place) and, with
``--program 1``, the program's own.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 --program 1 --seconds 4

The limits in ``configs/<config>.json`` were set from what this prints
on the chip (PERF.md section 2 gives the readings). The benchmark's own
runs never call it. The last line is one JSON object:
``{"sound": {number: [values]}, "control": {number: [values]},
"limits": {number: limit}}``.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, bool(args.rehearsal))
    harness.find_device(cell.chips, bool(args.rehearsal))
    out = {"sound": {}, "control": {}, "limits": {}}

    def keep(kind, rows):
        for name, value, limit, _ in rows:
            out[kind].setdefault(name, []).append(value)
            out["limits"][name] = limit

    for seed in (int(s) for s in args.seeds.split(",")):
        if args.control:
            rows = cell.driver.control(cell, seed)
            harness.print_rows(rows, f"control seed {seed}")
            keep("control", rows)
        if args.program:
            session = cell.driver.setup(cell, seed)
            result = cell.driver.window(cell, session, args.seconds)
            rows = cell.driver.check(cell, session, result)
            cell.driver.close(session)
            harness.print_rows(rows, f"program seed {seed}")
            keep("sound", rows)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
