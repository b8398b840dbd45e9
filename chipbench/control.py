#!/usr/bin/env python3
"""Readings of the comparison for the program and for its control.

    python chipbench/control.py --workload h32-k16-q4.saturate \\
        --seeds 1,2,3 --seconds 10 --out out/control.json

For each seed, in one process that holds the chip: one run of the cell at
its own load and window, then the comparison of the program's answers
(the lower readings) and of the control's -- the reference with layer 2 in
bfloat16 put in the program's place, on the same packets (the upper
readings).  Every limit in ``reference/check.py`` was set from these.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import cell, harness
    cell.setup_jax()
    events = harness.CompileEvents()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = cell.run_cell(args.workload, seed, args.seconds, False,
                            t_proc=time.perf_counter(), rehearse=args.rehearse,
                            control=True, events=events)
        row = {"seed": seed, "correct": out["result"]["correct"],
               "program": out["readings"], "control": out["control"],
               "e2e": out["e2e"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
