#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate served
with zero drops and no ring backlog growth across the window.

    python chipbench/sweep.py --workload h32-k16-q4.swap \\
        --rates 80000,100000,120000 --seconds 5 --seed 11 \\
        --out out/sweep.json

One process holds the chip and runs the cell's traffic at each rate in
turn (each with a fresh runtime, every program compiled once).  Backlog
growth compares the mean number of rows waiting in the rings after each
dispatch over the window's last quarter with its second quarter; more
than one tick's worth (queues x 128 rows) counts as growth.  The cells'
rate is 0.8 of the knee, written by hand into the traffic files.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def growth(backlog, t0: float, t1: float) -> float:
    """Mean rows waiting in the last quarter minus the second quarter."""
    import numpy as np
    if not backlog:
        return 0.0
    t = np.array([b[0] for b in backlog])
    w = np.array([b[1] for b in backlog], float)
    q = (t1 - t0) / 4
    late = w[(t >= t0 + 3 * q) & (t <= t1)]
    mid = w[(t >= t0 + q) & (t < t0 + 2 * q)]
    if not late.size or not mid.size:
        return 0.0
    return float(late.mean() - mid.mean())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated packets/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import cell, harness
    cell.setup_jax()
    spec = cell.load_spec()
    cfg = cell.config(spec, cell.workload(spec, args.workload)["config"])
    allow = cfg["queues"] * 128
    events = harness.CompileEvents()
    rows, knee = [], None
    for rate in (float(r) for r in args.rates.split(",")):
        out = cell.run_cell(args.workload, args.seed, args.seconds, False,
                            t_proc=time.perf_counter(), rehearse=args.rehearse,
                            events=events,
                            mix_overrides={"rate_pps": rate})
        res = out["result"]
        g = growth(out["backlog"], *out["window"])
        ok = res["failed"] == 0 and g <= allow and res["correct"]
        row = {"rate_pps": rate, "dropped": res["failed"],
               "backlog_growth_rows": g, "correct": res["correct"],
               "sustained": ok, **out["e2e"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if ok:
            knee = rate
    summary = {"workload": args.workload, "seconds": args.seconds,
               "seed": args.seed, "knee_pps": knee,
               "rate_at_0.8_knee": None if knee is None else 0.8 * knee,
               "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
