#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python chipbench/run.py --workload h32-k16-q4.saturate --seed 7 \\
        --seconds 10 --trace 0

Prints the device, the window's compile count and the check's numbers
beside their limits, and as its last line of standard output one JSON
object: ``correct``, ``attempted`` (timed packets offered), ``failed``
(ring-edge drops), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` (traced
runs) and ``check``.  Off a TPU, with fewer chips than the cell asks
for, or, for a cell on several chips, with any other number, it prints
no result and exits 2.  ``--rehearse`` runs on whatever JAX finds (the
CPU, with ``JAX_PLATFORMS=cpu``; as many devices as a cell on several
chips asks for) and prints no metric.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def virtual_chips(name: str) -> None:
    """Give a rehearsal of a cell on N > 1 chips N devices of the CPU;
    XLA reads the flag when JAX first starts a backend."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        chips = next((w["chips"] for w in json.load(f)["workloads"]
                      if w["name"] == name), 1)
    if chips > 1:
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"),
            f"--xla_force_host_platform_device_count={chips}")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip; no metric is printed")
    ap.add_argument("--interpret", action="store_true",
                    help="with --rehearse: the Pallas kernel in interpret mode")
    args = ap.parse_args(argv)
    if args.interpret and not args.rehearse:
        ap.error("--interpret needs --rehearse")

    if args.rehearse:
        virtual_chips(args.workload)
    try:
        from chipbench import cell
    except ImportError as e:
        print(f"chipbench: cannot load the harness: {e}", file=sys.stderr)
        return 3
    import jax
    cell.setup_jax()
    dev = jax.devices()
    t_devices = time.perf_counter()
    print(f"device: platform={dev[0].platform} device_kind={dev[0].device_kind}"
          f" count={len(dev)}", flush=True)
    try:
        out = cell.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_proc=T_PROC,
                            t_devices=t_devices,
                            rehearse=args.rehearse, interpret=args.interpret)
    except cell.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    except ImportError as e:
        print(f"chipbench: the program is missing: {e}", file=sys.stderr)
        return 3
    result = out["result"]
    if args.rehearse:
        result["metrics"] = {}
        result.pop("breakdown", None)
        result = {"rehearsal": True, **result}
    print("setup: " + " ".join(f"{k}={v:.3f}s" for k, v in
                               out["setup_parts"].items()), file=sys.stderr)
    if out["trace_s"] is not None:
        print(f"trace: stopped and reduced in {out['trace_s']:.2f} s; "
              "each chip's clock aligned to the host's: "
              + ", ".join(f"{k} {v}" for k, v in out["aligned"].items()),
              file=sys.stderr)
    gcp = out["gc_pauses"]
    print(f"gc: {len(gcp)} full collections in the window"
          + (f", longest {max(gcp) * 1e3:.1f} ms" if gcp else ""),
          file=sys.stderr)
    print(f"compiles_in_window: {out['compiles']}"
          + (" (the window compiled or loaded programs)"
             if out["compiles"] else ""), flush=True)
    print(f"check: {out['readings']['checked']} packets compared in "
          f"{out['check_s']:.2f} s, {out['readings']['undecided']} scores "
          f"within the rounding margin of 0 (not judged)", file=sys.stderr)
    for k, v in result["check"].items():
        print(f"check: {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
