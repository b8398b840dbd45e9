"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python chipbench/tests/record_trace.py --out out/trace_sample
    python chipbench/tests/record_trace.py --out out/trace_sample \
        --chips 4 --queues 16 --ticks 4

Serves a few ticks of the ``h32-k16-q4.saturate`` cell on one TPU through
the harness, with its host spans and the ``window`` span as
``TraceAnnotation``s and the python tracer off, as a traced run has them,
and writes the ``.xplane.pb`` and a summary of what the reduction reads
from it.  With ``--chips 4 --queues 16`` the same configuration's 16
queues are spread over four chips, as the harness runs a cell on four.
The kept copies are ``chipbench/tests/data/tick_trace.xplane.pb`` and
``tick_trace_4chips.xplane.pb``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def scrub(data: bytes, root: str = ROOT) -> bytes:
    """The trace with the checkout's path in its source locations replaced
    by a placeholder of the same length, so the kept copy names no
    machine's directory and stays a valid protobuf."""
    path = root.encode()
    return data.replace(path, b"<" + b"." * (len(path) - 2) + b">")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--ticks", type=int, default=8)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--queues", type=int, default=None,
                    help="queues in place of the configuration's")
    args = ap.parse_args(argv)

    import jax
    from chipbench import cell, harness, tracing, weights
    from chipbench.traffic import closed
    from chipbench.traffic.packets import PacketSource
    cell.setup_jax()
    devs = cell.devices(args.chips, rehearse=False)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.dataplane import DataplaneRuntime

    spec = cell.load_spec()
    cfg = dict(cell.config(spec, "h32-k16-q4"))
    cfg["queues"] = args.queues or cfg["queues"]
    mix = cell.traffic("saturate")
    rt = DataplaneRuntime(weights.bank(cfg, 7), num_queues=cfg["queues"],
                          ring_capacity=cfg["ring_capacity"],
                          **cell.placement(devs[:args.chips]))
    h = harness.Harness(rt, annotate=True)
    src = PacketSource(slots=cfg["slots"], flows=mix["flows"],
                       monitor_share=mix["monitor_share"], seed=7)
    drv = closed.Driver(h, src, cfg, mix)
    drv.warm_up()
    raw = os.path.join(args.out, "raw")
    shutil.rmtree(raw, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(raw, profiler_options=opts)
    h.start()
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        seq = 0
        for _ in range(args.ticks):
            seq = drv._fill(seq)
            h.tick()
    h.drain()
    jax.profiler.stop_trace()
    path = tracing.find(raw)
    name = "tick_trace" + (f"_{args.chips}chips" if args.chips > 1 else "")
    with open(path, "rb") as f, \
            open(os.path.join(args.out, f"{name}.xplane.pb"), "wb") as g:
        g.write(scrub(f.read()))
    r = tracing.reduce(path, device_ids=[d.id for d in devs[:args.chips]])
    from chipbench.metrics import kernel_ns_per_pkt
    print(json.dumps({
        "device": devs[0].device_kind, "bytes": os.path.getsize(path),
        "window_s": r.window_s, "busy_s": r.busy_s(),
        "planes": [d.name for d in r.devices],
        "aligned": [d.aligned for d in r.devices],
        "modules": [len(d.modules) for d in r.devices],
        "ops": [len(d.ops) for d in r.devices],
        "kernel_s": r.op_seconds(kernel_ns_per_pkt.match),
        "step_other_s": r.op_seconds(
            lambda t: not kernel_ns_per_pkt.match(t), within="jit_packet_step"),
        "top_ops": r.top_ops(5), "idle_by_span": r.idle_by_span(),
        "spans": len(r.spans), "ticks": args.ticks,
        "tick_work": h.tick_work(0, float("inf")),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
