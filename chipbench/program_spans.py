#!/usr/bin/env python3
"""Run one cell with the data plane's own host spans on, and read them.

    python3 chipbench/program_spans.py --workload h32-k16-q4.saturate \\
        --seed 7 --seconds 10 --trace 1

The cell runs as ``run.py`` runs it, except that the runtime's
``HostSpans`` (``DataplaneRuntime.spans``, the ``dp.`` spans and
counters) record from the harness's ``start()`` to the end of the window:
the drain after it is left out.  With ``--trace 1`` each span is also a
profiler host event, on the clock the device's ops are moved to.  The
last line of standard output is one JSON object:

* ``result``: the line ``run.py`` prints for the same run;
* ``program``: ``metrics`` (the readers of ``metrics/`` that read the
  spans, and the harness's ``tick_ns_per_pkt.tput`` beside them),
  ``tick_split`` (the six tick-phase metrics plus ``dp.tick`` self time,
  against the harness's ``tick``), ``spans`` and ``counters`` (the
  snapshot, in ns), ``slowest_ticks`` (the longest ticks, in us, each
  with the self time of every span inside it);
* ``idle_by_program_span`` (``--trace 1``): the device's idle time
  charged to the innermost ``dp.`` span open on the host, ``other``
  where none is.

The benchmark's files are used as they are: the spans are switched on
through a ``Harness`` subclass put in place of ``chipbench.harness.Harness``
for this process.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import tracing  # noqa: E402

PREFIX = "dp."
#: The readers of ``metrics/`` that read the program's spans.
METRICS = ("tick_prep_ns_per_pkt.tput", "h2d_ns_per_pkt.tput",
           "launch_ns_per_pkt.tput", "device_wait_ns_per_pkt.tput",
           "d2h_ns_per_pkt.tput", "retire_host_ns_per_pkt.tput",
           "ring_wait_us.tput", "kernel_rows_per_pkt.tput")
#: The six of them that split the tick, in ns per packet.
TICK_PHASES = METRICS[:6]


def per_packet(ctx, names, key: str = "total_ns"):
    """Sum of ``key`` over the spans ``names`` of ``ctx.program``, per
    timed packet retired in the window; None without the spans."""
    prog = getattr(ctx, "program", None)
    if not prog or not ctx.retired_in_window:
        return None
    spans = prog["spans"]
    if not any(n in spans for n in names):
        return None
    return sum(spans[n][key] for n in names if n in spans) \
        / ctx.retired_in_window


def counter_ratio(ctx, num: str, den: str):
    """``ctx.program``'s counter ``num`` over counter ``den``."""
    prog = getattr(ctx, "program", None)
    c = prog["counters"] if prog else {}
    if not c.get(den) or num not in c:
        return None
    return c[num] / c[den]


def host_events(path: str, prefix: str = PREFIX) -> list:
    """[(start, end, name)] ns of the host events in a trace file whose
    name starts with ``prefix``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [e for e in tracing._events(line)
                    if e[2].startswith(prefix)]
    return out


def innermost(events) -> list:
    """Nested events as non-overlapping [(start, end, name)]: each
    instant goes to the innermost event open then."""
    segs, stack, t = [], [], None

    def upto(x):
        nonlocal t
        if stack and x > t:
            segs.append((t, x, stack[-1][1]))
        t = x if t is None else max(t, x)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            upto(stack[-1][0])
            stack.pop()
        upto(s)
        stack.append((e, name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    return segs


def idle_by_program_span(reduced, events, n: int = 16) -> list:
    """[[span, seconds]]: the device's idle time in the window, each gap
    charged to the innermost ``events`` span open on the host during it
    (``other`` where none), averaged over the chips."""
    segs = innermost(events)
    ends = [e for _, e, _ in segs]
    acc = collections.Counter()
    for d in reduced.devices:
        busy = tracing._union(tracing._clip(d.ops, reduced.window))
        for g0, g1 in tracing._gaps(busy, reduced.window):
            covered = 0
            i = bisect.bisect_right(ends, g0)
            while i < len(segs) and segs[i][0] < g1:
                s, e, name = segs[i]
                ov = min(e, g1) - max(s, g0)
                if ov > 0:
                    acc[name] += ov
                    covered += ov
                i += 1
            acc["other"] += (g1 - g0) - covered
    k = max(1, len(reduced.devices))
    return [[name, v / k / 1e9] for name, v in acc.most_common(n) if v > 0]


def _spanned_harness(base):
    """``base`` (the harness class) with the runtime's spans on from
    ``start()`` until the first ``drain()`` after it."""

    class SpannedHarness(base):
        last = None

        def __init__(self, rt, *, annotate: bool = False):
            super().__init__(rt, annotate=annotate)
            self.program = None
            SpannedHarness.last = self

        def start(self) -> None:
            super().start()
            self.rt.spans.enable(annotate=self.annotate)
            self.rt.spans.reset()

        def drain(self) -> None:
            if self.recording and self.program is None:
                self.program = self.rt.spans.snapshot()
                self.rt.spans.disable()
            super().drain()

    return SpannedHarness


def _us(ns: int) -> float:
    return ns / 1e3


def program_report(h, window, cell_mod) -> dict:
    """The spans' metrics and breakdown from a finished run's harness."""
    seqs, _, _, _, _, times = h.served()
    retired = int(((seqs < h.offered) & (times <= window[1])).sum())
    ctx = types.SimpleNamespace(
        program=h.program, retired_in_window=retired,
        spans={k: tuple(v) for k, v in h.spans.items()})
    metrics = {}
    for name in METRICS + ("tick_ns_per_pkt.tput",):
        v = cell_mod.reader(name).read(ctx)
        if v is not None:
            metrics[name] = v
    tick_self = per_packet(ctx, ("dp.tick",), "self_ns")
    split = None
    if tick_self is not None and "tick_ns_per_pkt.tput" in metrics:
        parts = sum(metrics.get(m, 0.0) for m in TICK_PHASES) + tick_self
        split = {"phases_plus_tick_self_ns_per_pkt": parts,
                 "dp_tick_self_ns_per_pkt": tick_self,
                 "over_harness_tick": parts / metrics["tick_ns_per_pkt.tput"]}
    prog = h.program or {"spans": {}, "counters": {}, "slowest_ticks": []}
    return {
        "retired_in_window": retired,
        "metrics": metrics,
        "tick_split": split,
        "spans": prog["spans"],
        "counters": prog["counters"],
        "slowest_ticks": [
            {"total_us": _us(t["total_ns"]),
             "self_us": {k: _us(v) for k, v in sorted(
                 t["self_ns"].items(), key=lambda kv: -kv[1])}}
            for t in prog["slowest_ticks"]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip (the CPU with JAX_PLATFORMS=cpu)")
    ap.add_argument("--interpret", action="store_true",
                    help="with --rehearse: the Pallas kernel in interpret mode")
    args = ap.parse_args(argv)
    if args.interpret and not args.rehearse:
        ap.error("--interpret needs --rehearse")

    if args.rehearse:
        from chipbench.run import virtual_chips
        virtual_chips(args.workload)
    import jax
    from chipbench import cell, harness as harness_lib
    cell.setup_jax()
    dev = jax.devices()
    t_devices = time.perf_counter()
    print(f"device: platform={dev[0].platform} device_kind="
          f"{dev[0].device_kind} count={len(dev)}", flush=True)
    spanned = _spanned_harness(harness_lib.Harness)
    harness_lib.Harness = spanned
    try:
        out = cell.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_proc=T_PROC,
                            t_devices=t_devices, rehearse=args.rehearse,
                            interpret=args.interpret)
    except cell.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    finally:
        harness_lib.Harness = spanned.__bases__[0]
    h = spanned.last
    report = {"workload": args.workload, "seed": args.seed,
              "trace": args.trace, "result": out["result"],
              "aligned": out["aligned"],
              "program": program_report(h, out["window"], cell)}
    if args.trace:
        path = tracing.find(cell.TRACE_DIR)
        report["idle_by_program_span"] = idle_by_program_span(
            tracing.reduce(path, device_ids=out["chips"]), host_events(path))
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
