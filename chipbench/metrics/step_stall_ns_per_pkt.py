"""step_stall_ns_per_pkt: device time, summed over the cell's chips, in
which a module named ``jit_packet_step...`` is open on a chip and none of
that chip's ops runs, per timed packet retired in the traced window.

A step's module opens when its launch reaches the chip; time it then
spends with no op running is the step waiting on its inputs: on the
chips of a sharded launch, for a batch staged on one chip and sliced to
the others, or for a bank moved before it can run.  A trace with no such
module reads nothing here."""

import bisect

from chipbench import tracing

STEP = "jit_packet_step"


def stall_ns(device, window) -> float | None:
    """ns in ``window`` in which one of ``device``'s step modules is open
    and none of its ops runs; None where no step module is open."""
    mods = tracing._union(tracing._clip(
        [m for m in device.modules if STEP in m[2]], window))
    if not mods:
        return None
    busy = tracing._union(tracing._clip(device.ops, window))
    ends = [e for _, e in busy]
    idle = 0
    for s, e in mods:
        covered = 0
        i = bisect.bisect_right(ends, s)
        while i < len(busy) and busy[i][0] < e:
            covered += min(e, busy[i][1]) - max(s, busy[i][0])
            i += 1
        idle += (e - s) - covered
    return idle


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.retired_in_window:
        return None
    per_chip = [stall_ns(d, t.window) for d in t.devices]
    if all(v is None for v in per_chip):
        return None
    return sum(v for v in per_chip if v is not None) / ctx.retired_in_window
