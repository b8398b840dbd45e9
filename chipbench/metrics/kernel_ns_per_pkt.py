"""kernel_ns_per_pkt: device time of the fused forwarding kernel's events
in the traced window, per timed packet retired in it.

The kernel is the Pallas custom call that ``kernels/fused_forward.py``
emits; the trace names its op ``%fused_forward.<n> = ...
custom_call_target="tpu_custom_call"``.  A program without it reads
nothing here."""

import re

PATTERN = re.compile(r'^%fused_forward(\.\d+)? = .*custom_call_target="tpu_custom_call"')


def match(text: str) -> bool:
    return PATTERN.match(text) is not None


def read(ctx):
    if ctx.trace is None or not ctx.retired_in_window:
        return None
    s = ctx.trace.op_seconds(match)
    return s / ctx.retired_in_window * 1e9 if s > 0 else None
