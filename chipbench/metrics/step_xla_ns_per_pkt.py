"""step_xla_ns_per_pkt: device time of the served step's ops other than
the fused kernel (slot parse, grouping, the pad to 384 lanes, the result
takes), per timed packet retired in the traced window.  The step is each
run of a module named ``jit_packet_step``."""

import re

KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')


def read(ctx):
    if ctx.trace is None or not ctx.retired_in_window:
        return None
    s = ctx.trace.op_seconds(lambda t: KERNEL.search(t) is None,
                             within="jit_packet_step")
    return s / ctx.retired_in_window * 1e9 if s > 0 else None
