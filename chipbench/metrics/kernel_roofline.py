"""kernel_roofline: the least time the chip needs for the window's ticks
(``work.least_time``: ops at the int8 peak or bytes at the HBM bandwidth,
whichever is longer, tick by tick) over the fused kernel's device time in
the traced window, in %."""

from chipbench.metrics import kernel_ns_per_pkt


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.tick_work:
        return None
    spent = ctx.trace.op_seconds(kernel_ns_per_pkt.match)
    if spent <= 0:
        return None
    least, _ = ctx.work.least_time(ctx.cfg, ctx.peaks, ctx.tick_work)
    return 100.0 * least / spent
