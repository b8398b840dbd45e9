"""kernel_roofline: the least time the chip needs for the window's ticks
(``work.least_time``: ops at the int8 peak or bytes at the HBM bandwidth,
whichever is longer, tick by tick) over the fused kernel's device time in
the traced window, in %.

On N chips the kernel's time is summed over them, so this is the share of
the N chips' combined roof, ``(least / N) / (spent / N)``, while the
program splits the tick's work evenly over them."""

from chipbench.metrics import kernel_ns_per_pkt


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.tick_work:
        return None
    spent = ctx.trace.op_seconds(kernel_ns_per_pkt.match)
    if spent <= 0:
        return None
    least, _ = ctx.work.least_time(ctx.cfg, ctx.peaks, ctx.tick_work)
    return 100.0 * least / spent
