"""swap_p99_us: 99th percentile over the window's SwapSlot epochs of the
time from the harness's ``submit`` call to the return of the runtime call
at whose entry the epoch applied (host clock)."""


def read(ctx):
    return ctx.stats.percentile(ctx.swap_us, 99) if ctx.swap_us else None
