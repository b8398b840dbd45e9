"""swap_submit_us: median host time of the harness's ``control.submit``
calls in the window (the epoch is queued and its SwapSlot params are
staged into the shadow bank: a 32 KiB H2D copy and a donated update)."""


def read(ctx):
    return ctx.stats.median(ctx.submit_us) if ctx.submit_us else None
