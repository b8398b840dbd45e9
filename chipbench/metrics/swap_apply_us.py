"""swap_apply_us: median over the window's epochs of the program's own
``EpochRecord.apply_us`` span (validate, apply and flip at the tick
boundary)."""


def read(ctx):
    return ctx.stats.median(ctx.apply_us) if ctx.apply_us else None
