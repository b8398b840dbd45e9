"""gen_late_p99_us: 99th percentile of how late the load generator
dispatched packets after their due time (host clock).  A starved
generator shows here, not as a fast system."""


def read(ctx):
    return None if ctx.late_us is None else ctx.stats.percentile(ctx.late_us, 99)
