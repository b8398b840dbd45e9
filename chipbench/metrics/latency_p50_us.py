"""latency_p50_us: median over all timed packets of the time from the
packet's due time to its verdict reaching the host (host clock)."""


def read(ctx):
    return None if ctx.latency_us is None else ctx.stats.percentile(ctx.latency_us, 50)
