"""device_idle: share of the traced window in which no op ran on the
device: 1 - (union of the ``XLA Ops`` intervals) / window, in %."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
