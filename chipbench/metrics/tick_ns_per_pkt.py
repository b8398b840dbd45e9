"""tick_ns_per_pkt: host seconds inside ``DataplaneRuntime.tick`` (pop,
pad, H2D, launch, block, D2H, telemetry, taps) over the window, per
timed packet retired in it (the harness's span around the call)."""


def read(ctx):
    s = ctx.spans.get("tick")
    if not s or not ctx.retired_in_window:
        return None
    return s[0] / ctx.retired_in_window * 1e9
