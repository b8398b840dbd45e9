"""throughput_pps: timed packets retired (their verdict reached the host)
inside the window, over the window's seconds (host clock)."""


def read(ctx):
    return ctx.retired_in_window / ctx.seconds
