"""dispatch_ns_per_pkt: host seconds inside ``DataplaneRuntime.dispatch``
(RSS hash, RETA, ring push) over the window, per timed packet offered
(the harness's span around the call)."""


def read(ctx):
    s = ctx.spans.get("dispatch")
    if not s or not ctx.offered:
        return None
    return s[0] / ctx.offered * 1e9
