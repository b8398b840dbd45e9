"""launch_ns_per_pkt: host time in the runtime's ``dp.tick.launch`` span
(the ``packet_step`` call, until it returns its unfinished results), per
timed packet retired in the window.  Reads nothing where the runtime's
spans were not on."""

from chipbench.program_spans import per_packet


def read(ctx):
    return per_packet(ctx, ("dp.tick.launch",))
