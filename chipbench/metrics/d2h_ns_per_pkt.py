"""d2h_ns_per_pkt: host time in the runtime's ``dp.retire.d2h`` span (the
slots, verdicts and actions pulled to the host), per timed packet
retired in the window.  Reads nothing where the runtime's spans were not
on."""

from chipbench.program_spans import per_packet


def read(ctx):
    return per_packet(ctx, ("dp.retire.d2h",))
