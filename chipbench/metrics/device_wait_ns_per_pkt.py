"""device_wait_ns_per_pkt: host time in the runtime's ``dp.retire.wait``
span (``block_until_ready``: the host waiting for the device), per timed
packet retired in the window.  Reads nothing where the runtime's spans
were not on."""

from chipbench.program_spans import per_packet


def read(ctx):
    return per_packet(ctx, ("dp.retire.wait",))
