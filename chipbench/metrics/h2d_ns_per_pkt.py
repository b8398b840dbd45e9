"""h2d_ns_per_pkt: host time in the runtime's ``dp.tick.h2d`` span (the
``jnp.asarray`` of each padded batch: its copy to the device), per timed
packet retired in the window.  Reads nothing where the runtime's spans
were not on."""

from chipbench.program_spans import per_packet


def read(ctx):
    return per_packet(ctx, ("dp.tick.h2d",))
