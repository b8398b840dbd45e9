"""tick_prep_ns_per_pkt: the program's host time preparing each tick,
per timed packet retired in the window: the self time of the runtime's
``dp.tick.control`` (control epochs and routing policy), ``dp.tick.pop``
(the ring pops) and ``dp.tick.pad`` (each batch padded to its static
shape) spans.  Reads nothing where the runtime's spans were not on."""

from chipbench.program_spans import per_packet


def read(ctx):
    return per_packet(ctx, ("dp.tick.control", "dp.tick.pop", "dp.tick.pad"),
                      "self_ns")
