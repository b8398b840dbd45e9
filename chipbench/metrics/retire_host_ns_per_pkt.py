"""retire_host_ns_per_pkt: the program's host time folding retired rows,
per timed packet retired in the window: the self time of the runtime's
``dp.retire.tap`` (the ``on_retire`` tap) and ``dp.retire.telemetry``
(counters, latency histogram, completion marks, delta stream) spans.
Reads nothing where the runtime's spans were not on."""

from chipbench.program_spans import per_packet


def read(ctx):
    return per_packet(ctx, ("dp.retire.tap", "dp.retire.telemetry"),
                      "self_ns")
