"""kernel_rows_per_pkt: rows the served kernel's grid computes per real
packet: the runtime's ``dp.kernel_rows`` counter (the slot-grouped
padding of each launch, ``core.bank.padded_rows``) over its
``dp.rows_popped``.  Reads nothing where the runtime's spans were not
on."""

from chipbench.program_spans import counter_ratio


def read(ctx):
    return counter_ratio(ctx, "dp.kernel_rows", "dp.rows_popped")
