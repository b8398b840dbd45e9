"""ring_wait_us: mean time a row waits in its ring, from its enqueue
stamp to the pop that takes it, in us: the runtime's ``dp.ring_wait_ns``
counter over its ``dp.rows_popped``.  Reads nothing where the runtime's
spans were not on."""

from chipbench.program_spans import counter_ratio


def read(ctx):
    v = counter_ratio(ctx, "dp.ring_wait_ns", "dp.rows_popped")
    return None if v is None else v / 1e3
