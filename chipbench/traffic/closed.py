"""Closed loop: keep every RSS ring backlogged, never drop.

Before each ``tick`` the generator offers bursts of ``queues *
burst_per_queue`` consecutive packets for as long as every ring has room
for a whole burst, so no burst can overflow a ring whatever the RSS hash
does with it, and the rings stay full whatever the program's batch is.
The window's rate is what the system completes: packets retired in the
window over its seconds.

Parameters (the mix's JSON file): ``burst_per_queue``, ``flows``,
``monitor_share``, ``warm_ticks`` (ticks run before the window, with
stamps outside the timed range, then drained).
"""

from __future__ import annotations

import numpy as np

from chipbench.harness import clock
from chipbench.traffic.packets import PACKET_WORDS

#: Stamps of warm-up packets start here, above any timed stamp.
WARM_BASE = 60_000_000


class Driver:
    def __init__(self, h, source, cfg: dict, traffic: dict, **_):
        self.h = h
        self.source = source
        self.burst = cfg["queues"] * traffic["burst_per_queue"]
        self.warm_ticks = traffic["warm_ticks"]
        self._buf = np.zeros((self.burst, PACKET_WORDS), np.uint32)

    def _fill(self, seq: int) -> int:
        h = self.h
        while min(r.free for r in h.rt.rings) >= self.burst:
            with h.span("generate"):
                rows = self.source.run(seq, self.burst, self._buf)
            h.dispatch(rows)
            seq += self.burst
        return seq

    def warm_up(self) -> None:
        seq = WARM_BASE
        for _ in range(self.warm_ticks):
            seq = self._fill(seq)
            self.h.tick()
        self.h.drain()

    def window(self, seconds: float) -> tuple[float, float]:
        """Run the timed window; returns its host-clock (start, end)."""
        h = self.h
        seq = 0
        t0 = clock()
        end = t0 + seconds
        while clock() < end:
            seq = self._fill(seq)
            h.tick()
        if seq >= self.source.capacity:
            raise RuntimeError("stamps ran past the payload pools")
        return t0, end

    def due(self, seqs: np.ndarray):
        """A closed loop has no due times."""
        return None
