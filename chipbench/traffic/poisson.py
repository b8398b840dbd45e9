"""Open loop: Poisson arrivals at a fixed rate, optionally with SwapSlot
epochs on a fixed period.

The window's ``N = rate * seconds`` arrivals are spaced by the exponential
distribution's quantiles at ``(i + 1/2) / N``, scaled to fill the window
and put in an order drawn from the seed: every seed offers the same set of
gaps, and only their order changes.  The loop polls and never sleeps: it
dispatches every packet that is due as one burst, ticks while a ring holds
packets, and otherwise spins until the next due time.  Each packet's
latency runs from its due time to its verdict reaching the host, and the
lateness of its dispatch is kept apart.

With ``swap_interval_s`` set, epoch ``j`` is due at ``j * swap_interval_s``
and installs swap model ``j % swap_models`` into slot ``j % K``; it is
submitted just before the dispatch of the packets due from then on, and
applies at the entry of that call.

Parameters (the mix's JSON file): ``rate_pps``, ``flows``,
``monitor_share``, ``warm_seconds``; ``swap_interval_s`` and
``swap_models`` for the swap mix.
"""

from __future__ import annotations

import numpy as np

from chipbench.harness import clock

#: Stamps of warm-up packets start here, above any timed stamp.
WARM_BASE = 60_000_000
ORDER_STREAM = 4


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of the window's arrivals."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2**64, ORDER_STREAM]))
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


class Driver:
    def __init__(self, h, source, cfg: dict, traffic: dict, *, seed: int,
                 swap_params=(), resident_params=()):
        self.h = h
        self.source = source
        self.rate = float(traffic["rate_pps"])
        self.warm_seconds = float(traffic["warm_seconds"])
        self.interval = traffic.get("swap_interval_s")
        self.slots = cfg["slots"]
        self.seed = seed
        self.swap_params = list(swap_params)
        self.resident_params = list(resident_params)
        self.due_abs = np.zeros(0)
        self.backlog: list[tuple] = []   # (t, rows waiting) after each dispatch

    def _loop(self, due: np.ndarray, swaps: np.ndarray, first_seq: int,
              swap_at) -> None:
        """Serve arrivals ``due`` (host clock) and epochs ``swaps`` (host
        clock; ``swap_at(j)`` submits epoch j)."""
        h = self.h
        n, m = due.shape[0], swaps.shape[0]
        i = j = 0
        while i < n or j < m:
            now = clock()
            if j < m and swaps[j] <= now:
                k = int(np.searchsorted(due, swaps[j], "left"))
                if k > i:
                    self._offer(due, i, k, first_seq)
                    i = k
                swap_at(j)
                j += 1
                continue
            k = int(np.searchsorted(due, now, "right"))
            if k > i:
                self._offer(due, i, k, first_seq)
                i = k
            if h.waiting():
                h.tick()
                continue
            if h.in_flight():
                h.flush()
                continue
            nxt = min(due[i] if i < n else np.inf, swaps[j] if j < m else np.inf)
            with h.span("poll"):
                while clock() < nxt:
                    pass

    def _offer(self, due, i, k, first_seq):
        h = self.h
        with h.span("generate"):
            rows = self.source.run(first_seq + i, k - i)
        h.dispatch(rows, due[i:k])
        self.backlog.append((clock(), h.waiting()))

    def warm_up(self) -> None:
        """A short stretch of the same traffic, with stamps outside the
        timed range, and -- in the swap mix -- two epochs that put resident
        models back into their own slots, so the staging programs are
        compiled and the bank is as the seed made it."""
        t0 = clock()
        due = t0 + arrivals(self.rate, self.warm_seconds, self.seed)
        swaps = np.zeros(0)
        if self.interval:
            swaps = t0 + np.array([0.25, 0.75]) * self.warm_seconds

        def reinstall(j):
            k = j % self.slots
            self.h.submit(k, self.resident_params[k], k)

        self._loop(due, swaps, WARM_BASE, reinstall)
        self.h.drain()

    def window(self, seconds: float) -> tuple[float, float]:
        h = self.h
        t0 = clock()
        self.due_abs = t0 + arrivals(self.rate, seconds, self.seed)
        swaps = np.zeros(0)
        if self.interval:
            swaps = t0 + np.arange(int(round(seconds / self.interval))) \
                * self.interval
        nswap = len(self.swap_params)

        def swap(j):
            h.submit(j % self.slots, self.swap_params[j % nswap],
                     self.slots + j % nswap)

        self._loop(self.due_abs, swaps, 0, swap)
        return t0, t0 + seconds

    def due(self, seqs: np.ndarray):
        """Host-clock due time of timed stamps."""
        return self.due_abs[seqs]
