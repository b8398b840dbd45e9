"""Packets of the BoundSwitch format, made from a seed and a sequence stamp.

A packet is 17 64-byte register blocks, 272 uint32 words (paper §II-B):

    word 0        slot id (sigma reads it)
    word 1        format version (1)
    word 2        control word; bit 0 is the monitor-only bit Pi reads
    words 4..7    flow tuple (the RSS hash input)
    word 15       sequence stamp
    words 16..271 the 1,024-byte payload

Everything about packet ``n`` follows from ``(seed, n)`` alone, so the
harness keeps only stamps during a run and remakes any packet after it:

* slot and monitor bit come from a counter hash of ``n``: slots are
  uniform over the K slots, and ``monitor_share`` of packets carry the
  monitor-only bit;
* the flow table holds random tuples that fill the RSS hash buckets
  evenly (``rss.balanced_flows``), and packet ``n`` belongs to flow
  ``perm[n % flows]`` for a seeded permutation, so any ``flows``
  consecutive packets visit every flow once;
* the payload is ``A[n % NA] ^ B[n % NB]`` for two seeded pools of
  coprime sizes: distinct for every ``n < NA * NB``, and two packets share
  a run of trailing payload words only by a chance of about 2**-64 per
  pair for runs of two words or more.
"""

from __future__ import annotations

import numpy as np

from chipbench.traffic import rss

PACKET_WORDS = 272
META_WORDS = 16
SLOT_WORD, VERSION_WORD, CONTROL_WORD, SEQ_WORD = 0, 1, 2, 15
FLOW_WORD, FLOW_WORDS = 4, 4
FORMAT_VERSION = 1
MONITOR_ONLY = 1

POOL_A, POOL_B = 8192, 8191
TRAFFIC_STREAM = 3

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)


def _splitmix(x: np.ndarray) -> np.ndarray:
    z = x + _GOLDEN
    z = (z ^ (z >> np.uint64(30))) * _M1
    z = (z ^ (z >> np.uint64(27))) * _M2
    return z ^ (z >> np.uint64(31))


class PacketSource:
    """Remakes packet ``n`` of a seeded stream."""

    def __init__(self, *, slots: int, flows: int, monitor_share: float,
                 seed: int, rss_buckets: int = 128,
                 payload_words: int = PACKET_WORDS - META_WORDS):
        if payload_words != PACKET_WORDS - META_WORDS:
            raise ValueError("the packet format carries 256 payload words")
        ss = np.random.SeedSequence([int(seed) % 2**64, TRAFFIC_STREAM])
        rng = np.random.default_rng(ss)
        self.slots = int(slots)
        self.flows = int(flows)
        self._monitor_per_mille = int(round(monitor_share * 1000))
        self._salt = np.uint64(ss.generate_state(1, np.uint64)[0])
        self.flow_table = rss.balanced_flows(rng, self.flows, FLOW_WORDS,
                                             rss_buckets)
        self._flow_perm = rng.permutation(self.flows)
        self.pool_a = rng.integers(0, 2**32, (POOL_A, payload_words),
                                   dtype=np.uint32)
        self.pool_b = rng.integers(0, 2**32, (POOL_B, payload_words),
                                   dtype=np.uint32)

    @property
    def capacity(self) -> int:
        """Stamps below this have distinct payloads."""
        return POOL_A * POOL_B

    def attributes(self, seqs: np.ndarray):
        """(slot, flow index, monitor bit) of each stamp."""
        with np.errstate(over="ignore"):
            h = _splitmix(np.asarray(seqs, np.uint64) ^ self._salt)
        slot = (h % np.uint64(self.slots)).astype(np.uint32)
        flow = self._flow_perm[np.asarray(seqs, np.int64) % self.flows]
        mon = ((h >> np.uint64(40)) % np.uint64(1000)
               < np.uint64(self._monitor_per_mille)).astype(np.uint32)
        return slot, flow, mon

    def packets(self, seqs: np.ndarray,
                out: np.ndarray | None = None) -> np.ndarray:
        """(n, 272) uint32 rows for the stamps ``seqs``, written into
        ``out`` when given (a reused buffer saves the allocation)."""
        seqs = np.asarray(seqs, np.int64)
        n = seqs.shape[0]
        if out is None:
            out = np.zeros((n, PACKET_WORDS), np.uint32)
        else:
            out = out[:n]
            out[:, :META_WORDS] = 0
        slot, flow, mon = self.attributes(seqs)
        out[:, SLOT_WORD] = slot
        out[:, VERSION_WORD] = FORMAT_VERSION
        out[:, CONTROL_WORD] = mon * MONITOR_ONLY
        out[:, FLOW_WORD:FLOW_WORD + FLOW_WORDS] = self.flow_table[flow]
        out[:, SEQ_WORD] = seqs.astype(np.uint32)
        if n and seqs[-1] - seqs[0] == n - 1 and np.all(np.diff(seqs) == 1):
            self._payload_run(out[:, META_WORDS:], int(seqs[0]), n)
        else:
            np.bitwise_xor(self.pool_a[seqs % POOL_A],
                           self.pool_b[seqs % POOL_B], out=out[:, META_WORDS:])
        return out

    def run(self, first: int, n: int,
            out: np.ndarray | None = None) -> np.ndarray:
        """Rows for the consecutive stamps ``first .. first + n - 1``."""
        return self.packets(np.arange(first, first + n, dtype=np.int64), out)

    def _payload_run(self, out: np.ndarray, first: int, n: int) -> None:
        """XOR of two pool slices, split where either pool wraps."""
        done = 0
        while done < n:
            a = (first + done) % POOL_A
            b = (first + done) % POOL_B
            m = min(n - done, POOL_A - a, POOL_B - b)
            np.bitwise_xor(self.pool_a[a:a + m], self.pool_b[b:b + m],
                           out=out[done:done + m])
            done += m
