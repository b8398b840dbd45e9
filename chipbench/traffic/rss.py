"""Flow tables whose RSS hash buckets are evenly filled.

A NIC hashes each packet's flow tuple with the Toeplitz function under the
Microsoft reference key and takes the hash's low bits as a bucket of its
indirection table.  A uniform random table of a few thousand flows fills
the buckets unevenly, and which queue gets the most then changes with the
seed: the closed loop is gated by its fullest ring, so the work of a run
would change with the seed.  ``balanced_flows`` draws random tuples and
keeps exactly ``flows / buckets`` of them per bucket, so every seed offers
every queue of an even indirection table the same share.
"""

from __future__ import annotations

import numpy as np

#: The Microsoft reference RSS key (40 bytes).
MS_KEY = bytes((
    0x6D, 0x5A, 0x56, 0xDA, 0x25, 0x5B, 0x0E, 0xC2,
    0x41, 0x67, 0x25, 0x3D, 0x43, 0xA3, 0x8F, 0xB0,
    0xD0, 0xCA, 0x2B, 0xCB, 0xAE, 0x7B, 0x30, 0xB4,
    0x77, 0xCB, 0x2D, 0xA3, 0x80, 0x30, 0xF2, 0x0C,
    0x6A, 0x42, 0xB7, 0x3B, 0xBE, 0xAC, 0x01, 0xFA))


def toeplitz(words: np.ndarray, key: bytes = MS_KEY) -> np.ndarray:
    """(n, F) uint32 words, read big-endian MSB first -> (n,) uint32."""
    words = np.ascontiguousarray(words, np.uint32)
    n_bits = words.shape[1] * 32
    k = int.from_bytes(key, "big")
    top = len(key) * 8 - 32
    windows = np.array([(k >> (top - j)) & 0xFFFFFFFF for j in range(n_bits)],
                       np.uint32)
    bits = np.unpackbits(words.astype(">u4").view(np.uint8), axis=1)
    return np.bitwise_xor.reduce(
        np.where(bits.astype(bool), windows, np.uint32(0)), axis=1)


def balanced_flows(rng: np.random.Generator, flows: int, words: int,
                   buckets: int) -> np.ndarray:
    """``flows`` random (words,) uint32 tuples, ``flows // buckets`` per
    hash bucket (``hash % buckets``), in bucket-interleaved order."""
    if flows % buckets:
        raise ValueError(f"{flows} flows do not fill {buckets} buckets evenly")
    per = flows // buckets
    kept = [np.empty((0, words), np.uint32) for _ in range(buckets)]
    while min(len(k) for k in kept) < per:
        cand = rng.integers(0, 2**32, (4 * flows, words), dtype=np.uint32)
        b = toeplitz(cand) % np.uint32(buckets)
        for i in range(buckets):
            if len(kept[i]) < per:
                kept[i] = np.concatenate([kept[i], cand[b == i]])[:per]
    return np.stack(kept, axis=1).reshape(flows, words)
