"""Pure-jnp oracles for every Pallas kernel in this package.

Conventions
-----------
* Bit packing: a {+1,-1} vector is stored as uint32 words, little-endian
  within the word; bit ``b`` encodes value ``1 - 2b`` (bit 0 -> +1,
  bit 1 -> -1).
* ``d`` (input bits) must be a multiple of 32.
* The binary dot product of two +-1 vectors of length d packed as words
  x, w is ``d - 2 * popcount(x XOR w)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PACK = 32


# ---------------------------------------------------------------------------
# packing helpers (host + device safe)
# ---------------------------------------------------------------------------

def pack_bits(x_pm1: jnp.ndarray) -> jnp.ndarray:
    """Pack a (+1/-1) array of shape (..., d) into (..., d//32) uint32."""
    d = x_pm1.shape[-1]
    if d % PACK:
        raise ValueError(f"d={d} must be a multiple of {PACK}")
    bits = (x_pm1 < 0).astype(jnp.uint32)          # bit 1 <=> -1
    bits = bits.reshape(*x_pm1.shape[:-1], d // PACK, PACK)
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    return (bits << shifts).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(packed: jnp.ndarray, d: int) -> jnp.ndarray:
    """Inverse of pack_bits -> (+1/-1) int8 of shape (..., d)."""
    if d != packed.shape[-1] * PACK:
        raise ValueError("d mismatch")
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(*packed.shape[:-1], d)
    return (1 - 2 * bits.astype(jnp.int8)).astype(jnp.int8)


# ---------------------------------------------------------------------------
# kernel oracles
# ---------------------------------------------------------------------------

def expand_block_slots(block_slots: jnp.ndarray, block_b: int,
                       total: int) -> jnp.ndarray:
    """Broadcast per-block slot ids to per-row ids: (n_blocks,) -> (total,).

    The single home for the ``jnp.repeat(block_slots, block_b, ...)`` pattern
    the grouped oracles need (the fused Pallas path reads the block id from
    SMEM instead and never materializes this).
    """
    return jnp.repeat(block_slots, block_b, total_repeat_length=total)


def popcount32(v: jnp.ndarray) -> jnp.ndarray:
    """SWAR popcount over uint32 words -> int32 bit counts.

    Bit-identical to ``jax.lax.population_count`` but lowers to plain
    shift/mask/multiply ops, which XLA:CPU vectorizes noticeably better
    than its POPCNT expansion — the whole forwarding path is
    popcount-bound, so this is measurable end to end.  TPU keeps using
    ``population_count`` (VPU-native).
    """
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def xnor_matmul_ref(x_packed: jnp.ndarray, w_packed: jnp.ndarray) -> jnp.ndarray:
    """Binary matmul oracle.

    x_packed: (B, W) uint32, w_packed: (H, W) uint32 -> (B, H) int32 dot
    products of the underlying +-1 vectors of length d = W*32.
    """
    d = x_packed.shape[-1] * PACK
    xor = jnp.bitwise_xor(x_packed[:, None, :], w_packed[None, :, :])
    mism = popcount32(xor).sum(axis=-1)
    return jnp.int32(d) - 2 * mism


def tree_sum(v: jnp.ndarray) -> jnp.ndarray:
    """Sum over the last axis in one fixed order, keeping it as size 1.

    The lanes are folded in halves (``v[:n] + v[n:2n]``) after zero-padding
    to a power of two (adding 0.0 is exact).  Every backend then performs
    the same f32 additions in the same order, so the result is
    bit-identical on XLA:CPU, XLA:TPU and inside a Pallas kernel, where a
    plain ``jnp.sum`` is free to pick its own order."""
    n = v.shape[-1]
    width = 1 << (n - 1).bit_length()
    if width != n:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, width - n)])
    while width > 1:
        width //= 2
        v = v[..., :width] + v[..., width:2 * width]
    return v


def dense_pm1(h: jnp.ndarray, w2: jnp.ndarray, b2: jnp.ndarray) -> jnp.ndarray:
    """Layer 2 on +-1 activations: ``y[:, c] = sum_h h * w2[c] + b2[c]``.

    h: (B, H) in {+1, -1}; w2: (C, H) shared, or (B, C, H) / (1, C, H);
    b2 broadcastable to (B, C).  Elementwise products and a ``tree_sum``
    per output column, never a matmul: each product is exact and the
    additions run in one fixed order, so scores agree bit for bit across
    backends (an f32 matmul at default precision on the TPU would drop to
    bf16 passes).  The fused kernel calls this very function on its block,
    in a form the chip compiler accepts (a 3-D product summed over its last
    axis is refused there).
    """
    w2 = w2.reshape((-1,) + w2.shape[-2:])
    cols = [tree_sum(h * w2[:, j, :]) for j in range(w2.shape[1])]
    y = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=-1)
    return y + b2


def bnn_forward_ref(
    w1_packed: jnp.ndarray,  # (H, W) uint32
    b1: jnp.ndarray,         # (H,) float32
    w2: jnp.ndarray,         # (C, H) float32
    b2: jnp.ndarray,         # (C,) float32
    x_packed: jnp.ndarray,   # (B, W) uint32
) -> jnp.ndarray:
    """h = sign(W1 x + b1); y = W2 h + b2   (paper Eq. 1).  -> (B, C) f32."""
    pre = xnor_matmul_ref(x_packed, w1_packed).astype(jnp.float32) + b1[None, :]
    h = jnp.where(pre >= 0, 1.0, -1.0)
    return dense_pm1(h, w2, b2[None, :])


def banked_matmul_ref(
    x: jnp.ndarray,      # (B, D)
    w: jnp.ndarray,      # (K, D, H)
    b: jnp.ndarray,      # (K, H) or None
    slots: jnp.ndarray,  # (B,) int32
) -> jnp.ndarray:
    """Slot-selected matmul oracle: y[i] = x[i] @ w[slots[i]] + b[slots[i]]."""
    wg = w[slots]                       # (B, D, H)
    y = jnp.einsum("bd,bdh->bh", x, wg)
    if b is not None:
        y = y + b[slots]
    return y.astype(x.dtype)


def banked_xnor_forward_ref(
    bank_w1: jnp.ndarray,  # (K, H, W) uint32
    bank_b1: jnp.ndarray,  # (K, H) f32
    bank_w2: jnp.ndarray,  # (K, C, H) f32
    bank_b2: jnp.ndarray,  # (K, C) f32
    x_packed: jnp.ndarray, # (B, W) uint32
    slots: jnp.ndarray,    # (B,) int32
) -> jnp.ndarray:
    """Per-packet slot-selected BNN forward (gather strategy oracle)."""
    d = x_packed.shape[-1] * PACK
    w1g = bank_w1[slots]                              # (B, H, W)
    xor = jnp.bitwise_xor(x_packed[:, None, :], w1g)  # (B, H, W)
    mism = popcount32(xor).sum(axis=-1)
    pre = (jnp.int32(d) - 2 * mism).astype(jnp.float32) + bank_b1[slots]
    h = jnp.where(pre >= 0, 1.0, -1.0)                # (B, H)
    return dense_pm1(h, bank_w2[slots], bank_b2[slots])


# ---------------------------------------------------------------------------
# MXU-path oracle (beyond-paper TPU adaptation): unpack to +-1 bf16 and use
# the systolic array instead of VPU popcount.
# ---------------------------------------------------------------------------

def xnor_matmul_mxu_ref(x_packed: jnp.ndarray, w_packed: jnp.ndarray) -> jnp.ndarray:
    d = x_packed.shape[-1] * PACK
    xv = unpack_bits(x_packed, d).astype(jnp.bfloat16)
    wv = unpack_bits(w_packed, d).astype(jnp.bfloat16)
    return jnp.dot(xv, wv.T, preferred_element_type=jnp.float32).astype(jnp.int32)


def random_bnn_params(key, d_bits: int, hidden: int, n_out: int = 1):
    """Random single-slot BNN parameter set (packed)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    w1 = jnp.where(jax.random.bernoulli(k1, 0.5, (hidden, d_bits)), 1.0, -1.0)
    w1p = pack_bits(w1)
    b1 = jax.random.normal(k2, (hidden,), jnp.float32) * 8.0
    w2 = jax.random.normal(k3, (n_out, hidden), jnp.float32) / np.sqrt(hidden)
    b2 = jax.random.normal(k4, (n_out,), jnp.float32) * 0.1
    return {"w1p": w1p, "b1": b1, "w2": w2, "b2": b2}
