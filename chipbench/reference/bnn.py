"""Plain reference of the BoundSwitch forwarding path, in jax.numpy float32.

For each packet (paper §II-B, Algorithm 1, Eq. 1):

    k  = clip(reg0 word 0, 0, K - 1)                          sigma
    x  = the 8,192 payload bits as +-1 (bit 1 means -1)
    h  = sign(W1[m] x + b1[m])   with sign(0) = +1            layer 1
    y  = w2[m] . h + b2[m]                                     layer 2
    a  = DROP if y > 0 and not monitor-only, FLAG if y > 0 and
         monitor-only, FORWARD otherwise                       Pi

where ``m`` is the model that slot ``k`` held when the packet was served.
Layer 1 runs as a float32 matmul of unpacked +-1 values, exact at
``highest`` precision (integers below 2**24); layer 2 as float32 products
and a sum.  The program sums layer 2 in another order, so a score within
``margin`` of 0 -- twice the float32 rounding bound of a sum of H + 1
terms -- has no decided sign, and its verdict is not judged.

``layer2="bfloat16"`` is the control: the same path with the layer-2
weights rounded to bfloat16, as a default-precision matmul on the TPU
would take them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ACTION_FORWARD, ACTION_DROP, ACTION_FLAG = 0, 1, 2


def unpack_pm1(words: jnp.ndarray) -> jnp.ndarray:
    """(..., W) uint32 -> (..., 32 W) float32 in {+1, -1}, bit j of word i
    is element 32 i + j."""
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    bits = bits.reshape(*words.shape[:-1], words.shape[-1] * 32)
    return 1.0 - 2.0 * bits.astype(jnp.float32)


def layer1_table(models: dict) -> jnp.ndarray:
    """(d_bits, M * H) float32 +-1 layer-1 weights of all M models."""
    w1 = unpack_pm1(models["w1p"])                 # (M, H, d)
    m, h, d = w1.shape
    return w1.reshape(m * h, d).T


@functools.partial(jax.jit, static_argnames=("meta_words", "layer2"))
def scores(rows, model, w1_table, b1, w2, b2, *, meta_words: int,
           layer2: str = "float32"):
    """Scores and undecided margins of packets ``rows`` (n, 272) uint32,
    each under model ``model`` (n,) of the stacked ``b1`` (M, H),
    ``w2`` (M, C, H), ``b2`` (M, C).  Returns ``(y, margin)``, (n,) each."""
    m, h = b1.shape
    x = unpack_pm1(rows[:, meta_words:])                       # (n, d)
    with jax.default_matmul_precision("highest"):
        pre_all = jnp.dot(x, w1_table)                          # (n, M*H)
    pre = jnp.take_along_axis(pre_all.reshape(-1, m, h),
                              model[:, None, None], axis=1)[:, 0]
    hid = jnp.where(pre + b1[model] >= 0, 1.0, -1.0)           # (n, H)
    w = w2[model, 0]                                           # (n, H)
    if layer2 == "bfloat16":
        # an explicit rounding op: a convert pair f32 -> bf16 -> f32 may be
        # folded away by the compiler under its excess-precision rule
        w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    elif layer2 != "float32":
        raise ValueError(f"unknown layer2 precision {layer2!r}")
    y = jnp.sum(hid * w, axis=1) + b2[model, 0]
    terms = jnp.sum(jnp.abs(w), axis=1) + jnp.abs(b2[model, 0])
    u = np.float32(2.0 ** -24)
    gamma = (h + 1) * u / (1 - (h + 1) * u)
    return y, 2 * gamma * terms


def sigma(rows: np.ndarray, num_slots: int) -> np.ndarray:
    return np.clip(rows[:, 0].astype(np.int64), 0, num_slots - 1)


def pi(verdicts: np.ndarray, rows: np.ndarray) -> np.ndarray:
    monitor = (rows[:, 2] & 1) != 0
    return np.where(verdicts, np.where(monitor, ACTION_FLAG, ACTION_DROP),
                    ACTION_FORWARD).astype(np.int32)
