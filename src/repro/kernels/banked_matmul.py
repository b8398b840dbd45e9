"""Pallas TPU kernel: scalar-prefetch banked matmul — the paper's O(1) slot
selection, TPU-native.

BoundSwitch resolves the active model by reading a 4-byte slot id from reg0
and chasing one pointer into the resident bank.  The TPU analogue is scalar
prefetch: per-block slot ids are staged into SMEM *before* the grid runs, and
the weight BlockSpec's ``index_map`` reads them to steer the DMA engine at
the slot'th bank entry.  Selection therefore costs one SMEM read per block —
no gather materialization, no recompilation, and the non-selected K-1 slots
are never moved out of HBM.

Contract: packets/requests are pre-grouped so each block of ``block_b``
consecutive rows shares one slot (see ``repro.core.bank.group_by_slot``).
The ungrouped oracles in ``ref.py`` keep exact per-row granularity for
validation.

Also hosts the banked BNN layer-1 variant (uint32 XNOR words instead of a
float matmul) so the *entire* paper executor can run slot-selected inside
one kernel family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 32


# ---------------------------------------------------------------------------
# float banked matmul: y[i] = x[i] @ W[slot_of_block(i)] (+ b)
# ---------------------------------------------------------------------------

def _banked_kernel(slots_ref, x_ref, w_ref, b_ref, o_ref):
    del slots_ref  # consumed by the index_map, not the body
    y = jnp.dot(
        x_ref[...], w_ref[0], preferred_element_type=jnp.float32
    )
    o_ref[...] = (y + b_ref[0][None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def banked_matmul(
    x: jnp.ndarray,            # (B, D)
    w: jnp.ndarray,            # (K, D, H)
    b: jnp.ndarray,            # (K, H)
    block_slots: jnp.ndarray,  # (B // block_b,) int32 — one slot per block
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    bsz, d = x.shape
    k, dw, h = w.shape
    if dw != d or b.shape != (k, h):
        raise ValueError(f"bank shape mismatch: x {x.shape}, w {w.shape}, b {b.shape}")
    block_b = min(block_b, bsz)
    if bsz % block_b:
        raise ValueError(f"B={bsz} must divide block_b={block_b}")
    n_blocks = bsz // block_b
    if block_slots.shape != (n_blocks,):
        raise ValueError(f"block_slots must be ({n_blocks},), got {block_slots.shape}")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, s: (i, 0)),
            pl.BlockSpec((1, d, h), lambda i, s: (s[i], 0, 0)),
            pl.BlockSpec((1, h), lambda i, s: (s[i], 0)),
        ],
        out_specs=pl.BlockSpec((block_b, h), lambda i, s: (i, 0)),
    )
    return pl.pallas_call(
        _banked_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h), x.dtype),
        interpret=interpret,
        name="banked_matmul",
    )(block_slots, x, w, b)


# ---------------------------------------------------------------------------
# banked BNN layer 1: slot-selected XNOR-popcount
# ---------------------------------------------------------------------------

def _banked_xnor_kernel(slots_ref, x_ref, w_ref, b1_ref, o_ref, *, d_bits, chunk):
    del slots_ref
    w_words = x_ref.shape[-1]
    n_chunks = w_words // chunk
    n_hidden = w_ref.shape[1]

    def body(c, acc):
        xs = x_ref[:, pl.ds(c * chunk, chunk)]
        ws = w_ref[0, :, pl.ds(c * chunk, chunk)]  # selected slot's weights
        xor = jnp.bitwise_xor(xs[:, None, :], ws[None, :, :])
        return acc + jax.lax.population_count(xor).astype(jnp.int32).sum(axis=-1)

    mism = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros((x_ref.shape[0], n_hidden), jnp.int32)
    )
    pre = (jnp.int32(d_bits) - 2 * mism).astype(jnp.float32) + b1_ref[0, :][None, :]
    o_ref[...] = pre


@functools.partial(jax.jit, static_argnames=("block_b", "chunk", "interpret"))
def banked_xnor_layer1(
    x_packed: jnp.ndarray,     # (B, W) uint32
    bank_w1: jnp.ndarray,      # (K, H, W) uint32
    bank_b1: jnp.ndarray,      # (K, H) f32
    block_slots: jnp.ndarray,  # (B // block_b,) int32
    *,
    block_b: int = 256,
    chunk: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Slot-selected layer-1 pre-activations (float32, bias added)."""
    bsz, w_words = x_packed.shape
    k, h, ww = bank_w1.shape
    if ww != w_words or bank_b1.shape != (k, h):
        raise ValueError("bank shape mismatch")
    block_b = min(block_b, bsz)
    chunk = min(chunk, w_words)
    if bsz % block_b or w_words % chunk:
        raise ValueError("blocking must divide shapes")
    n_blocks = bsz // block_b
    if block_slots.shape != (n_blocks,):
        raise ValueError(f"block_slots must be ({n_blocks},)")

    kernel = functools.partial(_banked_xnor_kernel, d_bits=w_words * PACK, chunk=chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_b, w_words), lambda i, s: (i, 0)),
            pl.BlockSpec((1, h, w_words), lambda i, s: (s[i], 0, 0)),
            pl.BlockSpec((1, h), lambda i, s: (s[i], 0)),
        ],
        out_specs=pl.BlockSpec((block_b, h), lambda i, s: (i, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h), jnp.float32),
        interpret=interpret,
        name="banked_xnor_layer1",
    )(block_slots, x_packed, bank_w1, bank_b1)
