"""Pallas TPU kernel: bit-packed XNOR-popcount binary matmul (paper Eq. 1, layer 1).

This is the TPU-native adaptation of BoundSwitch's AVX-512 executor.  The
x86 design loads sixteen 64-byte payload blocks into ZMM registers and runs
XNOR + VPOPCNT accumulation.  On TPU:

* the payload lives as uint32 words; a (block_b, W) tile of packets and a
  (block_h, W) tile of weight rows are staged into VMEM via BlockSpecs,
* the VPU computes ``popcount(x XOR w)`` on (8, 128)-lane int32 vectors,
* accumulation runs over W in chunks so the broadcast intermediate
  (block_b, block_h, chunk) stays comfortably inside VMEM.  The chunk loop
  is unrolled at trace time: every lane offset is then static, which the
  chip compiler needs (it refuses a dynamic lane offset it cannot prove
  128-aligned, and the fused kernel's payload starts 16 words into a row).

Grid: (B / block_b, H / block_h).  Each grid cell writes a (block_b, block_h)
int32 tile of binary dot products ``d - 2 * mismatches``.

VMEM budget at the default production blocking (block_b=128, block_h=32,
chunk=128, W=256 for the paper's 1024-byte payload):
  x tile 128*256*4 = 128 KiB, w tile 32*256*4 = 32 KiB,
  xor intermediate 128*32*128*4 = 2 MiB, out tile 16 KiB  -> ~2.2 MiB << VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PACK = 32


def xnor_mismatches(x_ref, w_ref, *, x_off: int, w_words: int, chunk: int):
    """``sum popcount(x XOR w)`` for every (row, hidden) pair: (rows, H) i32.

    ``x_ref`` rows hold the packed input at lanes ``[x_off, x_off +
    w_words)``; ``w_ref`` is (H, w_words).  The chunk loop is a Python
    loop, so every slice below has static bounds."""
    mism = None
    for lo in range(0, w_words, chunk):
        xs = x_ref[:, x_off + lo:x_off + lo + chunk]      # (rows, chunk)
        ws = w_ref[:, lo:lo + chunk]                       # (H, chunk)
        xor = jnp.bitwise_xor(xs[:, None, :], ws[None, :, :])
        pc = jax.lax.population_count(xor).astype(jnp.int32).sum(axis=-1)
        mism = pc if mism is None else mism + pc
    return mism


def _xnor_kernel(x_ref, w_ref, o_ref, *, d_bits: int, chunk: int):
    """x_ref: (bB, W) uint32; w_ref: (bH, W) uint32; o_ref: (bB, bH) int32."""
    mism = xnor_mismatches(x_ref, w_ref, x_off=0, w_words=x_ref.shape[-1],
                           chunk=chunk)
    o_ref[...] = jnp.int32(d_bits) - 2 * mism


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_h", "chunk", "interpret")
)
def xnor_matmul(
    x_packed: jnp.ndarray,   # (B, W) uint32
    w_packed: jnp.ndarray,   # (H, W) uint32
    *,
    block_b: int = 128,
    block_h: int = 32,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Binary matmul: (B, W) x (H, W) -> (B, H) int32 +-1 dot products."""
    b, w_words = x_packed.shape
    h = w_packed.shape[0]
    if w_packed.shape[1] != w_words:
        raise ValueError("word-count mismatch between x and w")
    block_b = min(block_b, b)
    block_h = min(block_h, h)
    chunk = min(chunk, w_words)
    if b % block_b or h % block_h or w_words % chunk:
        raise ValueError(
            f"shapes (B={b}, H={h}, W={w_words}) must divide blocks "
            f"({block_b}, {block_h}, chunk={chunk})"
        )
    d_bits = w_words * PACK
    kernel = functools.partial(_xnor_kernel, d_bits=d_bits, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(b // block_b, h // block_h),
        in_specs=[
            pl.BlockSpec((block_b, w_words), lambda i, j: (i, 0)),
            pl.BlockSpec((block_h, w_words), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_h), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, h), jnp.int32),
        interpret=interpret,
        name="xnor_matmul",
    )(x_packed, w_packed)
