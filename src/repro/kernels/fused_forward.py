"""Fused packet-forwarding megakernel (parse -> select -> XNOR -> verdict).

The paper's per-packet numbers come from keeping the whole forwarding path
inline in one pass over the payload.  The staged TPU port split that path
across four XLA programs (layer-1 Pallas kernel, sign, layer-2 einsum, three
``jnp.repeat`` gathers) with HBM round trips between them.  This kernel runs
the complete executor in VMEM inside ONE ``pl.pallas_call``:

  * the per-block slot id is scalar-prefetched into SMEM (the O(1)
    pointer-chase analogue: one SMEM read steers the weight DMA at the
    selected bank entry; the K-1 non-selected slots never leave HBM),
  * layer 1 (XNOR-popcount), the sign activation, layer 2, and optionally
    the Pi action are computed on the block without touching HBM,
  * only the final ``(block_b, C)`` score tile (and the ``(block_b, 1)``
    action tile) is written back.

Two input modes:

  * **contiguous** (``row_ids is None``) — rows are already grouped so each
    ``block_b`` block shares one slot; the payload is streamed through the
    normal blocked-BlockSpec pipeline.
  * **gather** (``row_ids`` given) — the batch stays in HBM in its original
    arrival order (``memory_space=ANY``); a prefetched per-row index table
    drives a DMA gather prologue that copies exactly the rows of each block
    into VMEM scratch, so no ``scatter_padded``/``gather_padded`` grouped
    copy of the batch is made.  The TPU's DMA moves a row only as a whole
    tile of a multiple of 128 lanes, so the rows are first zero-padded to
    such a width (one XLA pad of the batch).  The prologue issues one row
    DMA at a time; a start-all, wait-all scheme could hide the latency.

``meta_words > 0`` means ``x`` rows are full packets (reg0 metadata followed
by payload words); the parse is then inline too — the kernel slices the
payload and reads the control word for the action, so nothing upstream has
to materialize a payload view.

The reg0 constants are mirrored from ``repro.core.packet`` (the kernels
package stays importable without the core layer); ``repro.core.pipeline``
asserts they agree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bnn_xnor import PACK, xnor_mismatches
from .ref import dense_pm1

# reg0 layout + Pi codes, mirrored from repro.core.packet.
CTRL_WORD = 2
CTRL_MONITOR_ONLY = 1
ACTION_FORWARD = 0
ACTION_DROP = 1
ACTION_FLAG = 2


def _pi(malicious: jnp.ndarray, ctrl_words: jnp.ndarray) -> jnp.ndarray:
    """Pi, elementwise: verdicts + uint32 control words -> i32 actions."""
    monitor = (ctrl_words & jnp.uint32(CTRL_MONITOR_ONLY)) != 0
    return jnp.where(
        malicious,
        jnp.where(monitor, ACTION_FLAG, ACTION_DROP),
        ACTION_FORWARD,
    ).astype(jnp.int32)


def actions_ref(scores: jnp.ndarray, ctrl_words: jnp.ndarray) -> jnp.ndarray:
    """Pi oracle on (B, C) scores + (B,) uint32 control words -> (B,) i32."""
    return _pi(scores[:, 0] > 0.0, ctrl_words)


def _bnn_block(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, *, meta_words, chunk,
               d_bits):
    """Full executor on one block: x_ref rows (meta + payload words) ->
    (block_b, C) f32 scores, entirely in VMEM.

    Layer 1 is integer popcount arithmetic, bit-exact against the oracle;
    layer 2 is the oracle's own ``dense_pm1`` (elementwise products and a
    lane sum per column; the chip compiler refuses a (block_b, H) x (H, C)
    dot followed by the bias add with "Lane broadcast")."""
    mism = xnor_mismatches(x_ref, w1_ref.at[0], x_off=meta_words,
                           w_words=d_bits // PACK, chunk=chunk)
    pre = (jnp.int32(d_bits) - 2 * mism).astype(jnp.float32) + b1_ref[0]
    h = jnp.where(pre >= 0, 1.0, -1.0)
    return dense_pm1(h, w2_ref[...], b2_ref[0])


def _emit(x_ref, y, out_refs, with_actions):
    out_refs[0][...] = y
    if with_actions:
        ctrl = x_ref[:, CTRL_WORD:CTRL_WORD + 1]            # (block_b, 1)
        out_refs[1][...] = _pi(y[:, :1] > 0.0, ctrl)


def _fused_contig_kernel(slots_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                         *out_refs, meta_words, chunk, d_bits, with_actions):
    del slots_ref  # consumed by the index_maps, not the body
    y = _bnn_block(x_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                   meta_words=meta_words, chunk=chunk, d_bits=d_bits)
    _emit(x_ref, y, out_refs, with_actions)


def _fused_gather_kernel(slots_ref, rows_ref, x_hbm, w1_ref, b1_ref, w2_ref,
                         b2_ref, *out_refs_and_scratch, meta_words, chunk,
                         d_bits, with_actions):
    del slots_ref
    *out_refs, x_rows, x_vmem, sem = out_refs_and_scratch
    i = pl.program_id(0)
    bb = out_refs[0].shape[0]

    def copy_row(r, carry):
        src = rows_ref[i * bb + r]
        cp = pltpu.make_async_copy(x_hbm.at[src], x_rows.at[r], sem)
        cp.start()
        cp.wait()
        return carry

    jax.lax.fori_loop(0, bb, copy_row, 0)
    x_vmem[...] = x_rows[:, 0, :]
    y = _bnn_block(x_vmem, w1_ref, b1_ref, w2_ref, b2_ref,
                   meta_words=meta_words, chunk=chunk, d_bits=d_bits)
    _emit(x_vmem, y, out_refs, with_actions)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "chunk", "interpret", "meta_words",
                     "with_actions"),
)
def fused_forward(
    x: jnp.ndarray,            # (B, meta_words + W) uint32 rows
    bank_w1: jnp.ndarray,      # (K, H, W) uint32
    bank_b1: jnp.ndarray,      # (K, H) f32
    bank_w2: jnp.ndarray,      # (K, C, H) f32
    bank_b2: jnp.ndarray,      # (K, C) f32
    block_slots: jnp.ndarray,  # (n_blocks,) i32 — one slot per output block
    row_ids: jnp.ndarray | None = None,  # (n_blocks * block_b,) i32 gather map
    *,
    block_b: int = 256,
    chunk: int = 128,
    interpret: bool = False,
    meta_words: int = 0,
    with_actions: bool = False,
):
    """One-launch fused forwarding path.

    Returns ``(n_blocks * block_b, C)`` f32 scores, plus a
    ``(n_blocks * block_b, 1)`` i32 action tile when ``with_actions``.
    Output row r belongs to input row ``row_ids[r]`` (gather mode) or row r
    (contiguous mode).
    """
    total_words = x.shape[-1]
    w_words = total_words - meta_words
    k, h, ww = bank_w1.shape
    c = bank_w2.shape[1]
    if ww != w_words:
        raise ValueError(f"payload words {w_words} != bank words {ww}")
    if bank_b1.shape != (k, h) or bank_w2.shape != (k, c, h) \
            or bank_b2.shape != (k, c):
        raise ValueError("bank shape mismatch")
    if with_actions and meta_words <= CTRL_WORD:
        raise ValueError("with_actions requires metadata words in x")
    n_blocks = block_slots.shape[0]
    n_rows = n_blocks * block_b
    chunk = min(chunk, w_words)
    if w_words % chunk:
        raise ValueError(f"chunk={chunk} must divide payload words {w_words}")

    d_bits = w_words * PACK
    kern_kwargs = dict(meta_words=meta_words, chunk=chunk, d_bits=d_bits,
                       with_actions=with_actions)
    out_shape = [jax.ShapeDtypeStruct((n_rows, c), jnp.float32)]
    out_specs = [pl.BlockSpec((block_b, c), lambda i, *_: (i, 0))]
    if with_actions:
        out_shape.append(jax.ShapeDtypeStruct((n_rows, 1), jnp.int32))
        out_specs.append(pl.BlockSpec((block_b, 1), lambda i, *_: (i, 0)))

    # Biases ride as (K, 1, n) so each slot's block is the whole trailing
    # (1, n) tile: the TPU lowering refuses a (1, n) block cut from (K, n).
    bank_b1 = bank_b1.reshape(k, 1, h)
    bank_b2 = bank_b2.reshape(k, 1, c)
    bank_specs = [
        pl.BlockSpec((1, h, w_words), lambda i, s, *_: (s[i], 0, 0)),
        pl.BlockSpec((1, 1, h), lambda i, s, *_: (s[i], 0, 0)),
        pl.BlockSpec((1, c, h), lambda i, s, *_: (s[i], 0, 0)),
        pl.BlockSpec((1, 1, c), lambda i, s, *_: (s[i], 0, 0)),
    ]

    if row_ids is None:
        if x.shape[0] != n_rows:
            raise ValueError(
                f"contiguous mode needs B={n_rows} rows, got {x.shape[0]}")
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((block_b, total_words),
                                   lambda i, s: (i, 0))] + bank_specs,
            out_specs=out_specs,
        )
        kernel = functools.partial(_fused_contig_kernel, **kern_kwargs)
        operands = (block_slots, x, bank_w1, bank_b1, bank_w2, bank_b2)
    else:
        if row_ids.shape != (n_rows,):
            raise ValueError(f"row_ids must be ({n_rows},), got {row_ids.shape}")
        # The chip's DMA moves one row only as a whole (1, lanes) tile
        # whose lane count is a multiple of 128: view the rows as
        # (B, 1, lanes), zero-padded past ``total_words``.
        lanes = -(-total_words // 128) * 128
        x_tiles = jnp.pad(x, ((0, 0), (0, lanes - total_words))).reshape(
            x.shape[0], 1, lanes)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + bank_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block_b, 1, lanes), jnp.uint32),
                pltpu.VMEM((block_b, lanes), jnp.uint32),
                pltpu.SemaphoreType.DMA,
            ],
        )
        kernel = functools.partial(_fused_gather_kernel, **kern_kwargs)
        operands = (block_slots, row_ids.astype(jnp.int32), x_tiles,
                    bank_w1, bank_b1, bank_w2, bank_b2)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_forward",
    )(*operands)
    return tuple(out) if with_actions else out[0]
