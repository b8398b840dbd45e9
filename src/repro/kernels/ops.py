"""Jitted public wrappers over the Pallas kernels with oracle fallbacks.

Backend selection:
  * ``pallas``    — compiled Pallas kernel (TPU target; ``interpret=True``
                    under tests on CPU).
  * ``ref``       — pure-jnp oracle (fast on CPU; bit-identical semantics).
  * ``mxu``       — beyond-paper path: unpack bits to +-1 bf16 and contract
                    on the MXU instead of VPU popcount.
  * ``auto``      — ``pallas`` on TPU, ``ref`` elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from . import bnn_xnor as _bnn_xnor
from . import banked_matmul as _banked
from . import fused_forward as _fused


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode: only off the TPU
    (tests on the CPU).  On the TPU they are always compiled by Mosaic."""
    return not _on_tpu()


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if _on_tpu() else "ref"
    return backend


# ---------------------------------------------------------------------------
# binary (XNOR-popcount) matmul
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend",))
def xnor_matmul(x_packed, w_packed, *, backend: str = "auto"):
    """(B, W)u32 x (H, W)u32 -> (B, H)i32 binary dot products."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.xnor_matmul_ref(x_packed, w_packed)
    if backend == "mxu":
        return _ref.xnor_matmul_mxu_ref(x_packed, w_packed)
    return _bnn_xnor.xnor_matmul(
        x_packed, w_packed, interpret=interpret_mode()
    )


@functools.partial(jax.jit, static_argnames=("backend",))
def bnn_forward(params, x_packed, *, backend: str = "auto"):
    """Single-slot BNN forward (paper Eq. 1): -> (B, C) f32 scores."""
    pre = xnor_matmul(x_packed, params["w1p"], backend=backend).astype(jnp.float32)
    pre = pre + params["b1"][None, :]
    h = jnp.where(pre >= 0, 1.0, -1.0)
    return _ref.dense_pm1(h, params["w2"], params["b2"][None, :])


# ---------------------------------------------------------------------------
# banked (slot-selected) execution
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend",))
def bnn_forward_banked(bank, x_packed, slots, *, backend: str = "auto"):
    """Per-packet slot-selected BNN forward (gather/onehot semantics).

    bank leaves are stacked (K, ...).  Exact per-packet granularity — the
    grouped Pallas path lives in ``bnn_forward_grouped``.
    """
    backend = _resolve(backend)
    if backend == "mxu":
        # onehot-style MXU contraction: selection becomes a K-contraction.
        d = x_packed.shape[-1] * _ref.PACK
        xv = _ref.unpack_bits(x_packed, d).astype(jnp.bfloat16)   # (B, d)
        wv = _ref.unpack_bits(bank["w1p"], d).astype(jnp.bfloat16)  # (K, H, d)
        onehot = jax.nn.one_hot(slots, bank["w1p"].shape[0], dtype=jnp.bfloat16)
        pre = jnp.einsum(
            "bd,khd,bk->bh", xv, wv, onehot,
            preferred_element_type=jnp.float32,
        )
        pre = pre + bank["b1"][slots]
        h = jnp.where(pre >= 0, 1.0, -1.0)
        return _ref.dense_pm1(h, bank["w2"][slots], bank["b2"][slots])
    return _ref.banked_xnor_forward_ref(
        bank["w1p"], bank["b1"], bank["w2"], bank["b2"], x_packed, slots
    )


@functools.partial(jax.jit, static_argnames=("block_b", "backend"))
def bnn_forward_grouped(
    bank, x_packed, block_slots, *, block_b: int = 256, backend: str = "auto"
):
    """Grouped slot-selected BNN forward via the scalar-prefetch kernel.

    Rows must be pre-grouped so each ``block_b`` block shares a slot
    (``repro.core.bank.group_by_slot``).  block_slots: (B // block_b,) i32.
    """
    bb = min(block_b, x_packed.shape[0])
    # contiguous fused mode: one launch, layer 1 + sign + layer 2 in VMEM
    return bnn_forward_fused(
        bank, x_packed, block_slots, None, block_b=bb, backend=backend
    )


@functools.partial(jax.jit, static_argnames=("block_b", "backend"))
def bnn_forward_fused(
    bank, x_packed, block_slots, row_ids=None, *, block_b: int = 256,
    backend: str = "auto",
):
    """Zero-copy fused BNN forward: one kernel launch, gather prologue.

    ``row_ids`` maps output row r to input row ``row_ids[r]`` so the batch
    never has to be re-laid-out in HBM (``repro.core.bank.group_by_slot_padded``
    provides it).  ``row_ids=None`` means rows are already grouped
    contiguously.  The ref/mxu backends reproduce the same semantics with a
    jnp gather — the oracle for parity tests.
    """
    backend = _resolve(backend)
    n_rows = block_slots.shape[0] * block_b if row_ids is None \
        else row_ids.shape[0]
    if backend in ("ref", "mxu"):
        rows = x_packed if row_ids is None \
            else jnp.take(x_packed, row_ids, axis=0)
        slots = _ref.expand_block_slots(block_slots, block_b, n_rows)
        return _ref.banked_xnor_forward_ref(
            bank["w1p"], bank["b1"], bank["w2"], bank["b2"], rows, slots
        )
    return _fused.fused_forward(
        x_packed, bank["w1p"], bank["b1"], bank["w2"], bank["b2"],
        block_slots, row_ids, block_b=block_b, interpret=interpret_mode(),
    )


@functools.partial(jax.jit, static_argnames=("meta_words", "block_b", "backend"))
def packet_forward_fused(
    bank, packets, block_slots, row_ids, *, meta_words: int,
    block_b: int = 256, backend: str = "auto",
):
    """Whole forwarding path in one launch: parse + select + BNN + Pi.

    ``packets`` are raw (B, meta_words + W) uint32 rows in arrival order;
    the kernel gathers each block's rows by DMA, slices the payload, and
    emits (scores, actions).  Returns ``(n_rows, C) f32, (n_rows,) i32``.
    """
    backend = _resolve(backend)
    if backend in ("ref", "mxu"):
        rows = jnp.take(packets, row_ids, axis=0)
        payload = rows[:, meta_words:]
        slots = _ref.expand_block_slots(block_slots, block_b, row_ids.shape[0])
        scores = _ref.banked_xnor_forward_ref(
            bank["w1p"], bank["b1"], bank["w2"], bank["b2"], payload, slots
        )
        return scores, _fused.actions_ref(scores, rows[:, _fused.CTRL_WORD])
    scores, actions = _fused.fused_forward(
        packets, bank["w1p"], bank["b1"], bank["w2"], bank["b2"],
        block_slots, row_ids, block_b=block_b, meta_words=meta_words,
        with_actions=True, interpret=interpret_mode(),
    )
    return scores, actions[:, 0]


@functools.partial(jax.jit, static_argnames=("block_b", "backend"))
def banked_matmul(x, w, b, block_slots, *, block_b: int = 128, backend: str = "auto"):
    """Grouped slot-selected float matmul (adapter/head banks)."""
    backend = _resolve(backend)
    bsz = x.shape[0]
    bb = min(block_b, bsz)
    if backend == "ref":
        slots = _ref.expand_block_slots(block_slots, bb, bsz)
        return _ref.banked_matmul_ref(x, w, b, slots)
    return _banked.banked_matmul(
        x, w, b, block_slots, block_b=bb, interpret=interpret_mode()
    )
