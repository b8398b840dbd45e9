"""The shared forwarding path (paper Algorithm 1).

One jitted function implements the whole per-packet pipeline:

    1. parse slot metadata from reg0
    2. k_p  <- sigma(m_p)          (O(1) slot extraction)
    3. resolve resident slot f_{k_p} in the bank
    4. y_p  <- f_{k_p}(x_p)        (shared BNN executor)
    5. a_p  <- Pi(m_p, y_p)        (forwarding action)

The parser, executor and forwarding logic are byte-identical across packets
and across slots — the compiled XLA program never changes; only the slot
index (data) differs.  The "fixed single-model path" used as the paper's
baseline operating mode is the same pipeline with sigma replaced by a
constant.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bank as bank_lib, executor, packet as pkt
from repro.kernels import fused_forward as _fused_kernel
from repro.kernels import ops

# The kernel package mirrors the reg0 layout so it stays core-free; make the
# mirror impossible to drift silently.
assert _fused_kernel.CTRL_WORD == pkt.CONTROL_WORD_LO
assert _fused_kernel.CTRL_MONITOR_ONLY == int(pkt.CTRL_MONITOR_ONLY)
assert (_fused_kernel.ACTION_FORWARD, _fused_kernel.ACTION_DROP,
        _fused_kernel.ACTION_FLAG) == (pkt.ACTION_FORWARD, pkt.ACTION_DROP,
                                       pkt.ACTION_FLAG)


class PacketResult(NamedTuple):
    slots: jnp.ndarray     # (B,) resolved k_p
    scores: jnp.ndarray    # (B,) y_p (first output column)
    verdicts: jnp.ndarray  # (B,) bool — malicious?
    actions: jnp.ndarray   # (B,) int32 Pi output


@functools.partial(
    jax.jit,
    static_argnames=("num_slots", "strategy", "backend", "fixed_slot",
                     "block_b"),
)
def packet_step(
    bank,
    packets: jnp.ndarray,  # (B, 272) uint32
    *,
    num_slots: int,
    strategy: str = "take",
    backend: str = "auto",
    fixed_slot: int | None = None,
    block_b: int = 256,
) -> PacketResult:
    """Process one batch of packets along the shared forwarding path.

    ``strategy="fused"`` runs steps 1-5 as ONE Pallas launch over the raw
    packet rows: the kernel gathers each block's packets by DMA, slices the
    payload, runs the banked BNN in VMEM, and emits verdict + Pi action —
    no payload view, no padded batch copy, no HBM intermediates.  The other
    strategies share the staged executor (`executor.forward_banked`).
    """
    if fixed_slot is None:
        slots = pkt.slot_of(packets, num_slots)           # sigma(m_p)
    else:  # baseline operating mode: fixed single-model path
        slots = jnp.full(packets.shape[:1], fixed_slot, jnp.int32)
    if strategy == "fused":
        if ops._resolve(backend) in ("ref", "mxu"):
            # No Pallas launch to feed: the oracle gathers per-row weights
            # anyway, so slot-grouping only adds an argsort and up to
            # ``num_slots`` padding blocks of dead compute.  Run the bank
            # directly on the arrival-order batch (bit-identical scores).
            from repro.kernels import ref as _ref
            scores_d = _ref.banked_xnor_forward_ref(
                bank["w1p"], bank["b1"], bank["w2"], bank["b2"],
                pkt.payload_of(packets), slots)
            actions_d = _fused_kernel.actions_ref(
                scores_d, packets[:, pkt.CONTROL_WORD_LO])
            return PacketResult(slots, scores_d[:, 0], scores_d[:, 0] > 0.0,
                                actions_d)
        bb = min(block_b, packets.shape[0])
        g = bank_lib.group_by_slot_padded(slots, num_slots, bb)
        scores_pad, actions_pad = ops.packet_forward_fused(
            bank, packets, g.block_slots, g.row_ids,
            meta_words=pkt.META_WORDS, block_b=bb, backend=backend,
        )
        scores = jnp.take(scores_pad[:, 0], g.result_rows)
        actions = jnp.take(actions_pad, g.result_rows)
        return PacketResult(slots, scores, scores > 0.0, actions)
    payload = pkt.payload_of(packets)                     # x_p
    scores = executor.forward_banked(
        bank, payload, slots, strategy=strategy, backend=backend,
        block_b=block_b,
    )[:, 0]                                               # y_p
    actions = pkt.decide_action(packets, scores)          # Pi(m_p, y_p)
    return PacketResult(slots, scores, scores > 0.0, actions)


@functools.partial(
    jax.jit,
    static_argnames=("num_slots", "strategy", "backend", "block_b"),
)
def packet_step_queues(
    bank,
    packets: jnp.ndarray,  # (Q, B, 272) uint32: every queue's padded batch
    *,
    num_slots: int,
    strategy: str = "take",
    backend: str = "auto",
    block_b: int = 256,
) -> jnp.ndarray:
    """Every queue's batch through ONE ``packet_step``, packed for one pull.

    A row's slot, score, verdict and action depend only on that row and
    the bank, so the flat ``(Q * B)``-row step gives bit-identical
    per-row results to ``Q`` launches of ``B`` rows.  Returns
    ``(Q, 3, B)`` int32: queue ``q``'s slots, verdicts (0/1) and actions
    at ``[q, 0]``, ``[q, 1]`` and ``[q, 2]``.  Scores stay on the device.
    """
    q, b, words = packets.shape
    res = packet_step(bank, packets.reshape(q * b, words),
                      num_slots=num_slots, strategy=strategy,
                      backend=backend, block_b=block_b)
    return jnp.stack([res.slots, res.verdicts.astype(jnp.int32),
                      res.actions], axis=0).reshape(3, q, b).swapaxes(0, 1)


@functools.partial(jax.jit, static_argnames=("backend",))
def slot_select_only(packets: jnp.ndarray, num_slots: int, *, backend="auto"):
    """Isolated sigma for the Fig. 4 / Fig. 5 microbenchmarks."""
    return pkt.slot_of(packets, num_slots)


@functools.partial(jax.jit, static_argnames=("backend",))
def inference_only(params, payload_words, *, backend: str = "auto"):
    """Isolated single-slot inference for the Fig. 4 breakdown."""
    return executor.forward(params, payload_words, backend=backend)
