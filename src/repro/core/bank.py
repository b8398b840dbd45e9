"""Resident model bank (paper §II-C) as a generic JAX pytree container.

``M = {f_0 .. f_{K-1}}`` is realized by stacking K structurally identical
parameter pytrees on a new leading axis.  All slots live at fixed HBM
locations inside ONE compiled program for the whole runtime — switching is
slot *indexing* (data), never recompilation or weight delivery (code).

Selection strategies (see DESIGN.md §3):
  * ``take``    — per-row gather ``leaf[slots]``.  Exact packet granularity;
                  materializes per-row weights (memory-bound).
  * ``onehot``  — contraction with ``one_hot(slots, K)``; selection becomes
                  an MXU einsum.  K x FLOPs, zero gathers — wins for small K.
  * ``grouped`` — sort rows by slot so each kernel block serves one slot,
                  then ONE scalar-prefetch fused Pallas kernel gathers each
                  block's rows by DMA and fetches only the selected slot's
                  weights from HBM (O(1) per block, the closest TPU analogue
                  of the paper's pointer-chase).  Zero-copy: the batch stays
                  in arrival order in HBM.
  * ``grouped_staged`` — the pre-fused layout: materialize a padded
                  slot-sorted copy of the batch (``scatter_padded``), run the
                  kernel, un-permute (``gather_padded``).  Kept as the
                  fused-vs-staged benchmark baseline.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import expand_block_slots

Params = Any  # pytree


def stack_bank(param_sets: list[Params]) -> Params:
    """Stack K structurally identical pytrees into (K, ...) leaves."""
    if not param_sets:
        raise ValueError("empty bank")
    treedefs = {jax.tree_util.tree_structure(p) for p in param_sets}
    if len(treedefs) != 1:
        raise ValueError("bank slots must share one pytree structure")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *param_sets)


def bank_size(bank: Params) -> int:
    leaves = jax.tree_util.tree_leaves(bank)
    return int(leaves[0].shape[0])


def select_slot(bank: Params, k) -> Params:
    """f_k: materialize one resident slot (traceable; k may be a tracer)."""
    return jax.tree_util.tree_map(lambda leaf: leaf[k], bank)


def update_slot(bank: Params, k: int, new_params: Params) -> Params:
    """Control-plane style in-place slot replacement (the *heavyweight* path —
    used only by the Table V baseline, never by resident switching)."""
    return jax.tree_util.tree_map(
        lambda leaf, new: leaf.at[k].set(new), bank, new_params
    )


def bank_bytes(bank: Params) -> int:
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(bank))


# ---------------------------------------------------------------------------
# grouped execution support
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Grouping:
    """Result of sorting a batch by slot for block-wise execution."""
    order: jnp.ndarray        # (B,) permutation applied to rows
    inverse: jnp.ndarray      # (B,) inverse permutation
    block_slots: jnp.ndarray  # (B // block_b,) slot id per block
    valid: jnp.ndarray        # (B,) bool — False for rows whose block mixes slots


def group_by_slot(slots: jnp.ndarray, block_b: int) -> Grouping:
    """Stable-sort rows by slot and derive per-block slot ids.

    With B a multiple of ``block_b``, blocks that land entirely inside one
    slot's segment are exact; rows in straddling blocks are flagged invalid
    so callers can re-run them through the exact ``take`` path (in practice
    the scheduler pads each slot's segment to a block multiple so ``valid``
    is all-True; the flag makes the invariant checkable).
    """
    bsz = slots.shape[0]
    if bsz % block_b:
        raise ValueError(f"B={bsz} must be a multiple of block_b={block_b}")
    order = jnp.argsort(slots, stable=True)
    sorted_slots = slots[order]
    blocks = sorted_slots.reshape(-1, block_b)
    block_slots = blocks[:, 0].astype(jnp.int32)
    valid_blocks = jnp.all(blocks == blocks[:, :1], axis=1)
    valid_sorted = expand_block_slots(valid_blocks, block_b, bsz)
    inverse = jnp.argsort(order)
    return Grouping(
        order=order,
        inverse=inverse,
        block_slots=block_slots,
        valid=valid_sorted[inverse],
    )


@dataclasses.dataclass
class PaddedGrouping:
    """Exact, static-shape grouping: every block is single-slot.

    Each slot's segment is padded up to a multiple of ``block_b`` inside a
    buffer of static size ``b_pad = roundup(B + K*block_b)``; padding rows
    execute under their block's slot (wasted-but-bounded compute:
    < K * block_b rows).  This is the in-jit production path for the grouped
    strategy — exact per-row semantics with O(1)-per-block slot resolution.

    ``row_ids`` / ``result_rows`` are the zero-copy form consumed by the
    fused kernel's DMA gather prologue: the batch itself is never scattered
    into the padded layout — only these two tiny int32 index vectors exist.
    ``order``/``dest`` remain for the legacy staged path (``scatter_padded``
    / ``gather_padded``), kept as the fused-vs-staged benchmark baseline.
    """
    order: jnp.ndarray        # (B,) stable sort permutation
    dest: jnp.ndarray         # (B,) destination of sorted row i in the padded buffer
    block_slots: jnp.ndarray  # (b_pad // block_b,) slot id per block
    b_pad: int                # static padded row count
    row_ids: jnp.ndarray      # (b_pad,) source row per padded position (pad -> 0)
    result_rows: jnp.ndarray  # (B,) padded position holding row i's result


def _exclusive_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """[x0, x1, ...] -> [0, x0, x0+x1, ...] (segment start offsets)."""
    return jnp.concatenate([jnp.zeros(1, x.dtype), jnp.cumsum(x)[:-1]])


def padded_rows(b: int, num_slots: int, block_b: int) -> int:
    """``PaddedGrouping.b_pad``: the static row count a batch of ``b``
    rows over ``num_slots`` slots is padded to, in whole ``block_b``
    blocks — the rows the grouped kernel's grid covers."""
    return ((b + num_slots * block_b + block_b - 1) // block_b) * block_b


def group_by_slot_padded(
    slots: jnp.ndarray, num_slots: int, block_b: int
) -> PaddedGrouping:
    b = slots.shape[0]
    order = jnp.argsort(slots, stable=True)
    sorted_slots = slots[order]
    counts = jnp.bincount(slots, length=num_slots)
    padded = ((counts + block_b - 1) // block_b) * block_b
    rank = jnp.arange(b) - _exclusive_cumsum(counts)[sorted_slots]
    dest = (_exclusive_cumsum(padded)[sorted_slots] + rank).astype(jnp.int32)
    b_pad = padded_rows(b, num_slots, block_b)
    seg_end = jnp.cumsum(padded)
    block_starts = jnp.arange(b_pad // block_b) * block_b
    block_seg = jnp.searchsorted(seg_end, block_starts, side="right")
    block_slots = jnp.clip(block_seg, 0, num_slots - 1).astype(jnp.int32)
    row_ids = jnp.zeros(b_pad, jnp.int32).at[dest].set(order.astype(jnp.int32))
    result_rows = jnp.zeros(b, jnp.int32).at[order].set(dest)
    return PaddedGrouping(order=order, dest=dest, block_slots=block_slots,
                          b_pad=b_pad, row_ids=row_ids,
                          result_rows=result_rows)


def scatter_padded(x: jnp.ndarray, g: PaddedGrouping) -> jnp.ndarray:
    """Place rows into the padded, slot-grouped layout (padding rows zero)."""
    out = jnp.zeros((g.b_pad,) + x.shape[1:], x.dtype)
    return out.at[g.dest].set(x[g.order])


def gather_padded(y_pad: jnp.ndarray, g: PaddedGrouping) -> jnp.ndarray:
    """Undo ``scatter_padded`` on the kernel output."""
    b = g.order.shape[0]
    out = jnp.zeros((b,) + y_pad.shape[1:], y_pad.dtype)
    return out.at[g.order].set(y_pad[g.dest])


def pad_group_by_slot(
    slots: np.ndarray, block_b: int, pad_slot: int = 0
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side scheduler grouping: pad each slot segment to a block multiple.

    Returns (order, block_slots, row_valid) where ``order`` indexes into the
    original batch with repeats allowed for padding rows (marked invalid).
    Guarantees every block is single-slot — the production path for the
    grouped strategy.
    """
    slots = np.asarray(slots)
    order_parts: list[np.ndarray] = []
    block_slots: list[int] = []
    valid_parts: list[np.ndarray] = []
    for k in np.unique(slots):
        idx = np.nonzero(slots == k)[0]
        pad = (-len(idx)) % block_b
        padded = np.concatenate([idx, np.repeat(idx[-1:], pad)])
        order_parts.append(padded)
        valid_parts.append(
            np.concatenate([np.ones(len(idx), bool), np.zeros(pad, bool)])
        )
        block_slots.extend([int(k)] * (len(padded) // block_b))
    return (
        np.concatenate(order_parts),
        np.asarray(block_slots, np.int32),
        np.concatenate(valid_parts),
    )


# ---------------------------------------------------------------------------
# double-buffered bank: zero-copy SwapSlot commit (DESIGN.md §14)
# ---------------------------------------------------------------------------

def copy_bank(bank: Params, sharding=None) -> Params:
    """Deep device copy of a bank pytree (fresh buffers, same contents):
    where it is, or placed with ``sharding`` (e.g. replicated on every
    chip of a mesh).  The copy comes after the put, which may keep a
    source buffer for the shard on its own device."""
    if sharding is not None:
        bank = jax.device_put(bank, sharding)
    return jax.tree_util.tree_map(lambda leaf: jnp.asarray(leaf).copy(), bank)


@functools.partial(jax.jit, donate_argnums=(0,))
def _stage_slot(shadow: Params, params: Params, slot) -> Params:
    """Write one slot's params into the shadow, donating the shadow's
    buffers so XLA updates in place — no second copy of the bank survives.
    ``slot`` is a traced scalar: one compilation serves every slot id."""
    return jax.tree_util.tree_map(
        lambda leaf, new: leaf.at[slot].set(new), shadow, params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _sync_slot(shadow: Params, active: Params, slot) -> Params:
    """Catch the shadow up on one slot the active bank has since published
    (dirty-slot resync).  Donates the shadow only; the active bank — still
    serving traffic — is read, never consumed."""
    return jax.tree_util.tree_map(
        lambda leaf, cur: leaf.at[slot].set(cur[slot]), shadow, active)


class _Buf:
    """One of the two device-resident bank copies, with a pin count.

    A pinned buffer is referenced outside the double buffer (e.g. an
    epoch snapshot held for rollback) and must never be donated;
    ``DoubleBufferedBank.stage`` un-aliases it with a fresh copy instead
    (copy-on-write — a lingering pin costs one extra copy, never
    correctness)."""

    __slots__ = ("tree", "pins")

    def __init__(self, tree: Params):
        self.tree = tree
        self.pins = 0


class DoubleBufferedBank:
    """Two device-resident copies of the bank: *active* (serving traffic)
    and *shadow* (staging target).  ``SwapSlot`` staging donates into the
    shadow while ticks keep reading the active copy; the epoch's barrier
    commit is then ``commit()`` — a Python reference flip, O(1) regardless
    of bank size.  Protocol, staging states, and rollback rules are
    documented in DESIGN.md §14.

    Invariants:
      * the active buffer is never donated — every holder of the runtime's
        ``bank`` attribute stays valid until the next flip *and* the next
        staging onto that (by then shadow) buffer; holders that span that
        window pin the buffer (``pin_active``/``unpin``).
      * at most ONE epoch's swaps are prestaged at a time
        (``_staged_epoch``); a second epoch's prestage is refused and
        falls back to staging at apply time (``force=True``), which still
        commits by flip.
      * per-buffer dirty-slot sets record how far each buffer lags the
        other; ``stage`` resyncs the shadow's dirty slots from the active
        buffer before writing new params, so a flip always publishes a
        complete bank.
      * with ``sharding`` both buffers, every staged slot and every
        copy-on-write or reseeded buffer are placed with it (the
        ``shard_map`` fan-out keeps the bank replicated on each chip of
        its mesh), so a flip or a resync never moves the bank between
        devices.  ``puts`` counts the placements of bank bytes onto the
        device(s) (``on_put`` is called at each): two at construction,
        one per staged slot, copy-on-write copy or reseed.
    """

    def __init__(self, bank: Params, *, sharding=None, on_put=None):
        self.num_slots = bank_size(bank)
        self.sharding = sharding
        self._on_put = on_put
        self.puts = 0
        # private copies: donation must never invalidate the caller's arrays
        self._bufs = [_Buf(self._copy(bank)), _Buf(self._copy(bank))]
        self._active = 0
        self._dirty: list[set[int]] = [set(), set()]
        self._staged: dict[Any, tuple[int, Params]] = {}
        self._staged_epoch: Any = None
        self._committed: dict[Any, int] = {}
        self.stages = self.syncs = self.flips = 0
        self.discards = self.unalias_copies = 0

    def _counted_put(self) -> None:
        self.puts += 1
        if self._on_put is not None:
            self._on_put()

    def _copy(self, tree: Params) -> Params:
        """A fresh copy of a whole bank where the bank lives."""
        self._counted_put()
        return copy_bank(tree, self.sharding)

    def _place(self, params: Params) -> Params:
        """One slot's params where the bank lives, for ``_stage_slot``."""
        self._counted_put()
        if self.sharding is not None:
            return jax.device_put(params, self.sharding)
        return jax.tree_util.tree_map(jnp.asarray, params)

    # -- views ------------------------------------------------------------

    @property
    def active(self) -> Params:
        return self._bufs[self._active].tree

    @property
    def shadow(self) -> Params:
        return self._bufs[1 - self._active].tree

    @property
    def has_staged(self) -> bool:
        return bool(self._staged)

    def is_staged(self, token) -> bool:
        return token in self._staged

    def committed(self, token) -> bool:
        return token in self._committed

    # -- pinning ----------------------------------------------------------

    def pin_active(self) -> _Buf:
        """Pin the current active buffer (returns the pin handle)."""
        buf = self._bufs[self._active]
        buf.pins += 1
        return buf

    def unpin(self, buf: _Buf) -> None:
        buf.pins = max(0, buf.pins - 1)

    # -- staging ----------------------------------------------------------

    def stage(self, slot: int, params: Params, *, token, epoch,
              force: bool = False) -> bool:
        """Stage ``params`` into the shadow's ``slot``; True if staged.

        ``token`` identifies the request (a command's ``id()``, or a
        prefetch key) so commit/rollback bookkeeping survives re-entry;
        ``epoch`` scopes the one-staged-epoch policy.  A same-slot,
        same-params re-stage (a prefetch being promoted to a real epoch)
        adopts the existing staged entry without touching the device.
        ``force=True`` (apply-time staging) evicts a stale staged epoch
        instead of refusing.
        """
        if token in self._staged:
            return True
        for t, (s, p) in list(self._staged.items()):
            if s == slot and p is params:  # prefetch promotion: rebind
                del self._staged[t]
                self._staged[token] = (slot, params)
                self._staged_epoch = epoch
                return True
        if self._staged and self._staged_epoch != epoch:
            if not force:
                return False
            self.discard_staged()
        sh = 1 - self._active
        buf = self._bufs[sh]
        if buf.pins:
            # copy-on-write: the pinned buffer stays with its pinner
            buf = self._bufs[sh] = _Buf(self._copy(buf.tree))
            self.unalias_copies += 1
        act = self._bufs[self._active].tree
        for k in sorted(self._dirty[sh]):
            if k == slot:
                continue  # about to be overwritten anyway
            buf.tree = _sync_slot(buf.tree, act, jnp.int32(k))
            self.syncs += 1
        self._dirty[sh].clear()
        buf.tree = _stage_slot(buf.tree, self._place(params), jnp.int32(slot))
        self._staged[token] = (slot, params)
        self._staged_epoch = epoch
        self.stages += 1
        return True

    def discard_staged(self) -> None:
        """Drop staged-but-uncommitted entries (their slots go dirty)."""
        if not self._staged:
            return
        sh = 1 - self._active
        self._dirty[sh].update(s for s, _ in self._staged.values())
        self._staged.clear()
        self._staged_epoch = None
        self.discards += 1

    # -- commit / rollback -------------------------------------------------

    def commit(self) -> Params:
        """Publish every staged slot by flipping which buffer is active.

        O(1) — a Python reference swap; no weights move.  The demoted
        buffer becomes the next shadow, dirty at exactly the slots just
        published.  Returns the new active bank pytree."""
        if not self._staged:
            return self.active
        old = self._active
        self._active = 1 - old
        for s, _ in self._staged.values():
            self._dirty[old].add(s)
        self._committed.update(
            {t: s for t, (s, _) in self._staged.items()})
        self._staged.clear()
        self._staged_epoch = None
        self.flips += 1
        return self.active

    def mark(self):
        """Snapshot flip/staging bookkeeping for epoch rollback.

        Taken at the epoch barrier's ``_control_state``; the previous
        epoch's committed tokens are dead by then and are purged so
        ``id()`` reuse can never alias a new command onto them."""
        self._committed.clear()
        return (self._active, dict(self._staged), self._staged_epoch,
                dict(self._committed),
                (set(self._dirty[0]), set(self._dirty[1])))

    def restore(self, m) -> None:
        """Roll back to a ``mark()``: un-flip if the epoch flipped, and
        mark every slot staged/committed since the mark dirty (the shadow
        holds rolled-back params there)."""
        active, staged, staged_epoch, committed, dirty = m
        rolled = {s for t, (s, _) in self._staged.items() if t not in staged}
        rolled |= {s for t, s in self._committed.items() if t not in committed}
        self._active = active
        self._staged = dict(staged)
        self._staged_epoch = staged_epoch
        self._committed = dict(committed)
        self._dirty = [set(dirty[0]), set(dirty[1])]
        self._dirty[1 - active].update(rolled)

    def reseed(self, bank: Params) -> None:
        """Adopt externally supplied contents (trace-replay install, mesh
        shard resync) as the new active bank.  The shadow is left in place
        — possibly pinned — and marked fully dirty so the next stage
        resyncs it."""
        self.discard_staged()
        self._bufs[self._active] = _Buf(self._copy(bank))
        self._dirty[self._active].clear()
        self._dirty[1 - self._active] = set(range(self.num_slots))
        self._committed.clear()


# ---------------------------------------------------------------------------
# generic banked apply
# ---------------------------------------------------------------------------

def apply_banked(
    bank: Params,
    apply_fn: Callable[[Params, jnp.ndarray], jnp.ndarray],
    x: jnp.ndarray,
    slots: jnp.ndarray,
    *,
    strategy: str = "take",
) -> jnp.ndarray:
    """Run ``apply_fn(f_{slots[i]}, x[i])`` for every row under a strategy.

    ``take`` vmaps a per-row gather; ``onehot`` computes all K results per
    row and contracts (exact, K x FLOPs — only for cheap apply_fns / small K).
    The grouped strategy lives with the kernels (`repro.kernels.ops`), since
    it changes the execution layout, not just the math.
    """
    if strategy == "take":
        return jax.vmap(lambda s, xi: apply_fn(select_slot(bank, s), xi))(slots, x)
    if strategy == "onehot":
        k = bank_size(bank)
        all_out = jax.vmap(
            lambda xi: jax.vmap(lambda s: apply_fn(select_slot(bank, s), xi))(
                jnp.arange(k)
            )
        )(x)  # (B, K, ...)
        onehot = jax.nn.one_hot(slots, k, dtype=all_out.dtype)
        return jnp.einsum("bk,bk...->b...", onehot, all_out)
    raise ValueError(f"unknown strategy {strategy!r}")
