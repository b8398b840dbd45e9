"""Multi-queue data-plane runtime: RSS dispatch -> rings -> sharded workers.

This is the repo's analogue of the paper's AF_XDP deployment shape: the
NIC hashes each flow to one of N queues (``rss``), every queue buffers
into a bounded ring (``ring``), and each queue drains through the *same*
resident-bank forwarding program (`repro.core.pipeline.packet_step`) —
one launch over every queue's rows per tick, one packed result pulled
once, per-queue FIFO ordering, and online slot swaps that never produce
a wrong verdict.

Control plane (DESIGN.md §7): every runtime mutation — slot swap, RETA
rewrite, queue fail/restore, policy change — flows through
``self.control`` (`repro.control.ControlPlane`) as an epoch-stamped
command batch.  Epochs apply only at tick boundaries (entry of
``dispatch``/``tick``), so in-flight device work keeps the bank/RETA
version it was dispatched with; the legacy ``swap_slot``/``set_reta``/
``fail_queues`` methods are deprecation shims that emit single-command
epochs.  An installed ``RoutingPolicy`` is consulted at every tick
boundary and its rebalances land as ordinary ``ProgramReta`` epochs.

Each tick pads every queue's pop to ``batch`` rows in one (Q, B, 272)
host batch (an empty queue's rows are zeros and its results are
discarded), copies it to the device once, and runs
`repro.core.pipeline.packet_step_queues`: ONE ``packet_step`` over the
flat (Q * B)-row batch, whose slots, verdicts and actions come back
packed in one (Q, 3, B) int32 array — one block and one pull per tick.
The step is row-independent, so this equals a launch per queue bit for
bit.  Fan-out modes (``fanout=``) only split that work:

* ``vmap``      — the one flat launch on one device (``auto`` picks it
                  for every strategy).
* ``shard_map`` — the same flat step as each shard's body, queues sharded
                  over a device mesh (`repro.launch.mesh.make_queue_mesh`,
                  over exactly ``devices=`` when given) exactly like RSS
                  maps flows onto NIC queues.  The bank is placed
                  replicated on every chip of the mesh once, when the
                  runtime is built (and each staged slot when it is
                  staged), and each tick's batch goes straight into
                  per-chip shards in one ``device_put``, so a launch
                  moves no bank bytes and stages nothing on one chip.
                  Host-simulated on 1-device CPU CI; real spread on TPU.
* ``loop``      — one launch and one pull per non-empty queue: kept only
                  as the per-queue reference the parity tests compare
                  the flat launch with.

The tick loop is the runtime's one execution engine: a 3-stage pipeline
(dispatch / device / retire) with a bounded in-flight window, the
multi-queue form of ``switching.replay_trace(stream=True)``: each
``tick()`` pops at most ``batch`` rows per queue, pads to the static
batch shape (no recompiles), issues the launch asynchronously, and
retires the oldest tick once the window is full.  By default
(``pipeline_depth=None``) the window is read from the rings: a tick
stays in flight only while some ring still holds a whole ``batch`` after
its pop, so the next tick is already waiting; its result pull starts at
launch and it retires behind the next tick's launch.  Otherwise it
retires at once, so a lightly loaded stream waits no extra tick.  An
integer ``pipeline_depth`` fixes the window at that many ticks; ``1`` is
the synchronous loop.  Every window produces bit-identical verdicts
because every tick captures the bank/RETA version current at its
dispatch.
``audit=True`` re-scores every tick through the exact ``take`` path
*against that captured bank* and counts verdict mismatches — valid
across every control command kind, not just slot swaps.

``self.spans`` (`repro.obs.spans.HostSpans`, off until enabled) names
each host step of the tick loop: ``dp.dispatch`` (``.hash``,
``.push``), ``dp.tick`` (``.control``, ``.pop``, ``.pad``, then per
launch ``.h2d`` and ``.launch``) and the retire (``dp.retire.wait``, per
launch ``.d2h``, per queue ``.tap``, ``.telemetry``, ``.audit``), with
the counters ``dp.rows_popped``, ``dp.ring_wait_ns``, ``dp.kernel_rows``,
``dp.retire.deferred`` (ticks retired behind a later tick's launch),
``dp.queue_batches`` (non-empty queue batches served), ``dp.h2d_bytes``
(host-to-device bytes of the launches' batches, summed over the chips)
and ``dp.bank_puts`` (placements of bank bytes onto the device(s): at
construction, per staged slot, never per tick).
"""

from __future__ import annotations

import collections
import functools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.control import (ControlPlane, FailQueues, ProgramReta,
                           RestoreQueues, SetPolicy, SwapSlot)
from repro.control import policy as policy_mod
from repro.core import bank as bank_lib, pipeline
from repro.dataplane import rss
from repro.dataplane.ring import PacketRing
from repro.dataplane.workloads.phases import SEQ_WORD
from repro.dataplane.telemetry import Telemetry
from repro.kernels import ops as _ops
from repro.launch import mesh as mesh_lib
from repro.obs.spans import HostSpans

# strategies whose kernel pads every slot's segment to whole blocks
_GROUPED_STRATEGIES = ("fused", "grouped", "grouped_staged")

_DEPRECATION = ("%s() is a deprecation shim: submit a %s command through "
                "runtime.control.submit(...) instead")


def queue_mesh(num_queues: int):
    """Compatibility alias: device layout now lives in one place —
    `repro.launch.mesh.make_queue_mesh` (the single source of truth)."""
    return mesh_lib.make_queue_mesh(num_queues)


def apply_routing_command(rt, cmd) -> bool:
    """Apply the service-state commands whose semantics are identical on
    the single-host runtime and the mesh facade (which passes global
    queue ids through ``rt.num_queues`` and its own ``_install_reta``):
    ``FailQueues`` (union + affinity-preserving failover), ``RestoreQueues``
    (default table minus still-failed), ``SetPolicy``.  Returns False for
    any other command so callers keep their own dispatch."""
    if isinstance(cmd, FailQueues):
        failed = rt.failed_queues | set(cmd.queues)
        # compute-then-commit: an unservable failover (zero live queues)
        # raises here without mutating any runtime state
        table = rss.failover_table(rt.reta, tuple(sorted(failed)),
                                   num_queues=rt.num_queues)
        rt.failed_queues = failed
        rt._install_reta(table)
    elif isinstance(cmd, RestoreQueues):
        rt.failed_queues -= set(cmd.queues or range(rt.num_queues))
        rt._install_reta(rss.restore_table(
            rt.num_queues, len(rt.reta), rt.failed_queues))
    elif isinstance(cmd, SetPolicy):
        rt.policy = cmd.policy
    else:
        return False
    return True


def consult_policy(rt, *, num_hosts: int = 1) -> None:
    """Tick-boundary policy consultation, shared by the single-host
    runtime and the mesh facade: freeze a view of the runtime's queue
    pressure, and submit any proposal as an ordinary ``ProgramReta``
    epoch (effective at the *next* boundary).  ``rt`` needs the runtime
    protocol surface: policy / rings / reta / bucket_load /
    failed_queues / control."""
    if rt.policy is None:
        return
    view = policy_mod.PolicyView(
        tick=rt._tick_count,
        num_queues=rt.num_queues,
        num_hosts=num_hosts,
        reta=rt.reta.copy(),
        queue_depth=np.array([len(r) for r in rt.rings], np.int64),
        queue_dropped=np.array(
            [r.counters.dropped for r in rt.rings], np.int64),
        bucket_load=rt.bucket_load.copy(),
        failed_queues=frozenset(rt.failed_queues),
    )
    proposal = rt.policy.propose(view)
    if proposal is not None and not np.array_equal(proposal, rt.reta):
        rt.control.submit(ProgramReta(tuple(proposal)))


def drain_rings(rt, max_ticks: int = 100_000) -> int:
    """Tick until every ring is empty, then flush the pipeline — the one
    drain loop both the single-host runtime and the mesh facade use."""
    done = 0
    for _ in range(max_ticks):
        n = rt.tick()
        done += n
        if n == 0 and not any(len(r) for r in rt.rings):
            rt.retire_all()
            return done
    raise RuntimeError("drain did not converge")


class _InFlight:
    """One dispatched-but-unretired tick (the device stage of the pipeline)."""

    __slots__ = ("tick", "popped", "counts", "batch", "launches", "bank")

    def __init__(self, tick, popped, counts, batch, launches, bank):
        self.tick = tick
        self.popped = popped      # [(rows, ts)] per queue
        self.counts = counts      # rows popped per queue
        self.batch = batch        # (Q, B, words) padded host batch
        self.launches = launches  # [(queues, (len(queues), 3, B) i32)] async
        self.bank = bank          # bank version captured at dispatch


class DataplaneRuntime:
    """Single-host multi-queue data-plane runtime (DESIGN.md §6/§7).

    ``devices`` (``fanout="shard_map"`` only) names the devices the
    queues are sharded over; their count must divide ``num_queues``.
    Without it the mesh is `repro.launch.mesh.make_queue_mesh`'s default.

    Public surface: ``dispatch`` (arrival edge), ``tick`` (pipeline
    step), ``retire_all``/``drain`` (flush), ``control`` (the epoch-
    stamped mutation funnel, `repro.control.ControlPlane`),
    ``flush_control``, ``adopt_bank``, ``audit_conservation`` and
    ``snapshot`` (reporting).  All state mutation flows through control
    epochs; the attributes (``bank``, ``reta``, ``policy``, ...) are
    read-only views between tick boundaries.

    With ``double_buffer=True`` (default) the resident bank is held in a
    `repro.core.bank.DoubleBufferedBank`: SwapSlot params stage into the
    shadow copy at submit time while traffic flows, and the epoch commit
    is an O(1) pointer flip (DESIGN.md §14) instead of a bank re-stage.
    """

    def __init__(
        self,
        bank,
        *,
        num_queues: int,
        num_slots: int | None = None,
        strategy: str = "fused",
        fanout: str = "auto",
        batch: int = 128,
        block_b: int = 32,
        ring_capacity: int = 2048,
        backend: str = "auto",
        rss_key: bytes = rss.DEFAULT_KEY,
        audit: bool = False,
        record: bool = False,
        pipeline_depth: int | None = None,
        policy=None,
        fault_injector=None,
        log_capacity: int | None = None,
        log_spill: str | None = None,
        double_buffer: bool = True,
        devices=None,
    ):
        self.num_queues = int(num_queues)
        self.num_slots = int(num_slots if num_slots is not None
                             else bank_lib.bank_size(bank))
        self.spans = HostSpans()
        if fanout == "auto":
            fanout = "vmap"
        if fanout not in ("loop", "vmap", "shard_map"):
            raise ValueError(f"unknown fanout {fanout!r}")
        if devices is not None and fanout != "shard_map":
            raise ValueError("devices= names a shard_map mesh; "
                             f"fanout is {fanout!r}")
        self.fanout = fanout
        # Under shard_map the bank lives replicated on every chip of the
        # queue mesh and each tick's batch is sharded over its queue axis
        self._mesh = self._bank_sharding = self._batch_sharding = None
        if fanout == "shard_map":
            self._mesh, self._axis = mesh_lib.make_queue_mesh(
                self.num_queues, devices)
            self._bank_sharding = NamedSharding(self._mesh, P())
            self._batch_sharding = NamedSharding(self._mesh, P(self._axis))
        # Double-buffered bank: the runtime owns two private device
        # copies; ``self.bank`` aliases the active one.  The caller's
        # ``bank`` argument is never donated.
        self._bankbuf = None
        self._epoch_nonce: object = None
        if double_buffer:
            self._bankbuf = bank_lib.DoubleBufferedBank(
                bank, sharding=self._bank_sharding,
                on_put=self._count_bank_put)
            self.bank = self._bankbuf.active
        else:
            self.bank = self._put_bank(bank)
        self.strategy = strategy
        self.batch = int(batch)
        self.block_b = min(int(block_b), self.batch)
        self.backend = backend
        self.rss_key = rss_key
        self.audit = audit
        self.reta = rss.indirection_table(self.num_queues)
        self.rings = [PacketRing(ring_capacity) for _ in range(self.num_queues)]
        self.telemetry = Telemetry(self.num_queues, self.num_slots)
        self._record = record
        self.completed_seq = [[] for _ in range(self.num_queues)]
        self.completed_verdicts = [[] for _ in range(self.num_queues)]
        self.completed_slots = [[] for _ in range(self.num_queues)]
        self.dropped_seq: list[int] = []
        # deploy/observability taps (host callbacks off the hot path; they
        # must treat their arguments as read-only and stay cheap — the tick
        # loop does not shield itself from a slow tap):
        #   on_retire(queue, rows, slots, verdicts, actions, tick)
        #   on_drop(queue, rows)   — dispatch-edge tail drops
        self.on_retire = None
        self.on_drop = None
        self._t_start: float | None = None
        if pipeline_depth is not None and pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        # None: the in-flight window is read from the rings at each tick
        self.pipeline_depth = (None if pipeline_depth is None
                               else int(pipeline_depth))
        self._inflight: collections.deque[_InFlight] = collections.deque()
        self._tick_count = 0
        self._faults = fault_injector
        self.control = ControlPlane(self, log_capacity=log_capacity,
                                    spill_path=log_spill)
        self.policy = policy          # initial config, not a mutation
        self.failed_queues: set[int] = set()
        self.bucket_load = np.zeros(len(self.reta), np.int64)
        self._step, self._kernel_rows = self._build_fanout(fanout)

    # -- worker construction ------------------------------------------------

    def _build_fanout(self, fanout: str):
        """The step one launch runs, (Qlaunch, B, 272) -> (Qlaunch, 3, B)
        packed results, and the kernel rows that launch computes."""
        step = functools.partial(
            pipeline.packet_step_queues, num_slots=self.num_slots,
            strategy=self.strategy, backend=self.backend,
            block_b=self.block_b)
        queues, shards = self.num_queues, 1
        if fanout == "loop":
            queues = 1
        elif fanout == "shard_map":
            shards = self._mesh.shape[self._axis]
            step = jax.jit(jax.shard_map(
                step, mesh=self._mesh, in_specs=(P(), P(self._axis)),
                out_specs=P(self._axis), check_vma=False,
            ))
        rows = queues // shards * self.batch
        if self.strategy in _GROUPED_STRATEGIES:
            rows = bank_lib.padded_rows(rows, self.num_slots, self.block_b)
        return step, shards * rows

    # -- bank placement -------------------------------------------------------

    def _count_bank_put(self) -> None:
        self.spans.count("dp.bank_puts", 1)

    def _put_bank(self, tree):
        """``tree`` (a bank or one slot's params) placed where the bank
        lives: replicated on the mesh under ``shard_map``, else as given
        (single-buffered bank only; the double buffer places its own)."""
        if self._bank_sharding is None:
            return tree
        self._count_bank_put()
        return jax.device_put(tree, self._bank_sharding)

    # -- control plane: command application (ControlPlane-only entry) -------

    def _validate_command(self, cmd) -> None:
        """Raise without mutating when ``cmd`` cannot apply to the current
        state.  ``ControlPlane.apply_pending`` validates a whole epoch
        before applying any of it, so a rejected epoch is atomic: nothing
        mutates.  (Validation is against the pre-epoch state; an epoch
        whose commands only conflict with *each other* still fails at
        apply time and is logged with its error.)"""
        self._fault_check("stage")
        if isinstance(cmd, SwapSlot):
            if not 0 <= int(cmd.slot) < self.num_slots:
                raise ValueError(f"slot {cmd.slot} out of range")
            if (jax.tree_util.tree_structure(cmd.params)
                    != jax.tree_util.tree_structure(self.bank)):
                raise ValueError("params pytree does not match bank slots")
        elif isinstance(cmd, ProgramReta):
            reta = np.asarray(cmd.reta, np.int32)
            if reta.size == 0:
                raise ValueError("empty RETA")
            if reta.min() < 0 or reta.max() >= self.num_queues:
                raise ValueError("RETA entry out of queue range")
        elif isinstance(cmd, FailQueues):
            if any(not 0 <= q < self.num_queues for q in cmd.queues):
                raise ValueError("failed queue id out of range")
            # NOTE: no zero-live-queues check here — it would judge each
            # command against the pre-epoch state and falsely reject
            # sequentially-valid epochs like [RestoreQueues, FailQueues];
            # the apply-time failover_table raises instead and the state
            # snapshot rolls the epoch back atomically.
        elif isinstance(cmd, RestoreQueues):
            if any(not 0 <= q < self.num_queues for q in cmd.queues):
                raise ValueError("restored queue id out of range")
        elif isinstance(cmd, SetPolicy):
            if cmd.policy is not None and not hasattr(cmd.policy, "propose"):
                raise TypeError("policy must implement propose(view)")
        else:
            raise TypeError(f"not a control command: {cmd!r}")

    def _apply_command(self, cmd) -> None:
        """Apply ONE control command.  Only ``ControlPlane.apply_pending``
        may call this — it is the single mutation funnel."""
        self._fault_check("apply")
        if isinstance(cmd, SwapSlot):
            if self._bankbuf is not None:
                # zero-copy path: make sure the params are staged in the
                # shadow (a no-op when the epoch prestaged at submit),
                # then leave publication to the _finish_epoch flip
                tok = id(cmd)
                if not self._bankbuf.committed(tok):
                    self._bankbuf.stage(int(cmd.slot), cmd.params,
                                        token=tok, epoch=self._epoch_nonce,
                                        force=True)
            else:
                self.bank = bank_lib.update_slot(
                    self.bank, cmd.slot, self._put_bank(cmd.params))
            self.telemetry.slot_swaps += 1
        elif isinstance(cmd, ProgramReta):
            self._install_reta(np.asarray(cmd.reta, np.int32))
        elif not apply_routing_command(self, cmd):
            raise TypeError(f"not a control command: {cmd!r}")

    def _fault_check(self, point: str) -> None:
        """Consult the armed ``FaultInjector`` (if any) at a stage/apply
        injection point; a single-host runtime is always host 0."""
        if self._faults is not None:
            self._faults.check(point, 0, self._tick_count)

    def _control_state(self) -> dict:
        """Snapshot everything epochs mutate (apply-time rollback).  Safe
        by reference: appliers install fresh objects, never mutate these."""
        self._epoch_nonce = object()  # scopes apply-time staging (§14)
        return dict(bank=self.bank, reta=self.reta,
                    failed=set(self.failed_queues), policy=self.policy,
                    bucket_load=self.bucket_load,
                    slot_swaps=self.telemetry.slot_swaps,
                    reta_updates=self.telemetry.reta_updates,
                    bankswap=(self._bankbuf.mark()
                              if self._bankbuf is not None else None))

    def _rollback_control_state(self, s: dict) -> None:
        if self._bankbuf is not None and s.get("bankswap") is not None:
            self._bankbuf.restore(s["bankswap"])
            # the rolled-back epoch's staged params are garbage; its slots
            # go dirty and resync from the (restored) active bank later
            self._bankbuf.discard_staged()
        self.bank = s["bank"]
        self.reta = s["reta"]
        self.failed_queues = s["failed"]
        self.policy = s["policy"]
        self.bucket_load = s["bucket_load"]
        self.telemetry.slot_swaps = s["slot_swaps"]
        self.telemetry.reta_updates = s["reta_updates"]

    def _prestage_epoch(self, rec) -> None:
        """Submit-time hook (``ControlPlane.submit``): stage the epoch's
        SwapSlot params into the shadow bank while traffic keeps flowing,
        so the barrier commit is a pointer flip (DESIGN.md §14).

        Best-effort by design: a busy shadow (another epoch already
        prestaged, or a live prefetch) just defers staging to apply time,
        and obviously-invalid commands are left for ``_validate_command``
        to reject with the normal epoch-atomic semantics."""
        if self._bankbuf is None:
            return
        for cmd in rec.commands:
            if not isinstance(cmd, SwapSlot):
                continue
            if not 0 <= int(cmd.slot) < self.num_slots:
                continue
            try:
                if (jax.tree_util.tree_structure(cmd.params)
                        != jax.tree_util.tree_structure(self.bank)):
                    continue
                self._bankbuf.stage(int(cmd.slot), cmd.params,
                                    token=id(cmd), epoch=rec.epoch)
            except Exception:
                # e.g. leaf-shape mismatch: apply-time validation owns the
                # rejection; drop whatever partially staged
                self._bankbuf.discard_staged()

    def _finish_epoch(self, rec) -> None:
        """Epoch barrier commit: publish every staged SwapSlot by flipping
        which device buffer is active — O(1), no weights move."""
        if self._bankbuf is not None:
            self.bank = self._bankbuf.commit()

    def adopt_bank(self, bank) -> None:
        """Install externally supplied bank contents outside the epoch
        path (trace-replay install, mesh shard resync).  Under double
        buffering the contents are copied into a fresh active buffer so
        staging and flips keep working; otherwise a plain reference
        install."""
        if self._bankbuf is not None:
            self._bankbuf.reseed(bank)
            self.bank = self._bankbuf.active
        else:
            self.bank = self._put_bank(bank)

    def _install_reta(self, reta: np.ndarray) -> None:
        reta = np.asarray(reta, np.int32)
        if reta.min() < 0 or reta.max() >= self.num_queues:
            raise ValueError("RETA entry out of queue range")
        if len(reta) != len(self.bucket_load):
            self.bucket_load = np.zeros(len(reta), np.int64)
        self.reta = reta
        self.telemetry.reta_updates += 1

    def _apply_control(self) -> None:
        """Apply queued epochs at a *fully quiescent* boundary: in-flight
        ticks retire first, so the wrong-verdict counter each epoch
        snapshots has absorbed every pre-epoch tick and per-epoch
        continuity attribution is exact whatever the in-flight window."""
        if self.control.has_pending:
            self.retire_all()
            self.control.apply_pending(self._tick_count)

    def _tick_boundary(self) -> None:
        """Quiescent point between ticks: apply queued control epochs,
        then let the routing policy react to current telemetry (its
        proposal lands as an epoch at the *next* boundary)."""
        self._apply_control()
        consult_policy(self)

    def flush_control(self) -> None:
        """Force-apply pending epochs now (we are between ticks by
        construction when host code runs)."""
        self._apply_control()

    # -- deprecated direct-mutation shims ------------------------------------

    def swap_slot(self, k: int, params) -> None:
        """Deprecated: emits a single-command ``SwapSlot`` epoch."""
        warnings.warn(_DEPRECATION % ("swap_slot", "SwapSlot"),
                      DeprecationWarning, stacklevel=2)
        self.control.submit(SwapSlot(int(k), params))
        self.flush_control()

    def set_reta(self, reta: np.ndarray) -> None:
        """Deprecated: emits a single-command ``ProgramReta`` epoch."""
        warnings.warn(_DEPRECATION % ("set_reta", "ProgramReta"),
                      DeprecationWarning, stacklevel=2)
        self.control.submit(ProgramReta(tuple(np.asarray(reta, np.int32))))
        self.flush_control()

    def fail_queues(self, failed: tuple[int, ...]) -> None:
        """Deprecated: emits a single-command ``FailQueues`` epoch."""
        warnings.warn(_DEPRECATION % ("fail_queues", "FailQueues"),
                      DeprecationWarning, stacklevel=2)
        self.control.submit(FailQueues(tuple(failed)))
        self.flush_control()

    def reset_reta(self) -> None:
        """Deprecated: emits a single-command ``RestoreQueues`` epoch."""
        warnings.warn(_DEPRECATION % ("reset_reta", "RestoreQueues"),
                      DeprecationWarning, stacklevel=2)
        self.control.submit(RestoreQueues())
        self.flush_control()

    # -- data plane ---------------------------------------------------------

    def dispatch(self, packets_np: np.ndarray, now: float | None = None,
                 *, queues: np.ndarray | None = None) -> dict:
        """RSS-dispatch one arrival burst into the per-queue rings.

        The arrival edge is a tick boundary: queued control epochs (RETA
        rewrites in particular) become effective before routing.

        ``queues`` is an optional precomputed per-packet queue-id array:
        the mesh facade resolves (host, queue) from ONE mesh-level hash
        and hands each shard its local ids, so the burst is never hashed
        twice.  The caller then owns per-bucket load accounting; when
        omitted the runtime hashes and resolves through its own RETA.
        """
        with self.spans.span("dp.dispatch"):
            return self._dispatch(packets_np, now, queues)

    def _dispatch(self, packets_np, now, queues) -> dict:
        sp = self.spans
        self._apply_control()
        if self._t_start is None:
            self._t_start = time.perf_counter()
        if now is None:
            now = time.perf_counter()
        packets_np = np.asarray(packets_np)
        if queues is None:
            with sp.span("dp.dispatch.hash"):
                sp.count("dp.rows_hashed", len(packets_np))
                h = rss.toeplitz_hash(rss.flow_words_of(packets_np),
                                      self.rss_key)
                bucket = rss.bucket_index(h, len(self.reta)).astype(np.int64)
                self.bucket_load += np.bincount(bucket,
                                                minlength=len(self.reta))
                q = self.reta[bucket]
        else:
            q = np.asarray(queues, np.int64)
            if q.size and not (0 <= q.min() and q.max() < self.num_queues):
                # a global id handed to a shard would otherwise match no
                # ring and vanish without tripping the conservation audit
                raise ValueError(
                    f"precomputed queue ids out of range for "
                    f"{self.num_queues} queues")
        self.telemetry.touch(now)
        per_queue = []
        with sp.span("dp.dispatch.push"):
            for i, ring in enumerate(self.rings):
                rows = packets_np[q == i]
                admitted = ring.push(rows, now)
                if self._record and admitted < rows.shape[0]:
                    self.dropped_seq.extend(
                        int(s) for s in rows[admitted:, SEQ_WORD])
                if self.on_drop is not None and admitted < rows.shape[0]:
                    self.on_drop(i, rows[admitted:])
                self.telemetry.record_drops(i, int(rows.shape[0]) - admitted)
                per_queue.append({"offered": int(rows.shape[0]),
                                  "admitted": admitted,
                                  "dropped": int(rows.shape[0]) - admitted})
        return {"per_queue": per_queue,
                "dropped": sum(p["dropped"] for p in per_queue)}

    def _host_batch(self, popped) -> np.ndarray:
        """Every queue's pop padded to ``batch`` rows in one fresh
        (Q, B, words) array: a short queue repeats its last row, an empty
        one is zeros.  Results past each queue's count are discarded."""
        words = popped[0][0].shape[1]
        out = np.empty((self.num_queues, self.batch, words), np.uint32)
        for q, (rows, _) in enumerate(popped):
            n = rows.shape[0]
            out[q, :n] = rows
            out[q, n:] = rows[n - 1] if n else 0
        return out

    def _kept_in_flight(self) -> int:
        """Ticks the window leaves in flight after a launch: a fixed
        ``pipeline_depth - 1``, or, derived, 1 while some ring still
        holds a whole batch (the next tick will launch at once) and 0
        otherwise."""
        if self.pipeline_depth is not None:
            return self.pipeline_depth - 1
        return int(any(len(r) >= self.batch for r in self.rings))

    def tick(self) -> int:
        """Pipeline stage 1 (dispatch): pop up to ``batch`` rows per queue
        and issue the workers asynchronously; stage 3 (retire) runs for
        every tick beyond the in-flight window (``_kept_in_flight``)."""
        with self.spans.span("dp.tick"):
            return self._tick()

    def _tick(self) -> int:
        sp = self.spans
        if (self._faults is not None
                and not self._faults.responsive(0, self._tick_count)):
            # injected stall: the tick elapses but the host serves
            # nothing — pending epochs stay queued, rings keep backlog
            self._tick_count += 1
            return 0
        with sp.span("dp.tick.control"):
            self._tick_boundary()
        self._tick_count += 1
        self.telemetry.runtime_ticks += 1
        with sp.span("dp.tick.pop") as pop:
            popped = [ring.pop(self.batch) for ring in self.rings]
        counts = [rows.shape[0] for rows, _ in popped]
        total = sum(counts)
        if total == 0:
            return 0
        if sp.enabled:
            t = pop.ended_s()
            sp.count("dp.rows_popped", total)
            sp.count("dp.ring_wait_ns", round(
                sum(float((t - ts).sum()) for _, ts in popped) * 1e9))
        keep = self._kept_in_flight()
        with sp.span("dp.tick.pad"):
            batch = self._host_batch(popped)
        if self.fanout == "loop":
            groups = [(q,) for q in range(self.num_queues) if counts[q]]
        else:
            groups = [tuple(range(self.num_queues))]
        launches = []
        for queues in groups:
            with sp.span("dp.tick.h2d"):
                host = batch[queues[0]:queues[-1] + 1]
                if self._batch_sharding is not None:
                    # one put: each chip receives its own queues' rows
                    x = jax.device_put(host, self._batch_sharding)
                else:
                    x = jnp.asarray(host)
            sp.count("dp.h2d_bytes", host.nbytes)
            with sp.span("dp.tick.launch"):
                out = self._step(self.bank, x)
            if keep:
                # retired behind a later launch: start its pull now
                out.copy_to_host_async()
            launches.append((queues, out))
            sp.count("dp.kernel_rows", self._kernel_rows)
        sp.count("dp.queue_batches", sum(1 for n in counts if n))
        rec = _InFlight(
            self._tick_count, popped, counts, batch, launches, self.bank)
        self._inflight.append(rec)
        while len(self._inflight) > keep:
            old = self._inflight.popleft()
            if old is not rec:
                sp.count("dp.retire.deferred", 1)
            self._retire(old)
        return total

    def _retire(self, rec: _InFlight) -> None:
        """Pipeline stage 3: block on the tick's device work, then fold
        results into telemetry / audit / record and retire ring rows."""
        sp = self.spans
        with sp.span("dp.retire.wait") as wait:
            for _, out in rec.launches:
                out.block_until_ready()
        now = wait.ended_s()
        for queues, out in rec.launches:
            with sp.span("dp.retire.d2h"):
                # a fresh host array every tick: the taps keep references
                packed = np.asarray(out)
            for i, q in enumerate(queues):
                if rec.counts[q]:
                    self._retire_queue(rec, q, packed[i], now)
        self.telemetry.touch(now)
        if self.telemetry.has_sink:
            with sp.span("dp.retire.telemetry"):
                self.telemetry.emit_delta(
                    tick=rec.tick, now=now,
                    depths=[len(r) for r in self.rings])

    def _retire_queue(self, rec: _InFlight, q: int, packed: np.ndarray,
                      now: float) -> None:
        """Fold queue ``q``'s rows of a retired tick (``packed``: its
        (3, B) slots / verdicts / actions) into taps, telemetry, audit
        and record, and retire its ring rows."""
        sp = self.spans
        n = rec.counts[q]
        rows, ts = rec.popped[q]
        slots, actions = packed[0, :n], packed[2, :n]
        verdicts = packed[1, :n] != 0
        if self.on_retire is not None:
            with sp.span("dp.retire.tap"):
                self.on_retire(q, rows, slots, verdicts, actions, rec.tick)
        with sp.span("dp.retire.telemetry"):
            self.telemetry.record_tick(
                q, slots, verdicts, actions, latency_us=(now - ts) * 1e6)
            self.rings[q].mark_completed(n)
        if self.audit:
            # audit against the bank version this tick was dispatched
            # with — a later epoch must not invalidate earlier work
            with sp.span("dp.retire.audit"):
                exact = pipeline.packet_step(
                    rec.bank, jnp.asarray(rec.batch[q]),
                    num_slots=self.num_slots, strategy="take",
                    backend=self.backend)
                bad = (np.asarray(exact.verdicts)[:n] != verdicts).sum()
                bad += (np.asarray(exact.slots)[:n] != slots).sum()
                self.telemetry.wrong_verdict += int(bad)
        if self._record:
            self.completed_seq[q].extend(int(s) for s in rows[:, SEQ_WORD])
            self.completed_verdicts[q].extend(bool(v) for v in verdicts)
            self.completed_slots[q].extend(int(s) for s in slots)

    def retire_all(self) -> None:
        """Flush the pipeline: retire every in-flight tick (oldest first)."""
        while self._inflight:
            self._retire(self._inflight.popleft())
        if self.telemetry.has_sink:
            # flush counters with no retire to ride on (e.g. trailing
            # dispatch-edge drops) so the delta stream sums to snapshot()
            self.telemetry.emit_delta(tick=self._tick_count)

    def in_flight_rows(self) -> list[int]:
        """Rows popped but not yet retired, per queue (the in-flight
        window's ticks), so conservation is checkable mid-window."""
        out = [0] * self.num_queues
        for rec in self._inflight:
            for q, n in enumerate(rec.counts):
                out[q] += n
        return out

    def drain(self, max_ticks: int = 100_000) -> int:
        """Tick until every ring is empty, then flush the pipeline.
        Returns the number of rows served."""
        return drain_rings(self, max_ticks)

    # -- audit + reporting --------------------------------------------------

    def audit_conservation(self) -> dict:
        """Per-queue + aggregate packet conservation; must always hold —
        including mid-pipeline, where popped-but-unretired rows are
        accounted as ``in_flight``."""
        inflight = self.in_flight_rows()
        per_queue = [ring.conservation(in_flight=inflight[q])
                     for q, ring in enumerate(self.rings)]
        totals = {k: sum(c[k] for c in per_queue)
                  for k in ("offered", "admitted", "dropped", "completed",
                            "occupancy", "in_flight")}
        ok = all(c["producer_ok"] and c["consumer_ok"] for c in per_queue)
        return {"per_queue": per_queue, "totals": totals, "ok": ok,
                "wrong_verdict": self.telemetry.wrong_verdict}

    def snapshot(self) -> dict:
        """One-call runtime report: telemetry totals, conservation audit,
        configuration echo, and control-plane stats."""
        elapsed = (time.perf_counter() - self._t_start
                   if self._t_start is not None else None)
        out = self.telemetry.snapshot(elapsed_s=elapsed)
        out["conservation"] = self.audit_conservation()
        out["fanout"] = self.fanout
        out["strategy"] = self.strategy
        out["backend"] = _ops._resolve(self.backend)
        out["pipeline_depth"] = self.pipeline_depth
        out["policy"] = getattr(self.policy, "name", None)
        out["control"] = self.control.stats()
        return out
