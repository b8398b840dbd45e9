"""Transactional, epoch-stamped control plane for the data-plane runtime.

The paper's core split — switching is a *data-plane* act, residency is a
*control-plane* act — only holds up if the control side has real
semantics.  This module gives it three:

* **Epochs are atomic.**  Commands submitted together apply together,
  in submission order, between two ticks; no packet ever observes half
  an epoch.
* **Application happens at tick boundaries only.**  ``submit`` never
  touches the runtime; the runtime calls ``apply_pending`` when it is
  quiescent between ticks (entry of ``dispatch``/``tick``).  In-flight
  device work keeps the bank/RETA version it was dispatched with.
* **Everything is logged.**  Each applied epoch records its id, the
  tick it became effective, the serialized command deltas, and two
  wall-clock latencies: submit-to-effective (the paper's control-plane
  update window, subsuming ``switching.measure_update_latency_us``) and
  the apply cost itself.  ``continuity_audit`` joins the log with the
  runtime's wrong-verdict counter so every epoch can prove it corrupted
  zero packets.

The ``ControlPlane`` object is the ONLY sanctioned mutation path; the
legacy ``DataplaneRuntime.swap_slot/set_reta/fail_queues`` methods are
deprecation shims that emit single-command epochs through it.

The same object fronts a multi-host mesh unchanged: a ``MeshDataplane``
implements the runtime protocol this plane drives — ``_validate_command``
is the *stage* phase (every host validates its projection, none mutates;
one host's rejection rejects the whole epoch), ``_apply_command`` is the
*commit* phase (every host applies between the same two mesh ticks), and
``_control_state``/``_rollback_control_state`` snapshot mesh-wide so a
failed commit rolls back every host, not just the one that raised.
Mesh runtimes stamp ``EpochRecord.host_ticks`` with the per-host apply
tick — all equal, the epoch-barrier proof in the log itself.

API v3 adds fault tolerance (DESIGN.md §10).  Every epoch now ends in
exactly one of three recorded outcomes (``EpochRecord.commit_mode``):
``"atomic"`` (every host staged, applied, and acked), ``"degraded"``
(a quorum of live hosts committed while dead/unacked hosts were failed
over), or ``"rollback"`` (staging, apply, or quorum failed and the
snapshot restored every host).  Failures that are *chaos inputs* —
injected shard errors, lost quorum — subclass ``NonFatalControlError``:
their epoch rolls back and is logged, but ``apply_pending`` keeps
draining the queue instead of unwinding the run.  A mesh runtime may
expose ``_finish_epoch(rec)``; it is called inside the transaction
after the last command applies, and is where quorum is counted and the
commit mode stamped — raising there rolls the epoch back like any
apply-time failure.

The in-memory log is bounded: ``log_capacity`` evicts the oldest
records into a compressed spill (zlib + msgpack chunks, the workload
trace codec), each stamped with its closed wrong-verdict window first,
so slot-thrash regimes (one epoch per tick) run in O(capacity) memory
while ``continuity_audit`` still proves every spilled window was clean.
"""

from __future__ import annotations

import dataclasses
import struct
import time
import zlib

import msgpack

from repro.control.commands import (API_VERSION, COMMAND_KINDS, Command,
                                    SwapSlot)

#: Spill-file framing: magic + u8 version, then length-prefixed chunks.
SPILL_MAGIC = b"BSWELOG1"

#: The only outcomes an epoch may end in.
COMMIT_MODES = ("atomic", "degraded", "rollback")


class NonFatalControlError(Exception):
    """An epoch failure that is an expected chaos outcome, not a bug:
    the epoch rolls back atomically and is logged with its error, but
    ``apply_pending`` continues with the next epoch instead of raising.
    Injected shard faults and lost commit quorums subclass this."""


@dataclasses.dataclass
class EpochRecord:
    """One applied (or pending) epoch in the command log."""
    epoch: int
    commands: tuple[Command, ...]
    summaries: tuple[dict, ...]        # describe() frozen at submit time
    submitted_s: float                 # perf_counter at submit
    applied_tick: int | None = None    # runtime tick the epoch preceded
    apply_latency_us: float | None = None  # submit -> effective
    apply_us: float | None = None          # apply duration alone
    wrong_verdict_at_apply: int | None = None
    error: str | None = None           # set when the epoch was rejected
    # one of COMMIT_MODES once the epoch has been decided; None while
    # pending ("atomic" = all hosts, "degraded" = quorum of live hosts,
    # "rollback" = rejected and snapshot restored everywhere)
    commit_mode: str | None = None
    # mesh runtimes stamp the per-host tick each epoch became effective
    # at (equal across barrier participants); None on single-host
    # runtimes and on rolled-back epochs
    host_ticks: tuple[int, ...] | None = None

    @property
    def applied(self) -> bool:
        return self.applied_tick is not None

    def as_dict(self) -> dict:
        return {
            "epoch": self.epoch,
            "api_version": API_VERSION,
            "commands": list(self.summaries),
            "applied_tick": self.applied_tick,
            "apply_latency_us": self.apply_latency_us,
            "apply_us": self.apply_us,
            "error": self.error,
            "commit_mode": self.commit_mode,
            "host_ticks": (list(self.host_ticks)
                           if self.host_ticks is not None else None),
        }


class ControlPlane:
    """Epoch queue + command log in front of one ``DataplaneRuntime``."""

    API_VERSION = API_VERSION

    def __init__(self, runtime, *, log_capacity: int | None = None,
                 spill_path: str | None = None):
        if log_capacity is not None and log_capacity < 1:
            raise ValueError("log_capacity must be >= 1 (or None)")
        self._runtime = runtime
        self._next_epoch = 1
        self._pending: list[EpochRecord] = []
        self._log: list[EpochRecord] = []
        self._log_capacity = log_capacity
        self._spill_path = spill_path
        self._spill_chunks: list[bytes] = []   # when no spill_path given
        self._spill_header_written = False
        self.spilled_epochs = 0
        self._spilled_wrong = 0
        self._mode_counts = {m: 0 for m in COMMIT_MODES}
        # observability tap: called with each EpochRecord as it lands in
        # the log (committed AND rolled-back epochs) — obs.attach wires
        # this into a TelemetryStream as span events
        self.on_record = None

    # -- submission ---------------------------------------------------------

    def submit(self, *commands: Command) -> int:
        """Queue one atomic epoch; returns its id.  Nothing is applied
        until the runtime reaches a tick boundary.

        Runtimes with a double-buffered bank expose ``_prestage_epoch``;
        it runs here, after the epoch is queued, so SwapSlot payloads
        start staging into the shadow bank immediately — overlapped with
        the traffic still flowing — and the eventual barrier commit is a
        pointer flip (DESIGN.md §14).  Prestaging is best-effort and
        mutates no runtime-visible state."""
        if not commands:
            raise ValueError("an epoch needs at least one command")
        for c in commands:
            if not isinstance(c, COMMAND_KINDS):
                raise TypeError(f"not a control command: {c!r}")
        rec = EpochRecord(
            epoch=self._next_epoch,
            commands=tuple(commands),
            summaries=tuple(c.describe() for c in commands),
            submitted_s=time.perf_counter(),
        )
        self._next_epoch += 1
        self._pending.append(rec)
        prestage = getattr(self._runtime, "_prestage_epoch", None)
        if prestage is not None:
            prestage(rec)
        return rec.epoch

    @property
    def pending(self) -> list[EpochRecord]:
        """Epochs queued but not yet applied (a defensive copy)."""
        return list(self._pending)

    @property
    def has_pending(self) -> bool:
        """Whether any epoch is queued for the next tick boundary."""
        return bool(self._pending)

    # -- application (runtime-side, tick boundary only) ---------------------

    def apply_pending(self, tick: int) -> list[EpochRecord]:
        """Apply every queued epoch atomically, in submission order.

        Called by the runtime when it is quiescent between ticks; user
        code should not call this directly (submit and let the next
        tick boundary pick it up, or use ``runtime.flush_control()``).
        """
        applied = []
        finish = getattr(self._runtime, "_finish_epoch", None)
        while self._pending:
            rec = self._pending.pop(0)
            t0 = time.perf_counter()
            state = self._runtime._control_state()
            try:
                # validate the WHOLE epoch up front (catches bad commands
                # before any work); the state snapshot backstops apply-time
                # failures validation cannot see (e.g. commands that only
                # conflict with each other) — either way a rejected epoch
                # mutates nothing (atomicity) and is logged with its error
                for cmd in rec.commands:
                    self._runtime._validate_command(cmd)
                for cmd in rec.commands:
                    self._runtime._apply_command(cmd)
                # mesh runtimes count commit acks / stamp host_ticks and
                # commit_mode here; a lost quorum raises and rolls back
                if finish is not None:
                    finish(rec)
            except Exception as e:
                self._runtime._rollback_control_state(state)
                rec.error = f"{type(e).__name__}: {e}"
                rec.commit_mode = "rollback"
                rec.host_ticks = None
                rec.wrong_verdict_at_apply = \
                    self._runtime.telemetry.wrong_verdict
                self._append_log(rec)
                if isinstance(e, NonFatalControlError):
                    continue
                raise
            t1 = time.perf_counter()
            rec.applied_tick = tick
            rec.apply_us = (t1 - t0) * 1e6
            rec.apply_latency_us = (t1 - rec.submitted_s) * 1e6
            rec.wrong_verdict_at_apply = \
                self._runtime.telemetry.wrong_verdict
            if rec.commit_mode is None:
                rec.commit_mode = "atomic"
            self._append_log(rec)
            applied.append(rec)
        return applied

    # -- bounded log + spill -------------------------------------------------

    def _append_log(self, rec: EpochRecord) -> None:
        self._strip_payloads(rec)
        if rec.commit_mode in self._mode_counts:
            self._mode_counts[rec.commit_mode] += 1
        if self.on_record is not None:
            self.on_record(rec)
        self._log.append(rec)
        cap = self._log_capacity
        if cap is not None and len(self._log) > cap:
            evicted, self._log = self._log[:-cap], self._log[-cap:]
            self._spill(evicted)

    def _spill(self, evicted: list[EpochRecord]) -> None:
        """Close each evicted record's wrong-verdict window (its
        successor is still known here) and push the batch out as one
        compressed chunk in the trace codec."""
        succ = self._log[0] if self._log else None
        docs = []
        for i, rec in enumerate(evicted):
            nxt = evicted[i + 1] if i + 1 < len(evicted) else succ
            doc = rec.as_dict()
            window = None
            if (nxt is not None and rec.wrong_verdict_at_apply is not None
                    and nxt.wrong_verdict_at_apply is not None):
                window = (nxt.wrong_verdict_at_apply
                          - rec.wrong_verdict_at_apply)
                self._spilled_wrong += window
            doc["wrong_verdict_in_window"] = window
            docs.append(doc)
        self.spilled_epochs += len(docs)
        blob = zlib.compress(
            msgpack.packb(docs, use_bin_type=True), 6)
        if self._spill_path is not None:
            mode = "ab" if self._spill_header_written else "wb"
            with open(self._spill_path, mode) as f:
                if not self._spill_header_written:
                    f.write(SPILL_MAGIC)
                f.write(struct.pack("<I", len(blob)))
                f.write(blob)
            self._spill_header_written = True
        else:
            self._spill_chunks.append(blob)

    def spilled_records(self) -> list[dict]:
        """Decode in-memory spill chunks (oldest first)."""
        out: list[dict] = []
        for blob in self._spill_chunks:
            out.extend(msgpack.unpackb(zlib.decompress(blob), raw=False))
        return out

    @staticmethod
    def _strip_payloads(rec: EpochRecord) -> None:
        """Drop delivered weight pytrees from logged SwapSlot commands:
        the log keeps the serialized summary (``delta_bytes``), never the
        payload, so a long-lived runtime does not pin every model it has
        ever swapped in."""
        if any(isinstance(c, SwapSlot) and c.params is not None
               for c in rec.commands):
            rec.commands = tuple(
                dataclasses.replace(c, params=None) if isinstance(c, SwapSlot)
                else c for c in rec.commands)

    # -- observability ------------------------------------------------------

    @property
    def log(self) -> list[EpochRecord]:
        """The in-memory epoch log, oldest first (a defensive copy)."""
        return list(self._log)

    def command_log(self) -> list[dict]:
        """The auditable, serializable command log."""
        return [rec.as_dict() for rec in self._log]

    def continuity_audit(self) -> dict:
        """Per-epoch continuity: wrong-verdict packets attributed to the
        window each epoch opened (its apply to the next epoch's apply,
        or to now for the last one).  With the runtime in audit mode, an
        all-zero column proves no command kind ever corrupted a verdict.
        """
        wrong_now = self._runtime.telemetry.wrong_verdict
        epochs = []
        for i, rec in enumerate(self._log):
            nxt = (self._log[i + 1].wrong_verdict_at_apply
                   if i + 1 < len(self._log) else wrong_now)
            epochs.append({
                "epoch": rec.epoch,
                "applied_tick": rec.applied_tick,
                "commands": [s["cmd"] for s in rec.summaries],
                "commit_mode": rec.commit_mode,
                "wrong_verdict_in_window": nxt - rec.wrong_verdict_at_apply,
            })
        ok = (wrong_now == 0
              and all(e["wrong_verdict_in_window"] == 0 for e in epochs)
              and self._spilled_wrong == 0)
        out = {
            "api_version": API_VERSION,
            "epochs": epochs,
            "commit_modes": dict(self._mode_counts),
            "spilled_epochs": self.spilled_epochs,
            "spilled_wrong_verdict": self._spilled_wrong,
            "wrong_verdict_total": wrong_now,
            "ok": ok,
        }
        # degraded commits must also conserve packets — including those
        # stranded on dead hosts — so fold the runtime's conservation
        # audit in when it offers one (mesh and audited runtimes do)
        cons_fn = getattr(self._runtime, "audit_conservation", None)
        if cons_fn is not None:
            cons = cons_fn()
            out["conservation_ok"] = bool(cons["ok"])
            if "stranded" in cons:
                out["stranded"] = cons["stranded"]
            out["ok"] = ok and bool(cons["ok"])
        return out

    def stats(self) -> dict:
        """Aggregate epoch latencies for telemetry snapshots."""
        applied = [r for r in self._log if r.applied]
        lat = [r.apply_latency_us for r in applied]
        return {
            "api_version": API_VERSION,
            "epochs_applied": len(applied),
            "epochs_pending": len(self._pending),
            "epochs_spilled": self.spilled_epochs,
            "commit_modes": dict(self._mode_counts),
            "apply_latency_us_max": max(lat) if lat else None,
        }


def load_epoch_spill(path: str) -> list[dict]:
    """Read a spill file written by a capacity-bounded ``ControlPlane``
    back into epoch dicts (oldest first)."""
    with open(path, "rb") as f:
        magic = f.read(len(SPILL_MAGIC))
        if magic != SPILL_MAGIC:
            raise ValueError(f"not an epoch spill file: {path}")
        out: list[dict] = []
        while True:
            head = f.read(4)
            if not head:
                return out
            (n,) = struct.unpack("<I", head)
            out.extend(msgpack.unpackb(zlib.decompress(f.read(n)),
                                       raw=False))
