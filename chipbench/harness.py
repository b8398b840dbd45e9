"""The harness's hold on the program: timed calls, taps and compile events.

``Harness`` wraps the runtime's public surface -- ``dispatch``, ``tick``,
``control.submit`` -- so that each call is a host span (seconds and count
per kind, and a ``TraceAnnotation`` when the profiler runs), and keeps
through the ``on_retire``/``on_drop`` taps what ``correct`` and the
latencies need: stamps, slots, verdicts, actions, the tick that served
them and the host clock when they reached the harness.  It never keeps a
packet; the check remakes packets from the seed.

Epochs apply at the entry of the runtime call that follows their submit,
and a tick serves every epoch applied before it.  The harness counts its
own ``tick`` calls, so it knows, from its own submissions alone, which
models each tick served.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

SEQ_WORD = 15
clock = time.perf_counter


class CompileEvents:
    """Programs compiled or loaded from the persistent cache, with the
    host clock of each, from JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.EVENTS[0]:
            self.times.append(clock())

    def _on_event(self, event, **_):
        if event == self.EVENTS[1]:
            self.times.append(clock())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


class FullCollections:
    """Host-clock start and length of each full (generation 2) collection
    of Python's garbage collector: a pause of the whole process, which
    stalls every packet behind it."""

    def __init__(self):
        import gc
        self.spans: list[tuple[float, float]] = []
        self._t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = clock()
        elif self._t0 is not None:
            self.spans.append((self._t0, clock() - self._t0))
            self._t0 = None

    def between(self, t0: float, t1: float) -> list[float]:
        return [d for t, d in self.spans if t0 <= t <= t1]


class Epoch:
    __slots__ = ("slot", "model", "submit_t", "epoch_id", "applied_tick",
                 "returned_t")

    def __init__(self, slot, model, submit_t, epoch_id):
        self.slot, self.model = slot, model
        self.submit_t, self.epoch_id = submit_t, epoch_id
        self.applied_tick = None
        self.returned_t = None


class Harness:
    """Timed, tapped calls into one ``DataplaneRuntime``."""

    def __init__(self, rt, *, annotate: bool = False):
        self.rt = rt
        self.annotate = annotate
        self.ticks = 0
        self.recording = False
        self.timing = False
        self.spans: dict[str, list] = {}
        self.epochs: list[Epoch] = []
        self._unapplied: list[Epoch] = []
        self.retired: list[tuple] = []   # (seqs, slots, verdicts, actions, tick, t)
        self.dropped: list[np.ndarray] = []
        self.offered = 0                 # timed rows dispatched
        self.late: list[np.ndarray] = []
        self.submit_us: list[float] = []
        rt.on_retire = self._on_retire
        rt.on_drop = self._on_drop

    # -- recording --------------------------------------------------------

    def start(self) -> None:
        """Begin the window's records (spans, taps, epochs)."""
        self.spans = {}
        self.timing = True
        self.retired, self.dropped, self.late = [], [], []
        self.epochs, self.submit_us = [], []
        self.offered = 0
        self.recording = True

    def _on_retire(self, queue, rows, slots, verdicts, actions, tick):
        if self.recording:
            self.retired.append((rows[:, SEQ_WORD].copy(), slots, verdicts,
                                 actions, tick, clock()))

    def _on_drop(self, queue, rows):
        if self.recording:
            self.dropped.append(rows[:, SEQ_WORD].copy())

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = clock()
        try:
            yield
        finally:
            dt = clock() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            if self.timing:
                s = self.spans.setdefault(name, [0.0, 0])
                s[0] += dt
                s[1] += 1

    # -- the runtime's calls ----------------------------------------------

    def _enter_call(self) -> None:
        for e in self._unapplied:
            e.applied_tick = self.ticks

    def _leave_call(self) -> None:
        if self._unapplied:
            t = clock()
            for e in self._unapplied:
                e.returned_t = t
            self._unapplied = []

    def dispatch(self, rows: np.ndarray, due: np.ndarray | None = None) -> None:
        """Offer ``rows``; ``due`` (host clock per row) records lateness."""
        now = clock()
        if self.recording:
            self.offered += rows.shape[0]
            if due is not None:
                self.late.append(now - due)
        self._enter_call()
        with self.span("dispatch"):
            self.rt.dispatch(rows, now=now)
        self._leave_call()

    def tick(self) -> int:
        self._enter_call()
        with self.span("tick"):
            n = self.rt.tick()
        self.ticks += 1
        self._leave_call()
        return n

    def submit(self, slot: int, params, model: int) -> None:
        """Submit a one-command ``SwapSlot`` epoch installing ``model``."""
        from repro.control import SwapSlot
        t = clock()
        with self.span("submit"):
            eid = self.rt.control.submit(SwapSlot(int(slot), params))
        e = Epoch(int(slot), int(model), t, eid)
        self._unapplied.append(e)
        if self.recording:
            self.epochs.append(e)
            self.submit_us.append((clock() - t) * 1e6)

    def waiting(self) -> int:
        """Rows in the rings (offered, not yet popped by a tick)."""
        return sum(len(r) for r in self.rt.rings)

    def in_flight(self) -> int:
        """Rows popped by a tick and not yet retired."""
        return sum(self.rt.in_flight_rows())

    def flush(self) -> None:
        """Retire every tick in flight (the rings are empty).  Epochs do
        not apply here: ``retire_all`` is no tick boundary."""
        with self.span("tick"):
            self.rt.retire_all()

    def drain(self) -> None:
        """Tick until the rings are empty, then retire what is in flight.
        The window's spans end here; its taps go on recording, so that
        every packet it offered is checked."""
        self.timing = False
        while self.waiting() or self._unapplied:
            self.tick()
        self.flush()

    # -- after the window ---------------------------------------------------

    def served(self):
        """Concatenated retirements: seqs, slots, verdicts, actions, tick
        and retire time per packet."""
        if not self.retired:
            z = np.zeros(0, np.int64)
            return z, z, z.astype(bool), z.astype(np.int32), z, z.astype(float)
        seqs = np.concatenate([r[0] for r in self.retired]).astype(np.int64)
        slots = np.concatenate([np.asarray(r[1]) for r in self.retired])
        verdicts = np.concatenate([np.asarray(r[2]) for r in self.retired])
        actions = np.concatenate([np.asarray(r[3]) for r in self.retired])
        counts = [r[0].shape[0] for r in self.retired]
        ticks = np.repeat([r[4] for r in self.retired], counts)
        times = np.repeat([r[5] for r in self.retired], counts)
        return seqs, slots, verdicts, actions, ticks, times

    def dropped_seqs(self) -> np.ndarray:
        if not self.dropped:
            return np.zeros(0, np.int64)
        return np.concatenate(self.dropped).astype(np.int64)

    def tick_work(self, t0: float, t1: float) -> list:
        """``(packets, distinct slots)`` of each tick retired in
        ``[t0, t1]``: the least a tick reads is its packets and each of
        its slots' weights once, however the program launches it."""
        by_tick: dict = {}
        for r in self.retired:
            if t0 <= r[5] <= t1:
                by_tick.setdefault(r[4], []).append(r[1])
        return [(sum(np.asarray(s).shape[0] for s in v),
                 int(np.unique(np.concatenate(v)).shape[0]))
                for v in by_tick.values()]
