"""Span/trace-event model for control-plane epochs and health leases.

An epoch's life is submit -> stage -> (barrier) -> commit | rollback.
``EpochRecord`` already timestamps the endpoints: ``submitted_s`` at
submit, ``apply_latency_us`` submit->effective, ``apply_us`` for the
stage+apply window alone.  ``epoch_event`` folds those into a span dict
(queued time = latency - apply) suitable for a timeline renderer, and
``health_event`` does the same for ``HealthMonitor`` transitions.

``epoch_log_doc`` is the ONE serializer for the machine-readable epoch
log — the ``/epochs`` API endpoint and ``--epoch-log-json`` both call
it, so the wire formats cannot drift apart.

``HostSpans`` times the data plane's own host calls: named, nested spans
inside ``DataplaneRuntime.dispatch``/``tick`` and counters beside them,
off until ``enable()`` is called (DESIGN.md §11).
"""

from __future__ import annotations

import heapq
import time

from repro.control.plane import API_VERSION, EpochRecord

#: The top-level span whose longest instances ``HostSpans`` keeps.
TICK = "dp.tick"


class _NoSpan:
    """The span a disabled recorder hands out: one shared object that
    reads no clock on entry or exit."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def ended_s(self) -> float:
        """A fresh read of the host clock, in seconds."""
        return time.perf_counter()


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("_rec", "name", "_ann", "t0", "t1", "child_ns")

    def __init__(self, rec: "HostSpans", name: str):
        self._rec = rec
        self.name = name
        self._ann = None
        self.child_ns = 0

    def __enter__(self):
        rec = self._rec
        if rec.annotate:
            self._ann = rec._annotation(self.name)
            self._ann.__enter__()
        rec._stack.append(self)
        self.t0 = rec._clock()
        return self

    def __exit__(self, *exc) -> bool:
        rec = self._rec
        self.t1 = rec._clock()
        rec._close(self)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return False

    def ended_s(self) -> float:
        """The clock read at the span's exit, in seconds."""
        return self.t1 / 1e9


class HostSpans:
    """Named host spans and counters, recorded in memory.

    Disabled (the default), ``span(name)`` returns one shared no-op
    context and ``count`` returns at once.  After ``enable()`` each span
    reads ``clock`` (``time.perf_counter_ns``) on entry and exit, and
    every name accumulates its count, total, self (total less the spans
    opened inside it) and longest time.  The ``LONGEST`` longest
    top-level ``dp.tick`` spans are kept with the self time of each name
    inside them, so a stall shows which call held it.  With
    ``annotate=True`` each span is also a ``jax.profiler.TraceAnnotation``
    of the same name: a host event on the profiler's clock, beside the
    device's ops.
    """

    LONGEST = 8

    def __init__(self, clock=time.perf_counter_ns):
        self.enabled = False
        self.annotate = False
        self._clock = clock
        self._annotation = None
        self._stack: list[_Span] = []
        self.reset()

    def enable(self, *, annotate: bool = False) -> None:
        """Start recording; ``annotate`` also emits profiler events."""
        if annotate:
            import jax
            self._annotation = jax.profiler.TraceAnnotation
        self.annotate = bool(annotate)
        self.enabled = True

    def disable(self) -> None:
        """Stop recording; what was recorded stays until ``reset``."""
        self.enabled = False
        self.annotate = False

    def reset(self) -> None:
        """Forget every span, counter and kept tick."""
        self._acc: dict[str, list[int]] = {}
        self.counters: dict[str, int] = {}
        self._ticks: list = []        # min-heap of (total_ns, seq, self_ns)
        self._tick_self: dict[str, int] = {}
        self._seq = 0

    def span(self, name: str):
        """Context manager timing one call of ``name``."""
        if not self.enabled:
            return _NO_SPAN
        return _Span(self, name)

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to counter ``name`` (nothing when disabled)."""
        if self.enabled:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    def _close(self, sp: _Span) -> None:
        stack = self._stack
        stack.pop()
        dt = sp.t1 - sp.t0
        own = dt - sp.child_ns
        acc = self._acc.get(sp.name)
        if acc is None:
            acc = self._acc[sp.name] = [0, 0, 0, 0]
        acc[0] += 1
        acc[1] += dt
        acc[2] += own
        if dt > acc[3]:
            acc[3] = dt
        if stack:
            stack[-1].child_ns += dt
            if stack[0].name == TICK:
                ts = self._tick_self
                ts[sp.name] = ts.get(sp.name, 0) + own
        elif sp.name == TICK:
            ts, self._tick_self = self._tick_self, {}
            ts[TICK] = own
            self._seq += 1
            item = (dt, self._seq, ts)
            if len(self._ticks) < self.LONGEST:
                heapq.heappush(self._ticks, item)
            elif dt > self._ticks[0][0]:
                heapq.heapreplace(self._ticks, item)

    def snapshot(self) -> dict:
        """Plain dicts: ``spans`` (name -> count, total_ns, self_ns,
        max_ns), ``counters``, and ``slowest_ticks`` (longest first,
        each with ``total_ns`` and the ``self_ns`` of every name in it)."""
        return {
            "spans": {name: {"count": a[0], "total_ns": a[1],
                             "self_ns": a[2], "max_ns": a[3]}
                      for name, a in self._acc.items()},
            "counters": dict(self.counters),
            "slowest_ticks": [{"total_ns": t, "self_ns": dict(ts)}
                              for t, _, ts in sorted(self._ticks,
                                                     reverse=True)],
        }


def tick_summary(snap: dict) -> dict | None:
    """The ``dp.tick`` spans of a ``HostSpans.snapshot()`` in
    microseconds: how many, their mean and longest, and the self time
    per tick of each tick-loop span (``dp.tick*``, ``dp.retire.*``).
    None when no tick was recorded."""
    tick = snap["spans"].get(TICK)
    if not tick:
        return None
    n = tick["count"]
    return {
        "ticks": n,
        "mean_us": tick["total_ns"] / n / 1e3,
        "max_us": tick["max_ns"] / 1e3,
        "self_us_per_tick": {
            name: s["self_ns"] / n / 1e3
            for name, s in snap["spans"].items()
            if name.startswith((TICK, "dp.retire."))},
    }


def epoch_event(rec: EpochRecord) -> dict:
    """One epoch record as a stream event with an embedded span."""
    doc = rec.as_dict()
    queued_us = None
    if rec.apply_latency_us is not None and rec.apply_us is not None:
        queued_us = max(0.0, rec.apply_latency_us - rec.apply_us)
    doc.update({
        "kind": "epoch",
        "span": {
            "submitted_s": rec.submitted_s,
            # time spent queued waiting for a quiescent tick boundary
            # (and, on meshes, for the cross-host barrier)
            "queued_us": queued_us,
            "apply_us": rec.apply_us,
            "total_us": rec.apply_latency_us,
            "outcome": rec.commit_mode,
        },
    })
    return doc


def health_event(tr) -> dict:
    """One ``HealthMonitor`` transition as a stream event."""
    return {"kind": "health", **tr.as_dict()}


def epoch_log_doc(runtime) -> dict:
    """The full machine-readable epoch log for ``runtime`` (single-host
    or mesh): per-epoch spans, commit-mode counts, continuity audit,
    health transitions, and injected fault events when present."""
    control = runtime.control
    doc = {
        "api_version": API_VERSION,
        "epochs": [epoch_event(rec) for rec in control.log],
        "stats": control.stats(),
        "continuity": control.continuity_audit(),
    }
    health = getattr(runtime, "health", None)
    if health is not None:
        doc["health"] = health.snapshot()  # states + transitions
    faults = getattr(runtime, "_faults", None)
    if faults is not None and getattr(faults, "events", None):
        doc["fault_events"] = [dict(e) for e in faults.events]
    deploy = getattr(runtime, "deploy_log", None)
    if deploy:
        # deployment decision trail (repro.deploy): canary start/promote/
        # rollback, retrain triggers, auto-remediation actions — each tied
        # to its typed epoch id in "epochs" above
        doc["deployments"] = [dict(d) for d in deploy]
    return doc
