"""Device-resident megastep (DESIGN.md §13): the deferred N-tick scan
window must be observationally identical to N sequential ``tick()``
calls — verdicts, slots, actions, telemetry count totals, epoch apply
ticks — including mid-window SwapSlot / ProgramReta epochs, and the
engine must fall back to the sequential loop whenever the configuration
needs per-tick host control (fault injection, non-fused strategies)."""

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro import deploy
from repro.control import ProgramReta, SwapSlot
from repro.core import executor, packet as pkt
from repro.dataplane import DataplaneRuntime, faults
from repro.dataplane.workloads.phases import SEQ_WORD
from repro.obs import TelemetryStream, attach

NUM_QUEUES = 2
NUM_SLOTS = 2
BATCH = 8


@pytest.fixture(scope="module")
def bank2():
    return executor.init_bank(jax.random.PRNGKey(0), NUM_SLOTS)


def _make_bursts(seed: int, sizes: list[int]) -> list[np.ndarray]:
    """Per-tick bursts over a tiny payload pool: repeated suffixes (the
    megastep's dedup fast path) mixed with per-packet word-0 twists and
    a few fully unique payloads (the no-sharing path)."""
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, 2**32, (3, pkt.PAYLOAD_WORDS), dtype=np.uint32)
    seq = 0
    bursts = []
    for n in sizes:
        if n == 0:
            bursts.append(np.zeros((0, pkt.PACKET_WORDS), np.uint32))
            continue
        payload = pool[rng.integers(0, pool.shape[0], n)].copy()
        payload[:, 0] ^= rng.integers(0, 2**32, n, dtype=np.uint32)
        unique = rng.random(n) < 0.2  # some rows share no suffix at all
        payload[unique] = rng.integers(
            0, 2**32, (int(unique.sum()), pkt.PAYLOAD_WORDS), dtype=np.uint32)
        rows = pkt.make_packets(
            rng.integers(0, NUM_SLOTS, n).astype(np.int32), payload)
        rows[:, pkt.CONTROL_WORD_LO] = rng.integers(0, 2, n).astype(np.uint32)
        rows[:, SEQ_WORD] = np.arange(seq, seq + n, dtype=np.uint32)
        seq += n
        bursts.append(rows)
    return bursts


def _drive(bank, bursts, epochs, megastep_ticks, *, audit=True,
           fault_injector=None, strategy="fused"):
    rt = DataplaneRuntime(
        bank, num_queues=NUM_QUEUES, strategy=strategy, batch=BATCH,
        ring_capacity=256, audit=audit, record=True,
        megastep_ticks=megastep_ticks, fault_injector=fault_injector)
    for t, burst in enumerate(bursts):
        for cmd in epochs.get(t, ()):
            rt.control.submit(cmd)
        rt.dispatch(burst)
        rt.tick()
    rt.drain()
    return rt


def _observed(rt) -> tuple:
    """Everything the bit-exactness contract covers, as one comparable
    value: per-queue completion streams, counting telemetry, epoch apply
    ticks.  (Wall-clock fields — latency — are excluded.)"""
    queues = []
    for q, qs in enumerate(rt.snapshot()["queues"]):
        queues.append((
            tuple(rt.completed_seq[q]),
            tuple(rt.completed_verdicts[q]),
            tuple(rt.completed_slots[q]),
            qs["completed"], qs["dropped"],
            tuple(qs["per_slot_total"]), tuple(qs["per_slot_malicious"]),
            tuple(sorted(qs["actions"].items())),
        ))
    epochs = tuple((r.applied_tick, type(r.commands[0]).__name__)
                   for r in rt.control.log if r.applied)
    return (tuple(queues), epochs, rt.telemetry.slot_swaps,
            rt.telemetry.reta_updates)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    sizes=st.lists(st.integers(0, 24), min_size=3, max_size=10),
    window=st.sampled_from([2, 3, 8]),
    swap_at=st.integers(0, 9),
    reta_at=st.integers(0, 9),
)
def test_megastep_equals_sequential(bank2, seed, sizes, window, swap_at,
                                    reta_at):
    """megastep(n) == n sequential ticks, bit for bit, with SwapSlot and
    ProgramReta epochs landing mid-window (both runs audited)."""
    bursts = _make_bursts(seed, sizes)
    epochs = {
        swap_at: [SwapSlot(swap_at % NUM_SLOTS,
                           executor.init_params(jax.random.PRNGKey(seed)))],
    }
    epochs.setdefault(reta_at, []).append(
        ProgramReta(tuple(int(x) for x in (np.arange(16) + reta_at)
                          % NUM_QUEUES)))
    rt_seq = _drive(bank2, bursts, epochs, 1)
    rt_meg = _drive(bank2, bursts, epochs, window)
    assert rt_meg._mega is not None  # the deferred engine actually ran
    assert _observed(rt_seq) == _observed(rt_meg)
    assert rt_seq.telemetry.wrong_verdict == 0
    assert rt_meg.telemetry.wrong_verdict == 0
    assert rt_seq.audit_conservation()["ok"]
    assert rt_meg.audit_conservation()["ok"]


def test_fault_injection_falls_back_to_sequential(bank2):
    """An armed injector needs per-tick host control: the runtime must
    run the sequential loop (no megastep engine) and still pass the
    audits through an injected stall."""
    plan = faults.FaultPlan(faults=(faults.StallHost(0, 2, 2),))
    bursts = _make_bursts(7, [16] * 8)
    rt = _drive(bank2, bursts, {}, 8,
                fault_injector=faults.FaultInjector(plan))
    assert rt._mega is None
    assert rt.telemetry.wrong_verdict == 0
    assert rt.audit_conservation()["ok"]
    # same traffic without the stall, deferred: identical completions
    # once both runs drain (the stall only delays, never drops)
    rt_meg = _drive(bank2, bursts, {}, 8)
    for q in range(NUM_QUEUES):
        assert sorted(rt.completed_seq[q]) == sorted(rt_meg.completed_seq[q])


def test_non_fused_strategies_fall_back_to_sequential(bank2):
    """The megastep's batched forward replicates the fused/ref path only;
    other strategies keep the per-tick loop (contract trivially holds)."""
    bursts = _make_bursts(11, [12] * 4)
    rt = _drive(bank2, bursts, {}, 8, strategy="take")
    assert rt._mega is None
    assert rt.audit_conservation()["ok"]


def test_megastep_batched_retires_respect_sampler_and_stream_bounds(bank2):
    """Whole-megastep drains hand the deploy/obs taps a window's worth of
    retires back to back: ``PacketSampler.max_pending`` must still bound
    the labeling backlog, and ``TelemetryStream`` overflow accounting
    must stay conserved (``next_sid == buffered + dropped_events``)."""
    pool, labels = deploy.labeled_pool(samples_per_group=64, seed=0)
    oracle = deploy.LabelOracle(pool, labels)
    rt = DataplaneRuntime(bank2, num_queues=NUM_QUEUES, strategy="fused",
                          batch=16, ring_capacity=1024, megastep_ticks=8)
    max_pending = 3
    sampler = deploy.PacketSampler(oracle, num_slots=NUM_SLOTS, per_tick=8,
                                   max_pending=max_pending).attach(rt)
    stream = TelemetryStream(capacity=4)  # tiny: force real overflow
    attach(rt, stream)
    flush_sizes = []
    orig_flush = sampler.flush
    sampler.flush = lambda: (flush_sizes.append(len(sampler._pending)),
                             orig_flush())[-1]
    rng = np.random.default_rng(0)
    peak = 0
    for _ in range(40):
        idx = rng.integers(0, pool.shape[0], 48)
        rt.dispatch(pkt.make_packets(
            rng.integers(0, NUM_SLOTS, 48).astype(np.int32), pool[idx]))
        rt.tick()
        peak = max(peak, len(sampler._pending))
    rt.drain()
    peak = max(peak, len(sampler._pending))
    sampler.detach()  # final flush
    completed = rt.snapshot()["completed_total"]
    assert completed > 0
    assert sampler.seen == completed
    # the backlog bound held across every batched retire burst
    assert peak <= max_pending
    assert max(flush_sizes, default=0) <= max_pending
    assert sampler.labeled + sampler.unknown == sampler.sampled
    # stream conservation: every event is either retained or counted out
    s = stream.snapshot_stats()
    assert s["next_sid"] == s["buffered"] + s["dropped_events"]
    assert s["dropped_events"] > 0  # the tiny ring really overflowed


@pytest.mark.parametrize("backend,megastep_ticks,engine", [
    ("ref", 8, "megastep"),
    ("ref", 1, "sequential"),
    ("pallas", 8, "sequential"),   # the TPU's auto backend: no megastep
])
def test_snapshot_reports_engine(bank2, backend, megastep_ticks, engine):
    """The run report names the engine that ran, so a megastep request
    that falls back to the per-tick loop is visible."""
    rt = DataplaneRuntime(bank2, num_queues=NUM_QUEUES, strategy="fused",
                          batch=BATCH, backend=backend,
                          megastep_ticks=megastep_ticks)
    snap = rt.snapshot()
    assert rt.engine == snap["engine"] == engine
    assert snap["backend"] == backend
