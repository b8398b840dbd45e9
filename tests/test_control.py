"""Control-plane API: epoch semantics, deprecation shims, command
interleaving invariants, pipelined-tick parity, and adaptive routing
policies (DESIGN.md §7)."""

import json
import warnings

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.control import (ControlPlane, DropRateRebalance, FailQueues,
                           LeastDepth, PolicyView, ProgramReta, RestoreQueues,
                           SetPolicy, StaticReta, SwapSlot, make_policy)
from repro.core import executor, packet as pkt
from repro.dataplane import (DataplaneRuntime, Phase, elephant_skew_phases,
                             emergency_phases, phase_commands, play, render,
                             rss, scenarios)
from repro.dataplane.workloads.phases import SEQ_WORD


@pytest.fixture(scope="module")
def bank2():
    return executor.init_bank(jax.random.PRNGKey(0), 2)


@pytest.fixture(scope="module")
def spare_params():
    return (executor.init_params(jax.random.PRNGKey(41)),
            executor.init_params(jax.random.PRNGKey(42)))


def small_phases(num_slots=2):
    uniform = tuple(1.0 / num_slots for _ in range(num_slots))
    return [
        Phase("steady", ticks=2, burst=64, flows=16, slot_mix=uniform),
        Phase("crowd", ticks=2, burst=192, flows=4, slot_mix=uniform),
        Phase("churn", ticks=2, burst=64, flows=16, slot_mix=uniform,
              failed_queues=(0,), swap_slot=1),
    ]


def make_rt(bank, **kw):
    kw.setdefault("num_queues", 4)
    kw.setdefault("strategy", "take")
    kw.setdefault("batch", 32)
    kw.setdefault("ring_capacity", 4096)
    return DataplaneRuntime(bank, **kw)


# ---------------------------------------------------------------------------
# epoch semantics
# ---------------------------------------------------------------------------

def test_epoch_applies_only_at_tick_boundary(bank2):
    rt = make_rt(bank2)
    before = rt.reta.copy()
    new = tuple(np.roll(rss.indirection_table(4), 1))
    epoch = rt.control.submit(ProgramReta(new))
    # submit never touches the runtime
    assert (rt.reta == before).all()
    assert [r.epoch for r in rt.control.pending] == [epoch]
    assert rt.telemetry.reta_updates == 0
    rt.tick()  # boundary (empty rings still cross it)
    assert (rt.reta == np.asarray(new)).all()
    assert not rt.control.pending
    rec = rt.control.log[-1]
    assert rec.epoch == epoch and rec.applied
    assert rec.apply_us > 0 and rec.apply_latency_us >= rec.apply_us


def test_epoch_is_atomic_and_ordered(bank2, spare_params):
    rt = make_rt(bank2)
    # two epochs: the first fails a queue and swaps a slot atomically,
    # the second restores — applied in submission order at one boundary
    e1 = rt.control.submit(FailQueues((0,)), SwapSlot(1, spare_params[0]))
    e2 = rt.control.submit(RestoreQueues())
    rt.flush_control()
    assert [r.epoch for r in rt.control.log] == [e1, e2]
    assert rt.telemetry.slot_swaps == 1
    assert rt.telemetry.reta_updates == 2       # failover then restore
    assert (rt.reta == rss.indirection_table(4)).all()
    assert rt.failed_queues == set()


def test_command_log_is_serializable(bank2, spare_params):
    rt = make_rt(bank2)
    rt.control.submit(SwapSlot(0, spare_params[0]),
                      ProgramReta(tuple(rss.indirection_table(4))))
    rt.control.submit(SetPolicy(LeastDepth()))
    rt.flush_control()
    log = rt.control.command_log()
    blob = json.dumps(log)  # must round-trip as JSON
    assert json.loads(blob) == log
    swap = log[0]["commands"][0]
    assert swap["cmd"] == "swap_slot" and swap["delta_bytes"] > 0
    assert log[1]["commands"][0]["policy"] == "least-depth"
    assert all(rec["api_version"] == ControlPlane.API_VERSION for rec in log)


def test_invalid_commands_rejected_atomically(bank2, spare_params):
    rt = make_rt(bank2)
    with pytest.raises(ValueError):
        rt.control.submit()
    with pytest.raises(TypeError):
        rt.control.submit("swap please")
    # a rejected epoch is atomic: the valid SwapSlot ahead of the bad
    # ProgramReta must NOT apply, and the rejection lands in the log
    rt.control.submit(SwapSlot(1, spare_params[0]),
                      ProgramReta(tuple([7] * rss.RETA_SIZE)))
    with pytest.raises(ValueError):
        rt.flush_control()
    assert rt.telemetry.slot_swaps == 0
    rec = rt.control.log[-1]
    assert rec.error and not rec.applied
    assert rt.control.command_log()[-1]["error"] == rec.error
    with pytest.raises(ValueError):  # failing every queue is unservable
        rt.control.submit(FailQueues((0, 1, 2, 3)))
        rt.flush_control()
    assert rt.failed_queues == set()


def test_conflicting_epoch_rolls_back_atomically(bank2):
    """Commands that are individually valid but conflict with each other
    fail at apply time; the state snapshot rolls EVERYTHING back."""
    rt = make_rt(bank2)
    rt.control.submit(FailQueues((0,)), FailQueues((1, 2, 3)))
    with pytest.raises(ValueError):
        rt.flush_control()
    assert rt.failed_queues == set()            # first command rolled back
    assert (rt.reta == rss.indirection_table(4)).all()
    assert rt.telemetry.reta_updates == 0
    assert rt.control.log[-1].error
    # phantom queue ids are rejected up front, not absorbed forever
    rt.control.submit(FailQueues((4,)))
    with pytest.raises(ValueError):
        rt.flush_control()
    assert rt.failed_queues == set()


def test_sequentially_valid_epoch_applies(bank2):
    """An epoch whose commands are only valid in order (restore one queue,
    then fail another) must apply — commands see their predecessors."""
    rt = make_rt(bank2)
    rt.control.submit(FailQueues((1, 2, 3)))
    rt.flush_control()
    rt.control.submit(RestoreQueues((1,)), FailQueues((0,)))
    rt.flush_control()                          # must not raise
    assert rt.failed_queues == {0, 2, 3}
    assert set(rt.reta.tolist()) == {1}         # queue 1 carries everything
    assert rt.control.log[-1].error is None


def test_render_rejects_bad_elephant_phases():
    bad_queue = [Phase("skew", ticks=1, burst=8, flows=8, slot_mix=(1.0,),
                       elephant_flows=2, elephant_queue=7)]
    with pytest.raises(ValueError, match="out of range"):
        render(bad_queue, num_slots=1, seed=0, num_queues=4)
    all_elephants = [Phase("skew", ticks=1, burst=8, flows=2, slot_mix=(1.0,),
                           elephant_flows=2, elephant_queue=0)]
    with pytest.raises(ValueError, match="elephant_flows"):
        render(all_elephants, num_slots=1, seed=0, num_queues=4)


def test_log_does_not_pin_swap_payloads(bank2, spare_params):
    rt = make_rt(bank2)
    rt.control.submit(SwapSlot(1, spare_params[0]))
    rt.flush_control()
    rec = rt.control.log[-1]
    assert rec.commands[0].params is None       # payload dropped after apply
    assert rec.summaries[0]["delta_bytes"] > 0  # but the delta size is kept
    assert rt.control.command_log()[-1]["commands"][0]["delta_bytes"] > 0


def test_policy_survives_reta_resize(bank2):
    """Installing a RETA of a different size must not crash the policy's
    delta tracking (the deltas restart instead)."""
    trace = render(small_phases(), num_slots=2, seed=9)
    bursts = [b for ph in trace.bursts for b in ph]
    rt = make_rt(bank2, policy=LeastDepth())
    rt.dispatch(bursts[0])
    rt.tick()                                   # seeds _last_load (len 128)
    rt.control.submit(ProgramReta(tuple(rss.indirection_table(4, 64))))
    rt.dispatch(bursts[1])                      # resize applies here
    rt.tick()                                   # must not raise
    rt.drain()
    assert len(rt.reta) == 64
    assert rt.audit_conservation()["ok"]


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def _drive(rt, bursts):
    for b in bursts:
        rt.dispatch(b)
        rt.tick()
    rt.drain()


def test_shims_warn_and_match_explicit_epochs(bank2, spare_params):
    trace = render(small_phases(), num_slots=2, seed=3)
    bursts = [b for ph in trace.bursts for b in ph]
    mid = len(bursts) // 2

    def run(mutate):
        rt = make_rt(bank2, record=True, audit=True)
        _drive(rt, bursts[:mid])
        mutate(rt)
        _drive(rt, bursts[mid:])
        return rt

    def via_shims(rt):
        with pytest.warns(DeprecationWarning):
            rt.swap_slot(1, spare_params[1])
        with pytest.warns(DeprecationWarning):
            rt.fail_queues((2,))
        with pytest.warns(DeprecationWarning):
            rt.set_reta(rss.failover_table(rt.reta, (3,), num_queues=4))
        with pytest.warns(DeprecationWarning):
            rt.reset_reta()

    def via_epochs(rt):
        rt.control.submit(SwapSlot(1, spare_params[1]))
        rt.control.submit(FailQueues((2,)))
        rt.control.submit(ProgramReta(
            tuple(rss.failover_table(
                rss.failover_table(rt.reta, (2,), num_queues=4),
                (3,), num_queues=4))))
        rt.control.submit(RestoreQueues())

    a, b = run(via_shims), run(via_epochs)
    assert a.completed_seq == b.completed_seq
    assert a.completed_verdicts == b.completed_verdicts
    assert a.completed_slots == b.completed_slots
    assert (a.reta == b.reta).all()
    assert a.telemetry.wrong_verdict == b.telemetry.wrong_verdict == 0
    # the shim path went through the control plane: everything is logged
    assert len(a.control.log) >= 4


# ---------------------------------------------------------------------------
# property: epoch interleavings preserve conservation + per-queue FIFO
# ---------------------------------------------------------------------------

_OP = st.sampled_from(
    ["dispatch", "tick", "fail", "restore", "reta", "swap", "policy"])


@settings(max_examples=12, deadline=None)
@given(ops=st.lists(_OP, min_size=4, max_size=24), seed=st.integers(0, 2**31))
def test_epoch_interleaving_invariants(ops, seed, bank2, spare_params):
    """Any interleaving of valid command epochs with traffic keeps the
    ring conservation invariants and per-queue FIFO ordering;
    ``audit_conservation`` holds after every single epoch."""
    rng = np.random.default_rng(seed)
    trace = render(small_phases(), num_slots=2, seed=seed % 97)
    bursts = [b for ph in trace.bursts for b in ph]
    rt = make_rt(bank2, ring_capacity=64, record=True,
                 pipeline_depth=1 + seed % 3)
    sent = 0
    for op in ops:
        if op == "dispatch":
            if sent < len(bursts):  # each burst once: seq stamps stay unique
                rt.dispatch(bursts[sent])
                sent += 1
        elif op == "tick":
            rt.tick()
        elif op == "fail":
            rt.control.submit(FailQueues((1 + rng.integers(3),)))
        elif op == "restore":
            rt.control.submit(RestoreQueues())
        elif op == "reta":
            rt.control.submit(ProgramReta(
                tuple(rng.integers(0, 4, rss.RETA_SIZE))))
        elif op == "swap":
            rt.control.submit(SwapSlot(int(rng.integers(2)),
                                       spare_params[rng.integers(2)]))
        elif op == "policy":
            rt.control.submit(SetPolicy(
                [None, StaticReta(), LeastDepth()][rng.integers(3)]))
        aud = rt.audit_conservation()
        assert aud["ok"], (op, aud)
    rt.drain()
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["totals"]["occupancy"] == 0
    assert aud["totals"]["in_flight"] == 0
    for seqs in rt.completed_seq:            # FIFO within every queue
        assert (np.diff(np.asarray(seqs)) > 0).all()
    done = [s for qs in rt.completed_seq for s in qs]
    assert len(done) == len(set(done))       # no duplication across queues
    assert len(done) + len(rt.dropped_seq) == aud["totals"]["offered"]


# ---------------------------------------------------------------------------
# pipelined ticks: bit-identical to the synchronous loop
# ---------------------------------------------------------------------------

def test_pipelined_ticks_bit_identical_on_emergency(bank2):
    trace = render(emergency_phases(2), num_slots=2, seed=0)
    runs = {}
    for depth in (1, 4):
        rt = make_rt(bank2, batch=128, record=True, pipeline_depth=depth)
        play(rt, trace)
        aud = rt.audit_conservation()
        assert aud["ok"] and aud["totals"]["completed"] == trace.total_packets
        runs[depth] = (rt.completed_seq, rt.completed_verdicts,
                       rt.completed_slots)
    assert runs[1] == runs[4]


def test_pipeline_window_accounts_in_flight(bank2, rng):
    rt = make_rt(bank2, num_queues=2, batch=16, pipeline_depth=3)
    rows = pkt.make_packets(
        np.zeros(64, np.int64),
        rng.integers(0, 2**32, (64, pkt.PAYLOAD_WORDS), dtype=np.uint32))
    rows[:, rss.FLOW_WORD_LO : rss.FLOW_WORD_LO + rss.FLOW_WORDS] = \
        rng.integers(0, 2**32, (64, rss.FLOW_WORDS), dtype=np.uint32)
    rt.dispatch(rows)
    rt.tick()
    rt.tick()
    aud = rt.audit_conservation()
    assert aud["ok"]                          # holds mid-pipeline
    assert aud["totals"]["in_flight"] > 0     # window actually open
    rt.drain()
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["totals"]["in_flight"] == 0
    assert aud["totals"]["completed"] == 64


# ---------------------------------------------------------------------------
# the derived window (pipeline_depth=None): in flight only while backlogged
# ---------------------------------------------------------------------------

def _queued_rows(rng, per_queue, num_queues=2):
    """``per_queue`` rows for each queue, with the queue ids to dispatch
    them to (round robin, so every queue gets exactly ``per_queue``)."""
    n = per_queue * num_queues
    rows = pkt.make_packets(
        rng.integers(0, 2, n),
        rng.integers(0, 2**32, (n, pkt.PAYLOAD_WORDS), dtype=np.uint32))
    rows[:, SEQ_WORD] = np.arange(n)
    return rows, np.arange(n) % num_queues


def test_derived_window_defers_a_backlogged_tick(bank2, rng):
    """With a ring still holding a whole batch after the pop, ``tick()``
    leaves its launch in flight and the next ``tick()`` retires it; the
    last tick, which empties the rings, retires at once."""
    rt = make_rt(bank2, num_queues=2, batch=16, record=True)
    assert rt.pipeline_depth is None
    rows, qs = _queued_rows(rng, 48)
    rt.dispatch(rows, queues=qs)
    done = lambda: sum(len(s) for s in rt.completed_seq)  # noqa: E731
    rt.tick()
    assert rt.in_flight_rows() == [16, 16] and done() == 0
    assert rt.audit_conservation()["ok"]
    rt.tick()
    assert rt.in_flight_rows() == [16, 16] and done() == 32
    rt.tick()
    assert rt.in_flight_rows() == [0, 0] and done() == 96
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["totals"]["completed"] == 96


def test_derived_window_retires_at_once_below_one_batch(bank2, rng):
    """With every ring below one batch after the pop, no tick stays in
    flight: a lightly loaded stream waits no extra tick."""
    rt = make_rt(bank2, num_queues=2, batch=16, record=True)
    for per_queue in (8, 16, 3, 16):
        rows, qs = _queued_rows(rng, per_queue)
        rt.dispatch(rows, queues=qs)
        rt.tick()
        assert rt.in_flight_rows() == [0, 0]
        assert sum(len(r) for r in rt.rings) == 0
    assert rt.audit_conservation()["totals"]["completed"] == 2 * 43


def test_derived_window_bit_identical_on_emergency(bank2):
    """The derived window serves the per-queue seqs, verdicts and slots
    of ``pipeline_depth=1`` on the emergency storyline, and engages
    there (its backlogged ticks retire behind a later launch)."""
    trace = render(emergency_phases(2), num_slots=2, seed=0)
    runs = {}
    for depth in (1, None):
        rt = make_rt(bank2, batch=32, record=True, pipeline_depth=depth)
        rt.spans.enable()
        play(rt, trace)
        aud = rt.audit_conservation()
        assert aud["ok"] and aud["totals"]["completed"] == trace.total_packets
        runs[depth] = (rt.completed_seq, rt.completed_verdicts,
                       rt.completed_slots)
        deferred = rt.spans.counters.get("dp.retire.deferred", 0)
        assert (deferred > 0) == (depth is None)
    assert runs[1] == runs[None]


def test_epoch_during_a_deferred_tick_keeps_continuity(bank2, spare_params,
                                                       rng):
    """A swap submitted while a backlogged tick is in flight applies only
    after that tick retires (against the bank it was dispatched with):
    zero wrong verdicts, continuity holds, and the streams equal the
    synchronous loop's."""
    rows, qs = _queued_rows(rng, 64)
    runs = {}
    for depth in (1, None):
        rt = make_rt(bank2, num_queues=2, batch=16, record=True, audit=True,
                     strategy="fused", pipeline_depth=depth)
        rt.dispatch(rows, queues=qs)
        rt.tick()
        if depth is None:
            assert sum(rt.in_flight_rows()) == 32
        rt.control.submit(SwapSlot(1, spare_params[0]))
        rt.tick()
        rt.control.submit(FailQueues((1,)), ProgramReta(
            tuple(np.zeros(rss.RETA_SIZE, np.int64))))
        rt.tick()
        rt.drain()
        cont = rt.control.continuity_audit()
        assert cont["ok"], cont
        assert all(e["wrong_verdict_in_window"] == 0 for e in cont["epochs"])
        assert rt.telemetry.slot_swaps == 1
        aud = rt.audit_conservation()
        assert aud["ok"] and aud["wrong_verdict"] == 0
        assert aud["totals"]["completed"] == 128
        runs[depth] = (rt.completed_seq, rt.completed_verdicts,
                       rt.completed_slots)
    assert runs[1] == runs[None]


def test_deferred_counter_counts_ticks_retired_behind_a_launch(bank2, rng):
    """``dp.retire.deferred`` counts the ticks retired behind a later
    launch: of five backlogged ticks the first four, not the last, which
    empties the rings; the synchronous loop counts none."""
    rows, qs = _queued_rows(rng, 80)
    for depth, want in ((None, 4), (1, 0)):
        rt = make_rt(bank2, num_queues=2, batch=16, pipeline_depth=depth)
        rt.spans.enable()
        rt.dispatch(rows, queues=qs)
        for _ in range(5):
            rt.tick()
        c = rt.spans.counters
        assert rt.spans.snapshot()["spans"]["dp.tick.launch"]["count"] == 5
        assert c.get("dp.retire.deferred", 0) == want
        assert sum(rt.in_flight_rows()) == 0


def _make_bursts(seed: int, sizes: list[int]) -> list[np.ndarray]:
    """Per-tick bursts over a tiny payload pool: repeated payloads with
    per-packet word-0 twists mixed with a few fully unique payloads, and
    a per-packet sequence stamp."""
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
        unique = rng.random(n) < 0.2
        payload[unique] = rng.integers(
            0, 2**32, (int(unique.sum()), pkt.PAYLOAD_WORDS), dtype=np.uint32)
        rows = pkt.make_packets(rng.integers(0, 2, n).astype(np.int32),
                                payload)
        rows[:, pkt.CONTROL_WORD_LO] = rng.integers(0, 2, n).astype(np.uint32)
        rows[:, SEQ_WORD] = np.arange(seq, seq + n, dtype=np.uint32)
        seq += n
        bursts.append(rows)
    return bursts


def _observed(rt) -> tuple:
    """Everything the window must not change, as one comparable value:
    per-queue completion streams, counting telemetry, epoch apply ticks.
    (Wall-clock fields — latency — are excluded.)"""
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


@pytest.mark.parametrize("depth", [None, 2, 3])
@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    sizes=st.lists(st.integers(0, 24), min_size=3, max_size=10),
    swap_at=st.integers(0, 9),
    reta_at=st.integers(0, 9),
)
def test_pipeline_window_equals_synchronous(bank2, depth, seed, sizes,
                                            swap_at, reta_at):
    """Any in-flight window serves what ``pipeline_depth=1`` serves, bit
    for bit, over random burst sizes with a SwapSlot and a ProgramReta
    epoch landing at random ticks (both runs audited)."""
    bursts = _make_bursts(seed, sizes)
    epochs = {swap_at: [SwapSlot(
        swap_at % 2, executor.init_params(jax.random.PRNGKey(seed)))]}
    epochs.setdefault(reta_at, []).append(ProgramReta(
        tuple(int(x) for x in (np.arange(16) + reta_at) % 2)))
    runs = []
    for d in (1, depth):
        rt = make_rt(bank2, num_queues=2, strategy="fused", batch=8,
                     ring_capacity=256, audit=True, record=True,
                     pipeline_depth=d)
        for t, burst in enumerate(bursts):
            for cmd in epochs.get(t, ()):
                rt.control.submit(cmd)
            rt.dispatch(burst)
            rt.tick()
        rt.drain()
        assert rt.telemetry.wrong_verdict == 0
        assert rt.audit_conservation()["ok"]
        runs.append(_observed(rt))
    assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# continuity: zero wrong verdicts across EVERY command kind
# ---------------------------------------------------------------------------

def test_zero_wrong_verdict_across_all_command_kinds(bank2):
    phases = small_phases() + elephant_skew_phases(2, 4, ticks=4)
    trace = render(phases, num_slots=2, seed=5, num_queues=4)
    rt = make_rt(bank2, ring_capacity=128, audit=True, pipeline_depth=2)
    rt.control.submit(SetPolicy(LeastDepth()))
    play(rt, trace)
    cont = rt.control.continuity_audit()
    kinds = {c for e in cont["epochs"] for c in e["commands"]}
    assert kinds >= {"set_policy", "restore_queues", "fail_queues",
                     "swap_slot", "program_reta"}, kinds
    assert cont["ok"], cont
    assert all(e["wrong_verdict_in_window"] == 0 for e in cont["epochs"])
    assert rt.audit_conservation()["wrong_verdict"] == 0


# ---------------------------------------------------------------------------
# routing policies
# ---------------------------------------------------------------------------

def test_elephant_skew_targets_one_queue():
    t1 = render(elephant_skew_phases(2, 4), num_slots=2, seed=0, num_queues=4)
    t2 = render(elephant_skew_phases(2, 4), num_slots=2, seed=0, num_queues=4)
    for a, b in zip(t1.bursts[1], t2.bursts[1]):
        assert (a == b).all()                 # replayable
    skew_rows = np.concatenate(t1.bursts[1])
    q = rss.queue_of(skew_rows, 4)
    share = (q == 0).mean()
    assert share > 0.7                        # elephants crush queue 0
    with pytest.raises(ValueError):           # elephants need num_queues
        render(elephant_skew_phases(2, 4), num_slots=2, seed=0)


def test_adaptive_policy_beats_static_on_elephant_skew(bank2):
    trace = render(elephant_skew_phases(2, 4), num_slots=2, seed=0,
                   num_queues=4)
    max_drop = {}
    for policy in (StaticReta(), LeastDepth(), DropRateRebalance()):
        rt = make_rt(bank2, batch=64, ring_capacity=256, policy=policy)
        play(rt, trace)
        aud = rt.audit_conservation()
        assert aud["ok"]
        max_drop[policy.name] = max(q["dropped"] for q in aud["per_queue"])
        if policy.name != "static":           # rebalances are real epochs
            assert any(isinstance(c, ProgramReta)
                       for r in rt.control.log for c in r.commands)
    assert max_drop["static"] > 0             # skew actually hurts
    assert max_drop["least-depth"] < max_drop["static"]
    assert max_drop["drop-rate"] < max_drop["static"]


def test_policy_respects_failed_queues(bank2):
    trace = render(elephant_skew_phases(2, 4), num_slots=2, seed=1,
                   num_queues=4)
    rt = make_rt(bank2, batch=64, ring_capacity=256, policy=LeastDepth())
    rt.control.submit(FailQueues((3,)))
    for phase_bursts in trace.bursts:         # no play(): its per-phase
        for burst in phase_bursts:            # RestoreQueues would undo
            rt.dispatch(burst)                # the failover under test
            rt.tick()
    rt.drain()
    assert 3 not in set(rt.reta.tolist())     # never rebalanced onto a dead queue
    assert rt.audit_conservation()["ok"]


def test_make_policy_registry():
    assert make_policy("least-depth").name == "least-depth"
    assert make_policy("drop-rate").name == "drop-rate"
    assert make_policy("static").propose(
        PolicyView(tick=0, num_queues=2, reta=rss.indirection_table(2),
                   queue_depth=np.zeros(2, np.int64),
                   queue_dropped=np.zeros(2, np.int64),
                   bucket_load=np.zeros(rss.RETA_SIZE, np.int64))) is None
    with pytest.raises(ValueError):
        make_policy("hrl-someday")
