"""Multi-host mesh data plane: global-queue-id RETA, cross-host failover
affinity, hosts=1 bit-identity, mesh-wide conservation + per-host FIFO,
epoch-barrier fan-out with atomic cross-host rollback, mesh policies,
and telemetry merge (DESIGN.md §8)."""

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.control import (FailQueues, LeastDepth, ProgramReta, RestoreQueues,
                           SetPolicy, StaticReta, SwapSlot)
from repro.core import executor
from repro.dataplane import (DataplaneRuntime, MeshDataplane, Phase,
                             cascading_failover_phases, emergency_phases,
                             make_scenario, play, render, rss, scenarios,
                             telemetry)
from repro.launch import mesh as mesh_lib


@pytest.fixture(scope="module")
def bank2():
    return executor.init_bank(jax.random.PRNGKey(0), 2)


@pytest.fixture(scope="module")
def spare_params():
    return executor.init_params(jax.random.PRNGKey(41))


def small_phases(num_slots=2, total_queues=4):
    """Fast mesh storyline: backpressure, whole-host failover, churn."""
    uniform = tuple(1.0 / num_slots for _ in range(num_slots))
    half = tuple(range(total_queues // 2))      # host 0 on a 2-host mesh
    return [
        Phase("steady", ticks=2, burst=64, flows=16, slot_mix=uniform),
        Phase("crowd", ticks=2, burst=192, flows=4, slot_mix=uniform),
        Phase("churn", ticks=2, burst=64, flows=16, slot_mix=uniform,
              failed_queues=half, swap_slot=1),
    ]


def make_mesh(bank, *, hosts=2, num_queues=2, **kw):
    kw.setdefault("strategy", "take")
    kw.setdefault("batch", 32)
    kw.setdefault("ring_capacity", 4096)
    return MeshDataplane(bank, hosts=hosts, num_queues=num_queues, **kw)


# ---------------------------------------------------------------------------
# global-queue-id RETA
# ---------------------------------------------------------------------------

def test_global_queue_id_roundtrip():
    gids = rss.global_queue_id(np.array([0, 1, 2]), np.array([3, 0, 1]), 4)
    assert gids.tolist() == [3, 4, 9]
    host, queue = rss.split_host_queue(gids, 4)
    assert host.tolist() == [0, 1, 2] and queue.tolist() == [3, 0, 1]


def test_mesh_indirection_degenerates_to_single_host():
    assert (rss.mesh_indirection_table(1, 4)
            == rss.indirection_table(4)).all()
    t = rss.mesh_indirection_table(2, 4)
    host, queue = rss.split_host_queue(t, 4)
    assert set(host.tolist()) == {0, 1}         # both hosts referenced
    assert set(queue.tolist()) == {0, 1, 2, 3}


def test_mesh_queue_of_spreads_hosts(rng):
    from repro.core import packet as pkt
    pkts = pkt.make_packets(
        np.zeros(256, np.int64),
        rng.integers(0, 2**32, (256, pkt.PAYLOAD_WORDS), dtype=np.uint32))
    pkts[:, rss.FLOW_WORD_LO : rss.FLOW_WORD_LO + rss.FLOW_WORDS] = \
        rng.integers(0, 2**32, (256, rss.FLOW_WORDS), dtype=np.uint32)
    host, queue = rss.mesh_queue_of(pkts, 2, 4)
    assert set(host.tolist()) == {0, 1}
    assert queue.min() >= 0 and queue.max() < 4
    # mesh dispatch at hosts=1 IS single-host dispatch
    h1, q1 = rss.mesh_queue_of(pkts, 1, 4)
    assert (h1 == 0).all()
    assert (q1 == rss.queue_of(pkts, 4)).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 4), st.integers(1, 4),
       st.integers(1, 6))
def test_property_cross_host_failover_preserves_survivor_affinity(
        seed, hosts, queues, n_failed):
    """Cross-host RETA failover never remaps a flow whose (host, queue)
    both survive: buckets pointing at surviving global ids keep their
    exact (host, queue), only dead buckets move — and they move onto
    survivors."""
    rng = np.random.default_rng(seed)
    total = hosts * queues
    reta = rng.integers(0, total, rss.RETA_SIZE).astype(np.int32)
    failed = tuple(sorted(rng.choice(total, size=min(n_failed, total - 1),
                                     replace=False).tolist()))
    if not failed:
        return
    fo = rss.mesh_failover_table(reta, failed, num_hosts=hosts,
                                 num_queues=queues)
    dead = np.isin(reta, failed)
    assert (fo[~dead] == reta[~dead]).all()     # survivors never remapped
    assert not np.isin(fo, failed).any()        # dead pairs fully drained
    # flows: any flow hashing to a surviving bucket keeps its (host, queue)
    fw = rng.integers(0, 2**32, (64, rss.FLOW_WORDS), dtype=np.uint32)
    b = rss.bucket_index(rss.toeplitz_hash(fw), len(reta))
    survives = ~dead[b]
    h0, q0 = rss.split_host_queue(reta[b], queues)
    h1, q1 = rss.split_host_queue(fo[b], queues)
    assert (h1[survives] == h0[survives]).all()
    assert (q1[survives] == q0[survives]).all()


# ---------------------------------------------------------------------------
# hosts=1 is the degenerate mesh: bit-identical to DataplaneRuntime
# ---------------------------------------------------------------------------

def test_hosts1_bit_identical_to_runtime(bank2):
    trace = render(small_phases(), num_slots=2, seed=3)
    kw = dict(strategy="fused", batch=32, ring_capacity=64, record=True)
    rt = DataplaneRuntime(bank2, num_queues=4, **kw)
    play(rt, trace)
    m1 = MeshDataplane(bank2, hosts=1, num_queues=4, **kw)
    play(m1, trace)
    assert m1.completed_seq == rt.completed_seq
    assert m1.completed_verdicts == rt.completed_verdicts
    assert m1.completed_slots == rt.completed_slots
    assert m1.dropped_seq == rt.dropped_seq
    assert (m1.reta == rt.reta).all()
    a, b = rt.audit_conservation(), m1.audit_conservation()
    assert a["totals"] == b["totals"] and b["ok"]
    sa, sb = rt.snapshot(), m1.snapshot()
    assert sa["completed_total"] == sb["completed_total"]
    assert sa["slot_swaps"] == sb["slot_swaps"] == 1
    assert sa["reta_updates"] == sb["reta_updates"]


# ---------------------------------------------------------------------------
# mesh conservation + per-host FIFO
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hosts,queues", [(2, 2), (3, 2)])
def test_mesh_conservation_and_per_host_fifo(bank2, hosts, queues):
    total = hosts * queues
    trace = render(small_phases(total_queues=total), num_slots=2, seed=11)
    mesh = make_mesh(bank2, hosts=hosts, num_queues=queues,
                     ring_capacity=64, record=True)
    play(mesh, trace)
    aud = mesh.audit_conservation()
    assert aud["ok"], aud
    t = aud["totals"]
    # offered == admitted + dropped summed across hosts, nothing vanishes
    assert t["offered"] == t["admitted"] + t["dropped"]
    assert t["offered"] == t["completed"] + t["dropped"] == trace.total_packets
    assert t["dropped"] > 0                     # crowd forced real drops
    for h in aud["per_host"]:
        assert h["ok"]
    # per-queue FIFO per host: sequence stamps strictly increase
    for shard in mesh.shards:
        for seqs in shard.completed_seq:
            assert (np.diff(np.asarray(seqs)) > 0).all()
    # every offered packet accounted exactly once across the whole mesh
    done = [s for qs in mesh.completed_seq for s in qs]
    allseq = done + mesh.dropped_seq
    assert len(allseq) == len(set(allseq)) == trace.total_packets


def test_dispatch_rejects_out_of_range_precomputed_queues(bank2, rng):
    """A global id handed to a shard must raise, not vanish silently
    past the conservation audit."""
    from repro.core import packet as pkt
    rt = DataplaneRuntime(bank2, num_queues=2, batch=8, ring_capacity=64)
    rows = pkt.make_packets(
        np.zeros(4, np.int64),
        rng.integers(0, 2**32, (4, pkt.PAYLOAD_WORDS), dtype=np.uint32))
    with pytest.raises(ValueError, match="out of range"):
        rt.dispatch(rows, queues=np.array([0, 1, 2, 3]))
    rt.dispatch(rows, queues=np.array([0, 1, 1, 0]))    # in range: fine
    assert rt.rings[0].counters.offered == 2
    assert rt.rings[1].counters.offered == 2


def test_mesh_failover_drains_dead_host(bank2):
    trace = render(small_phases(), num_slots=2, seed=1)
    mesh = make_mesh(bank2, hosts=2, num_queues=2)
    host0 = tuple(range(mesh.num_queues_per_host))
    mesh.control.submit(FailQueues(host0))
    mesh.flush_control()
    hostpart, _ = rss.split_host_queue(mesh.reta, mesh.num_queues_per_host)
    assert not (hostpart == 0).any()            # no bucket points at host 0
    for burst in trace.bursts[0]:
        mesh.dispatch(burst)
    assert all(r.counters.offered == 0 for r in mesh.shards[0].rings)
    assert sum(r.counters.offered for r in mesh.shards[1].rings) > 0
    mesh.drain()
    assert mesh.audit_conservation()["ok"]


# ---------------------------------------------------------------------------
# epoch barrier: same tick on every host, atomic cross-host rollback
# ---------------------------------------------------------------------------

def test_epoch_barrier_applies_at_same_tick_on_all_hosts(bank2, spare_params):
    trace = render(small_phases(), num_slots=2, seed=6)
    bursts = [b for ph in trace.bursts for b in ph]
    mesh = make_mesh(bank2, hosts=3, num_queues=2, pipeline_depth=2)
    for i, burst in enumerate(bursts):
        mesh.dispatch(burst)
        mesh.tick()
        if i == 1:
            mesh.control.submit(SwapSlot(1, spare_params),
                                ProgramReta(tuple(np.roll(mesh.reta, 1))))
        if i == 3:
            mesh.control.submit(FailQueues((0,)))
    mesh.drain()
    assert len(mesh.control.log) >= 2
    for rec in mesh.control.log:
        assert rec.applied
        assert rec.host_ticks is not None and len(rec.host_ticks) == 3
        assert len(set(rec.host_ticks)) == 1    # the barrier: one tick
        assert rec.host_ticks[0] == rec.applied_tick
    assert [b["host_ticks"] for b in mesh.barrier_log] == \
        [[r.applied_tick] * 3 for r in mesh.control.log]
    # serialized log carries the barrier proof too
    logged = mesh.control.command_log()
    assert all(rec["host_ticks"] == [rec["applied_tick"]] * 3
               for rec in logged)


def test_epoch_rejected_by_one_host_stages_nothing(bank2, spare_params,
                                                   monkeypatch):
    """Stage phase: if any single host rejects its projection, the epoch
    is rejected before ANY host mutates."""
    mesh = make_mesh(bank2, hosts=2, num_queues=2)
    banks_before = [s.bank for s in mesh.shards]
    orig = mesh.shards[1]._validate_command

    def veto(cmd):
        if isinstance(cmd, SwapSlot):
            raise ValueError("host 1 refuses delivery")
        orig(cmd)

    monkeypatch.setattr(mesh.shards[1], "_validate_command", veto)
    mesh.control.submit(SwapSlot(1, spare_params))
    with pytest.raises(ValueError, match="host 1 refuses"):
        mesh.flush_control()
    assert [s.bank for s in mesh.shards] == banks_before
    assert all(s.telemetry.slot_swaps == 0 for s in mesh.shards)
    rec = mesh.control.log[-1]
    assert rec.error and not rec.applied
    assert not mesh.barrier_log                 # no barrier was crossed


def test_epoch_commit_failure_rolls_back_every_host(bank2, spare_params):
    """Commit phase: an epoch that passes staging but fails mid-commit
    (apply-time conflict) rolls back ALL hosts — including ones that
    already applied earlier commands of the epoch."""
    mesh = make_mesh(bank2, hosts=2, num_queues=2)
    banks_before = [s.bank for s in mesh.shards]
    reta_before = mesh.reta.copy()
    # SwapSlot applies on both hosts first; failing every global queue
    # then raises at apply time (zero survivors) -> everything rolls back
    mesh.control.submit(SwapSlot(1, spare_params),
                        FailQueues(tuple(range(mesh.num_queues))))
    with pytest.raises(ValueError):
        mesh.flush_control()
    assert [s.bank for s in mesh.shards] == banks_before
    assert all(s.telemetry.slot_swaps == 0 for s in mesh.shards)
    assert (mesh.reta == reta_before).all()
    assert mesh.failed_queues == set()
    assert mesh.telemetry.slot_swaps == 0 and mesh.telemetry.reta_updates == 0
    rec = mesh.control.log[-1]
    assert rec.error and not rec.applied


def test_applied_epoch_keeps_barrier_stamp_when_later_epoch_rejects(
        bank2, spare_params):
    """An epoch that committed before a later pending epoch was rejected
    in the same flush still carries its host_ticks barrier proof."""
    mesh = make_mesh(bank2, hosts=2, num_queues=2)
    good = mesh.control.submit(SwapSlot(1, spare_params))
    mesh.control.submit(FailQueues(tuple(range(mesh.num_queues))))
    with pytest.raises(ValueError):
        mesh.flush_control()
    recs = {r.epoch: r for r in mesh.control.log}
    assert recs[good].applied
    assert recs[good].host_ticks == (0, 0)      # stamped despite the raise
    assert [b["epoch"] for b in mesh.barrier_log] == [good]
    assert mesh.telemetry.slot_swaps == 1       # the good epoch stuck
    bad = recs[max(recs)]
    assert bad.error and not bad.applied and bad.host_ticks is None


def test_mesh_continuity_audit_across_cascading_failover(bank2):
    phases = cascading_failover_phases(2, hosts=2, queues_per_host=2)
    trace = render(phases, num_slots=2, seed=0, num_queues=4)
    mesh = make_mesh(bank2, hosts=2, num_queues=2, strategy="fused",
                     ring_capacity=256, audit=True, pipeline_depth=2)
    play(mesh, trace)
    cont = mesh.control.continuity_audit()
    kinds = {c for e in cont["epochs"] for c in e["commands"]}
    assert kinds >= {"restore_queues", "fail_queues", "swap_slot"}, kinds
    assert cont["ok"], cont
    assert mesh.telemetry.wrong_verdict == 0
    aud = mesh.audit_conservation()
    assert aud["ok"]
    assert aud["totals"]["offered"] == trace.total_packets


# ---------------------------------------------------------------------------
# mesh policies: the single-host loop, unchanged at mesh scale
# ---------------------------------------------------------------------------

def test_mesh_policy_rebalances_with_global_ids(bank2):
    phases = scenarios.elephant_skew_phases(2, 4, ticks=6)
    trace = render(phases, num_slots=2, seed=0, num_queues=4)
    drops = {}
    for policy in (StaticReta(), LeastDepth()):
        mesh = make_mesh(bank2, hosts=2, num_queues=2, batch=64,
                         ring_capacity=256, policy=policy)
        play(mesh, trace)
        aud = mesh.audit_conservation()
        assert aud["ok"]
        drops[policy.name] = max(q["dropped"] for q in aud["per_queue"])
        if policy.name == "least-depth":
            rebalances = [r for r in mesh.control.log
                          if any(isinstance(c, ProgramReta)
                                 for c in r.commands)]
            assert rebalances                   # proposals became epochs
            assert all(len(set(r.host_ticks)) == 1 for r in rebalances)
    assert drops["static"] > 0                  # skew hurts one (host, queue)
    assert drops["least-depth"] < drops["static"]


def test_mesh_policy_never_routes_onto_failed_pairs(bank2):
    phases = scenarios.elephant_skew_phases(2, 4, ticks=4)
    trace = render(phases, num_slots=2, seed=1, num_queues=4)
    mesh = make_mesh(bank2, hosts=2, num_queues=2, batch=64,
                     ring_capacity=256, policy=LeastDepth())
    mesh.control.submit(FailQueues((3,)))       # host 1, queue 1
    for phase_bursts in trace.bursts:
        for burst in phase_bursts:
            mesh.dispatch(burst)
            mesh.tick()
    mesh.drain()
    assert 3 not in set(mesh.reta.tolist())
    assert mesh.audit_conservation()["ok"]


# ---------------------------------------------------------------------------
# telemetry merge
# ---------------------------------------------------------------------------

def test_telemetry_merge_aggregates_hosts():
    t0, t1 = telemetry.Telemetry(2, 2), telemetry.Telemetry(2, 2)
    t0.record_tick(0, np.array([0, 1]), np.array([True, False]),
                   np.array([0, 1]), latency_us=np.array([10.0, 20.0]))
    t1.record_tick(1, np.array([1, 1, 0]), np.array([True, True, False]),
                   np.array([2, 0, 0]), latency_us=np.array([5.0, 6.0, 7.0]))
    t0.slot_swaps, t1.wrong_verdict = 2, 3
    merged = telemetry.merge([t0, t1])
    assert len(merged.queues) == 4              # host-major global order
    assert [q.queue for q in merged.queues] == [0, 1, 2, 3]
    assert merged.queues[0].completed == 2      # host 0, queue 0
    assert merged.queues[3].completed == 3      # host 1, queue 1
    assert merged.slot_swaps == 2 and merged.wrong_verdict == 3
    snap = merged.snapshot()
    assert snap["completed_total"] == 5
    assert merged.queues[3].latency_hist.sum() == 3
    # deep copy: mutating the merge never touches the inputs
    merged.queues[0].per_slot_total[0] = 99
    assert t0.queues[0].per_slot_total[0] != 99
    with pytest.raises(ValueError):
        telemetry.merge([])
    with pytest.raises(ValueError):
        telemetry.merge([t0, telemetry.Telemetry(1, 3)])


# ---------------------------------------------------------------------------
# scenario registry + device-layout helper
# ---------------------------------------------------------------------------

def test_cascading_failover_phase_shapes():
    phases = cascading_failover_phases(2, hosts=2, queues_per_host=4)
    assert [p.name for p in phases] == ["steady", "host_down", "cascade",
                                        "recovery"]
    assert phases[1].failed_queues == (0, 1, 2, 3)       # all of host 0
    assert set(phases[2].failed_queues) >= {0, 1, 2, 3, 4, 5}
    assert phases[3].failed_queues == () and phases[3].swap_slot is not None
    with pytest.raises(ValueError, match="zero live"):
        cascading_failover_phases(2, hosts=1, queues_per_host=2)
    via_registry = make_scenario("cascading-failover", num_slots=2,
                                 num_queues=4, hosts=2)
    assert [p.name for p in via_registry] == [p.name for p in phases]


def test_queue_mesh_single_source_of_truth():
    from repro.dataplane import queue_mesh
    m1, ax1 = queue_mesh(4)
    m2, ax2 = mesh_lib.make_queue_mesh(4)
    assert ax1 == ax2
    assert m1.devices.shape == m2.devices.shape
    assert m1.axis_names == m2.axis_names
    with pytest.raises(ValueError):
        mesh_lib._build((2, 2), ("only-one-axis",))
