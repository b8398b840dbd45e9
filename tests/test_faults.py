"""Fault-tolerant epoch barriers (DESIGN.md §10): host-health leases,
degraded quorum commit, typed fault injection, bounded epoch logs, and
the property that every fault class ends in exactly one of {atomic
commit, atomic rollback, degraded quorum commit + failover epoch} with
conservation and zero wrong verdicts intact."""

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.control import (API_VERSION, HealthMonitor, NonFatalControlError,
                           SwapSlot, load_epoch_spill)
from repro.core import executor
from repro.dataplane import (DataplaneRuntime, MeshDataplane, Phase, faults,
                             render, scenarios, workloads)

LEASE = 4


@pytest.fixture(scope="module")
def bank2():
    return executor.init_bank(jax.random.PRNGKey(0), 2)


def small_phases(total_queues=4, ticks=10, burst=64):
    return [Phase("drive", ticks=ticks, burst=burst, flows=16,
                  slot_mix=(0.5, 0.5))]


def make_mesh(bank, *, hosts=2, num_queues=2, plan=None, **kw):
    kw.setdefault("strategy", "take")
    kw.setdefault("batch", 32)
    kw.setdefault("ring_capacity", 4096)
    kw.setdefault("lease_ticks", LEASE)
    if plan is not None:
        kw.setdefault("fault_injector", faults.FaultInjector(plan))
    return MeshDataplane(bank, hosts=hosts, num_queues=num_queues, **kw)


def drive(mesh, *, ticks=14, swap_every=3, seed=3, burst=64):
    """Dispatch + tick with a SwapSlot epoch every ``swap_every`` ticks."""
    total = mesh.hosts * mesh.num_queues_per_host
    trace = render(small_phases(total, ticks=ticks, burst=burst),
                   num_slots=2, seed=seed, num_queues=total)
    for t, b in enumerate(trace.bursts[0]):
        if swap_every and t % swap_every == 1:
            slot = (t // swap_every) % 2
            mesh.control.submit(
                SwapSlot(slot, scenarios.default_swap_delivery(slot)))
        mesh.dispatch(b)
        mesh.tick()
    mesh.drain()
    return trace


# ---------------------------------------------------------------------------
# fault plans
# ---------------------------------------------------------------------------

def test_fault_plan_json_roundtrip(tmp_path):
    plan = faults.FaultPlan(
        faults=(faults.StallHost(1, 4, 3), faults.CrashHost(2, 9),
                faults.ShardError(0, 5, "stage"), faults.DropAck(1, 7, 2),
                faults.DelayRetire(1, 3, 6)),
        name="kitchen-sink", seed=7)
    path = str(tmp_path / "plan.json")
    faults.save_plan(plan, path)
    loaded = faults.load_plan(path)
    assert loaded == plan
    assert loaded.to_dict() == plan.to_dict()


def test_demo_plan_covers_every_fault_class():
    for kind in faults.FAULT_CLASSES:
        for hosts in (1, 2, 4):
            plan = faults.demo_plan(kind, hosts=hosts, lease_ticks=LEASE)
            assert plan.faults, (kind, hosts)
            # host 0 must survive whenever there is a host to fail over to
            if hosts > 1:
                assert all(f.host != 0 for f in plan.faults)
    with pytest.raises(ValueError, match="unknown fault class"):
        faults.demo_plan("nope", hosts=2)


def test_random_plan_deterministic_and_spares_host0():
    a = faults.random_plan(11, hosts=3)
    assert a == faults.random_plan(11, hosts=3)
    assert all(f.host != 0 for f in a.faults)
    crashed = [f.host for f in a.faults if isinstance(f, faults.CrashHost)]
    assert len(set(crashed)) == len(crashed) <= 2  # a survivor always exists


# ---------------------------------------------------------------------------
# health monitor state machine
# ---------------------------------------------------------------------------

def test_health_monitor_lease_lifecycle():
    hm = HealthMonitor(2, lease_ticks=4, suspect_after=2)
    alive = False
    for t in range(4):
        hm.heartbeat(0, t)
        hm.miss(1, t)
        hm.miss(1, t)                       # deduped per (host, tick)
        hm.observe(t, probe=lambda h: alive)
    assert hm.total_misses == 4
    assert [(tr.to, tr.tick) for tr in hm.transitions] == \
        [("suspect", 1), ("dead", 3)]
    assert hm.dead_hosts() == (1,) and hm.live_hosts() == (0,)
    # exponential backoff: probes at died_at+2, then +4 after a failure
    probed = []
    for t in range(4, 12):
        hm.heartbeat(0, t)
        before = hm.total_probes
        hm.observe(t, probe=lambda h: probed.append(t) or alive)
        assert hm.total_probes - before in (0, 1)
    assert probed == [5, 9]
    alive = True                            # next probe due at tick 17
    for t in range(12, 20):
        hm.heartbeat(0, t)
        hm.observe(t, probe=lambda h: alive)
        if hm.state(1).value == "recovering":
            hm.heartbeat(1, t)              # caller resyncs, host serves
    assert hm.state(1).value == "healthy"
    assert [tr.to for tr in hm.transitions] == \
        ["suspect", "dead", "recovering", "healthy"]


def test_health_monitor_miss_beats_heartbeat_same_tick():
    hm = HealthMonitor(1, lease_ticks=2, suspect_after=1)
    for t in range(2):
        hm.miss(0, t)
        hm.heartbeat(0, t)                  # ignored: miss already recorded
        hm.observe(t)
    assert hm.is_dead(0)


def test_health_monitor_validates_config():
    with pytest.raises(ValueError, match="must not exceed"):
        HealthMonitor(2, lease_ticks=2, suspect_after=3)


# ---------------------------------------------------------------------------
# barrier outcomes per fault class
# ---------------------------------------------------------------------------

def test_stall_within_lease_defers_then_commits_atomic(bank2):
    plan = faults.FaultPlan(faults=(faults.StallHost(1, 4, 2),), name="blip")
    mesh = make_mesh(bank2, plan=plan, suspect_after=3, lease_ticks=6)
    drive(mesh, ticks=12)
    cont = mesh.control.continuity_audit()
    assert cont["ok"], cont
    assert cont["commit_modes"]["degraded"] == 0
    assert cont["commit_modes"]["rollback"] == 0
    assert mesh.failover_epochs == []
    assert mesh.health.dead_hosts() == ()
    # the swap submitted during the stall waited for the straggler
    stalled_epochs = [r for r in mesh.control.log
                      if r.applied and r.applied_tick >= 4]
    assert stalled_epochs and all(r.commit_mode == "atomic"
                                  for r in stalled_epochs)


def test_lease_expiry_degrades_then_recovers(bank2):
    plan = faults.demo_plan("stall", hosts=2, lease_ticks=LEASE, at_tick=4)
    mesh = make_mesh(bank2, plan=plan)
    drive(mesh, ticks=20)
    cont = mesh.control.continuity_audit()
    assert cont["ok"], cont
    assert cont["commit_modes"]["degraded"] > 0
    assert mesh.failover_epochs and mesh.restore_epochs
    tos = [t.to for t in mesh.health.transitions]
    assert tos[:2] == ["suspect", "dead"]
    assert mesh.health.state(1).value == "healthy"       # rejoined
    aud = mesh.audit_conservation()
    assert aud["ok"], aud
    assert aud["stranded"]["packets"] == 0               # backlog resynced
    # the mesh never stalled longer than the lease on the dead host
    dead_tick = next(t.tick for t in mesh.health.transitions
                     if t.to == "dead")
    miss_start = dead_tick - mesh.health.lease_ticks
    blocked = [r for r in mesh.control.log
               if r.applied and miss_start <= r.applied_tick <= dead_tick]
    assert all(r.applied_tick - miss_start <= mesh.health.lease_ticks + 1
               for r in blocked)


def test_crash_strands_packets_and_drain_converges(bank2):
    plan = faults.demo_plan("crash", hosts=2, lease_ticks=LEASE, at_tick=5)
    mesh = make_mesh(bank2, plan=plan)
    drive(mesh, ticks=16)                   # drain() inside must terminate
    aud = mesh.audit_conservation()
    assert aud["ok"], aud
    assert aud["stranded"]["hosts"] == [1]
    assert aud["stranded"]["packets"] > 0
    t = aud["totals"]
    assert t["offered"] == (t["completed"] + t["dropped"]
                            + t["occupancy"] + t["in_flight"])
    cont = mesh.control.continuity_audit()
    assert cont["ok"], cont
    assert cont["commit_modes"]["degraded"] > 0
    assert mesh.failover_epochs and not mesh.restore_epochs
    assert mesh.health.is_dead(1)


@pytest.mark.parametrize("point", ["stage", "apply"])
def test_shard_error_rolls_back_atomically(bank2, point):
    plan = faults.FaultPlan(
        faults=(faults.ShardError(1, 4, point),), name=f"err-{point}")
    mesh = make_mesh(bank2, plan=plan)
    reta_before = mesh.reta.copy()
    drive(mesh, ticks=10)
    log = mesh.control.log
    rolled = [r for r in log if r.commit_mode == "rollback"]
    assert len(rolled) == 1
    assert "injected shard error" in rolled[0].error
    assert not rolled[0].applied and rolled[0].apply_us is None
    # the fault is non-fatal: later epochs still commit
    assert any(r.applied and r.epoch > rolled[0].epoch for r in log)
    cont = mesh.control.continuity_audit()
    assert cont["ok"], cont
    assert mesh.audit_conservation()["ok"]
    assert np.array_equal(mesh.reta, reta_before)        # nothing leaked
    assert mesh.health.dead_hosts() == ()                # not a health event


def test_drop_ack_degrades_then_restores(bank2):
    plan = faults.FaultPlan(faults=(faults.DropAck(1, 4),), name="lost-ack")
    mesh = make_mesh(bank2, plan=plan)
    drive(mesh, ticks=16)
    cont = mesh.control.continuity_audit()
    assert cont["ok"], cont
    assert cont["commit_modes"]["degraded"] >= 1
    assert mesh.failover_epochs and mesh.restore_epochs  # suspected, rejoined
    assert mesh.health.state(1).value == "healthy"
    assert [t.to for t in mesh.health.transitions][0] == "suspect"
    assert mesh.audit_conservation()["ok"]


def test_quorum_lost_rolls_back_not_commits(bank2):
    plan = faults.FaultPlan(
        faults=(faults.CrashHost(1, 3), faults.CrashHost(2, 3)),
        name="two-down")
    mesh = make_mesh(bank2, hosts=3, plan=plan)       # quorum = 2, 1 lives
    drive(mesh, ticks=14)
    log = mesh.control.log
    rolled = [r for r in log if r.commit_mode == "rollback"]
    assert rolled and all("quorum" in r.error for r in rolled)
    assert not any(r.commit_mode == "degraded" for r in log
                   if r.epoch > rolled[0].epoch)
    cont = mesh.control.continuity_audit()
    assert cont["ok"], cont
    assert mesh.audit_conservation()["ok"]
    assert sorted(mesh.audit_conservation()["stranded"]["hosts"]) == [1, 2]


# ---------------------------------------------------------------------------
# bounded epoch log
# ---------------------------------------------------------------------------

def test_log_capacity_spills_and_audit_folds_in(bank2, tmp_path):
    spill = str(tmp_path / "epochs.bswel")
    mesh = make_mesh(bank2, log_capacity=2, log_spill=spill)
    drive(mesh, ticks=14, swap_every=2)
    stats = mesh.control.stats()
    assert len(mesh.control.log) == 2
    assert stats["epochs_spilled"] >= 2
    spilled = load_epoch_spill(spill)
    assert [d["epoch"] for d in spilled] == \
        list(range(1, stats["epochs_spilled"] + 1))
    assert all(d["commit_mode"] == "atomic" for d in spilled)
    assert all("wrong_verdict_in_window" in d for d in spilled)
    cont = mesh.control.continuity_audit()
    assert cont["ok"], cont
    assert cont["spilled_epochs"] == stats["epochs_spilled"]
    assert cont["spilled_wrong_verdict"] == 0


def test_log_capacity_validates():
    with pytest.raises(ValueError, match="log_capacity"):
        DataplaneRuntime(executor.init_bank(jax.random.PRNGKey(1), 2),
                         num_queues=2, log_capacity=0)


# ---------------------------------------------------------------------------
# single-host runtime injection points
# ---------------------------------------------------------------------------

def test_single_host_stall_and_stage_error_nonfatal(bank2):
    plan = faults.FaultPlan(
        faults=(faults.StallHost(0, 3, 2), faults.ShardError(0, 7, "stage")),
        name="single")
    rt = DataplaneRuntime(bank2, num_queues=2, batch=32, ring_capacity=4096,
                          strategy="take",
                          fault_injector=faults.FaultInjector(plan))
    trace = render(small_phases(2, ticks=12), num_slots=2, seed=5,
                   num_queues=2)
    for t, b in enumerate(trace.bursts[0]):
        if t % 3 == 1:
            rt.control.submit(
                SwapSlot(t % 2, scenarios.default_swap_delivery(t % 2)))
        rt.dispatch(b)
        rt.tick()
    rt.drain()
    rolled = [r for r in rt.control.log if r.commit_mode == "rollback"]
    assert len(rolled) == 1 and "injected" in rolled[0].error
    assert rt.control.continuity_audit()["ok"]
    assert rt.audit_conservation()["ok"]


def test_stall_under_derived_window_completes_the_same_seqs(bank2):
    """An injected ``StallHost`` while the derived window
    (``pipeline_depth=None``) holds a tick in flight: the audits pass,
    and once drained every queue has completed the same seqs, in the
    same order, as the unstalled run."""
    trace = render(small_phases(2, ticks=8, burst=48), num_slots=2, seed=7,
                   num_queues=2)
    runs = {}
    for name, plan in (("plain", None), ("stalled", faults.FaultPlan(
            faults=(faults.StallHost(0, 2, 2),), name="stall"))):
        rt = DataplaneRuntime(
            bank2, num_queues=2, strategy="fused", batch=8,
            ring_capacity=256, audit=True, record=True,
            fault_injector=plan and faults.FaultInjector(plan))
        assert rt.pipeline_depth is None
        rt.spans.enable()
        for t, b in enumerate(trace.bursts[0]):
            rt.dispatch(b)
            rt.tick()
            if plan is not None and t == 2:
                # the stalled tick served nothing and retired nothing
                assert sum(rt.in_flight_rows()) > 0
        rt.drain()
        assert rt.spans.counters.get("dp.retire.deferred", 0) > 0
        aud = rt.audit_conservation()
        assert aud["ok"] and aud["wrong_verdict"] == 0
        assert aud["totals"]["completed"] == trace.total_packets
        runs[name] = rt.completed_seq
    assert runs["stalled"] == runs["plain"]


# ---------------------------------------------------------------------------
# API surface
# ---------------------------------------------------------------------------

def test_api_v3_and_commit_mode_in_records(bank2):
    assert API_VERSION == 3
    mesh = make_mesh(bank2, plan=faults.demo_plan("crash", hosts=2,
                                                  lease_ticks=LEASE))
    drive(mesh, ticks=12)
    for rec in mesh.control.command_log():
        assert rec["commit_mode"] in ("atomic", "degraded", "rollback")
    assert isinstance(NonFatalControlError("x"), Exception)
    snap = mesh.snapshot()
    assert snap["degraded_commits"] > 0
    assert snap["health"]["hosts"][1]["state"] == "dead"
    assert snap["fault_events"]


def test_faulted_trace_replays_bit_exactly(bank2, tmp_path):
    wl = workloads.make_workload("crash-mid-commit", num_slots=2,
                                 num_queues=2, hosts=2)
    rendered = render(list(wl.phases), num_slots=2, seed=9, num_queues=4)
    mesh = make_mesh(bank2, plan=wl.fault_plan, record=True, audit=True)
    rec = workloads.record(mesh)
    workloads.play(rec, rendered)
    trace = rec.finish(name=wl.name, seed=9)
    assert trace.meta["fault_plan"]["faults"]
    assert trace.meta["lease_ticks"] == LEASE
    path = str(tmp_path / "crash.bswt")
    workloads.save(trace, path)
    loaded = workloads.load(path)
    rt2 = workloads.make_runtime(loaded, bank=bank2, audit=True)
    rep = workloads.replay(loaded, rt2)
    assert rep["ok"] and rep["digest_ok"]
    assert (rt2.control.continuity_audit()["commit_modes"]
            == mesh.control.continuity_audit()["commit_modes"])
    assert (rt2.audit_conservation().get("stranded")
            == mesh.audit_conservation().get("stranded"))


# ---------------------------------------------------------------------------
# property: any random fault plan x regime keeps every invariant
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(plan_seed=st.integers(min_value=0, max_value=10_000),
       regime=st.sampled_from(["emergency", "flash-crowd", "slot-thrash"]))
def test_random_faults_preserve_invariants(bank2, plan_seed, regime):
    plan = faults.random_plan(plan_seed, hosts=2, horizon=16)
    wl = workloads.make_workload(regime, num_slots=2, num_queues=2, hosts=2)
    rendered = render(list(wl.phases), num_slots=2, seed=plan_seed,
                      num_queues=4)
    mesh = make_mesh(bank2, plan=plan, audit=True, record=True,
                     ring_capacity=256)
    workloads.play(mesh, rendered)
    aud = mesh.audit_conservation()
    assert aud["ok"], aud                   # conservation incl. stranded
    t = aud["totals"]
    assert t["offered"] == (t["completed"] + t["dropped"]
                            + t["occupancy"] + t["in_flight"])
    cont = mesh.control.continuity_audit()
    assert cont["ok"], cont                 # zero wrong verdicts anywhere
    assert cont["wrong_verdict_total"] == 0
    for e in cont["epochs"]:
        assert e["commit_mode"] in ("atomic", "degraded", "rollback")
    for shard in mesh.shards:               # per-queue FIFO survives faults
        for seqs in shard.completed_seq:
            assert (np.diff(np.asarray(seqs)) > 0).all()
