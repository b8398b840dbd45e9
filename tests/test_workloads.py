"""Trace-driven workload engine (DESIGN.md §9): generator registry,
chaos phases, and the recordable/replayable trace format — in particular
the ISSUE 5 acceptance criterion that ``record()`` -> ``replay()`` is
bit-identical on verdicts and telemetry."""

import os

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.control import FailQueues, ProgramReta, RestoreQueues
from repro.core import executor
from repro.dataplane import DataplaneRuntime, MeshDataplane, workloads
from repro.dataplane.workloads import generators
from repro.dataplane.workloads.phases import ChaosEvent, Phase


@pytest.fixture(scope="module")
def bank2():
    return executor.init_bank(jax.random.PRNGKey(0), 2)


def small_chaos_phases(num_slots=2, num_queues=3):
    """A compact storyline with mid-phase chaos: surge, a queue dies at
    tick 2 while rings are loaded, restored at tick 4, swap at exit."""
    uniform = tuple(1.0 / num_slots for _ in range(num_slots))
    victim = num_queues - 1
    chaos = (ChaosEvent(at_tick=2, commands=(FailQueues((victim,)),)),
             ChaosEvent(at_tick=4, commands=(RestoreQueues((victim,)),)))
    return [
        Phase("calm", ticks=2, burst=48, flows=16, slot_mix=uniform),
        Phase("surge", ticks=6, burst=128, flows=8, slot_mix=uniform,
              chaos=chaos),
        Phase("after", ticks=2, burst=48, flows=16, slot_mix=uniform,
              swap_slot=1 % num_slots),
    ]


def _rt(bank, num_queues=3, **kw):
    kw.setdefault("batch", 64)
    kw.setdefault("ring_capacity", 256)
    kw.setdefault("record", True)
    return DataplaneRuntime(bank, num_queues=num_queues, **kw)


# ---------------------------------------------------------------------------
# compatibility shims
# ---------------------------------------------------------------------------

def test_scenarios_shim_reexports_workloads():
    from repro.dataplane import scenarios

    assert scenarios.Phase is workloads.Phase
    assert scenarios.render is workloads.render
    assert scenarios.play is workloads.play
    assert scenarios.SEQ_WORD == workloads.SEQ_WORD
    phases = scenarios.make_scenario(
        "emergency", num_slots=2, num_queues=4)
    assert [p.name for p in phases] == [
        "steady", "flash_crowd", "link_failover", "slot_churn"]


def test_registry_serves_every_regime():
    for name in workloads.REGIME_NAMES:
        w = workloads.make_workload(
            name, num_slots=2, num_queues=2, hosts=2,
            corpus_root=generators.SYNTHETIC_CORPUS)
        assert w.phases, name
        for p in w.phases:
            assert len(p.slot_mix) == 2, name
    with pytest.raises(ValueError, match="unknown workload"):
        workloads.make_workload("nope", num_slots=2, num_queues=2)


# ---------------------------------------------------------------------------
# trace round-trip: record -> save -> load -> replay, bit-identical
# ---------------------------------------------------------------------------

def test_record_replay_bit_identical(bank2, tmp_path):
    rendered = workloads.render(small_chaos_phases(), num_slots=2, seed=11,
                                num_queues=3)
    rt = _rt(bank2)
    rec = workloads.record(rt)
    reports = workloads.play(rec, rendered)
    trace = rec.finish(name="small-chaos", seed=11)
    assert [r["phase"] for r in reports] == ["calm", "surge", "after"]
    # the command timeline holds phase entries AND chaos epochs in order
    kinds = [type(c).__name__ for _, cmds in trace.command_timeline()
             for c in cmds]
    assert kinds.count("FailQueues") == 1
    assert kinds.count("SwapSlot") == 1

    path = str(tmp_path / "small.bswt")
    nbytes = workloads.save(trace, path)
    assert nbytes == os.path.getsize(path)
    loaded = workloads.load(path)
    assert loaded.meta["name"] == "small-chaos"
    assert loaded.total_packets == rendered.total_packets

    rt2 = workloads.make_runtime(loaded)
    rep = workloads.replay(loaded, rt2)
    assert rep["ok"], rep["mismatches"]
    assert rep["digest_ok"] is True
    # bit-identical verdict/telemetry streams, not just matching digests
    assert rt2.completed_seq == rt.completed_seq
    assert rt2.completed_verdicts == rt.completed_verdicts
    assert rt2.completed_slots == rt.completed_slots
    assert sorted(rt2.dropped_seq) == sorted(rt.dropped_seq)
    assert (rt2.telemetry.wrong_verdict, rt2.telemetry.slot_swaps) == \
        (rt.telemetry.wrong_verdict, rt.telemetry.slot_swaps)


@pytest.mark.parametrize("depth", [None, 3])
def test_record_replay_keeps_pipeline_depth(bank2, tmp_path, depth):
    """A trace replays at the window it was recorded with: the derived
    window (``None``) as ``None``, a fixed depth as that depth."""
    rendered = workloads.render(small_chaos_phases(), num_slots=2, seed=7,
                                num_queues=3)
    rt = _rt(bank2, pipeline_depth=depth)
    rec = workloads.record(rt)
    workloads.play(rec, rendered)
    path = str(tmp_path / "depth.bswt")
    workloads.save(rec.finish(name="depth", seed=7), path)
    loaded = workloads.load(path)
    assert loaded.meta["pipeline_depth"] == depth
    rt2 = workloads.make_runtime(loaded)
    assert rt2.pipeline_depth == depth
    rep = workloads.replay(loaded, rt2)
    assert rep["ok"], rep["mismatches"]
    assert rep["digest_ok"] is True
    assert rt2.completed_seq == rt.completed_seq
    assert rt2.completed_verdicts == rt.completed_verdicts


def test_record_replay_with_routing_policy(bank2, tmp_path):
    """Policy rebalance epochs are NOT in the recorded command timeline
    (they regenerate from the replaying runtime's own policy loop), so
    the trace must carry the policy name and replay must reinstall it."""
    from repro.control import make_policy

    w = workloads.make_workload("elephant-skew", num_slots=2, num_queues=3)
    rendered = workloads.render(list(w.phases), num_slots=2, seed=4,
                                num_queues=3)
    rt = _rt(bank2, policy=make_policy("least-depth"))
    rec = workloads.record(rt)
    workloads.play(rec, rendered)
    trace = rec.finish(name="skew-policy", seed=4)
    assert trace.meta["policy"] == "least-depth"
    rebalances = [r for r in rt.control.log
                  if any(isinstance(c, ProgramReta) for c in r.commands)]
    assert rebalances  # the policy really acted during the recording

    path = str(tmp_path / "pol.bswt")
    workloads.save(trace, path)
    rt2 = workloads.make_runtime(workloads.load(path))
    assert rt2.policy is not None and rt2.policy.name == "least-depth"
    rep = workloads.replay(workloads.load(path), rt2)
    assert rep["ok"], rep["mismatches"]
    assert rep["digest_ok"] is True
    # an anonymous policy cannot be recorded faithfully -> loud failure
    class Anon:
        def propose(self, view):
            return None

    rec2 = workloads.record(_rt(bank2, policy=Anon()))
    with pytest.raises(ValueError, match="non-registry policy"):
        rec2.finish()


def test_replay_detects_tampered_invariants(bank2, tmp_path):
    rendered = workloads.render(small_chaos_phases(), num_slots=2, seed=3,
                                num_queues=3)
    rec = workloads.record(_rt(bank2))
    workloads.play(rec, rendered)
    trace = rec.finish()
    for step in trace.steps:
        if step["kind"] == "phase":
            step["expect"]["completed"] += 1  # lie about one phase
            break
    rep = workloads.replay(trace, _rt(bank2))
    assert not rep["ok"]
    assert any("completed" in m for m in rep["mismatches"])
    with pytest.raises(AssertionError):
        workloads.replay(trace, _rt(bank2), strict=True)


def test_trace_rejects_bad_magic_and_version(tmp_path):
    bad = tmp_path / "bad.bswt"
    bad.write_bytes(b"NOTATRACE")
    with pytest.raises(ValueError, match="bad magic"):
        workloads.load(str(bad))
    t = workloads.synthesize(small_chaos_phases(), num_slots=2,
                             num_queues=3, seed=0)
    path = tmp_path / "v.bswt"
    workloads.save(t, str(path))
    from repro.dataplane.workloads import trace as trace_mod

    blob = bytearray(path.read_bytes())
    blob[len(trace_mod.MAGIC)] = 99  # bump the version byte
    path.write_bytes(bytes(blob))
    with pytest.raises(ValueError, match="version"):
        workloads.load(str(path))


def test_synthesized_replay_deterministic_on_mesh(bank2, tmp_path):
    w = workloads.make_workload("chaos-host-failover", num_slots=2,
                                num_queues=2, hosts=2)
    trace = workloads.synthesize(w.phases, num_slots=2, num_queues=4,
                                 seed=5, name=w.name)
    path = str(tmp_path / "mesh.bswt")
    workloads.save(trace, path)
    trace = workloads.load(path)

    def run():
        rt = MeshDataplane(bank2, hosts=2, num_queues=2, batch=64,
                           ring_capacity=256, record=True, audit=True)
        rep = workloads.replay(trace, rt)
        return rt, rep

    rt1, rep1 = run()
    rt2, rep2 = run()
    assert rep1["ok"], rep1["mismatches"]
    assert rep1["digest"]["sha256"] == rep2["digest"]["sha256"]
    assert rt1.telemetry.wrong_verdict == 0
    assert rt1.control.continuity_audit()["ok"]
    # the host-loss epoch really failed a whole host's queues and the
    # barrier stamps agree on every applied epoch
    fails = [r for r in rt1.control.log
             if any(isinstance(c, FailQueues) for c in r.commands)]
    assert fails and fails[0].host_ticks is not None
    assert len(set(fails[0].host_ticks)) == 1


# ---------------------------------------------------------------------------
# chaos + adversarial regimes keep the zero-wrong-verdict guarantee
# ---------------------------------------------------------------------------

def test_slot_thrash_storm_zero_wrong_verdicts(bank2):
    w = workloads.make_workload("slot-thrash", num_slots=2, num_queues=2)
    storm = [ev for p in w.phases for ev in p.chaos]
    assert len(storm) >= 8  # one epoch per storm tick
    assert any(isinstance(c, ProgramReta) for ev in storm
               for c in ev.commands)
    trace = workloads.synthesize(w.phases, num_slots=2, num_queues=2,
                                 seed=2, name=w.name)
    rt = _rt(bank2, num_queues=2, audit=True)
    rep = workloads.replay(trace, rt)
    assert rep["ok"], rep["mismatches"]
    assert rt.telemetry.wrong_verdict == 0
    assert rt.telemetry.slot_swaps >= 4
    cont = rt.control.continuity_audit()
    assert cont["ok"]
    assert len(cont["epochs"]) >= len(storm)


def test_chaos_event_fires_mid_phase_not_at_entry(bank2):
    rendered = workloads.render(small_chaos_phases(), num_slots=2, seed=1,
                                num_queues=3)
    rt = _rt(bank2, audit=True)
    workloads.play(rt, rendered)
    assert rt.telemetry.wrong_verdict == 0
    fail_epochs = [r for r in rt.control.log
                   if any(isinstance(c, FailQueues) for c in r.commands)]
    assert len(fail_epochs) == 1
    # phase entry applies at the surge's first tick; the chaos failover
    # applies strictly later (mid-surge), while the rings are loaded
    entry_tick = rt.control.log[1].applied_tick
    assert fail_epochs[0].applied_tick > entry_tick


# ---------------------------------------------------------------------------
# generator library
# ---------------------------------------------------------------------------

def test_diurnal_curve_rises_and_falls():
    phases = generators.diurnal_phases(2, steps=8)
    bursts = [p.burst for p in phases]
    assert len(bursts) == 8
    assert bursts[0] == min(bursts)          # starts at the nightly minimum
    peak = bursts.index(max(bursts))
    assert 2 <= peak <= 6                    # peaks mid-period
    assert max(bursts) > 2 * min(bursts)     # a real swing, not noise
    day_mix = phases[peak].slot_mix
    night_mix = phases[0].slot_mix
    assert day_mix[0] > night_mix[0]         # day leans on the triage slot


def test_file_replay_deterministic_and_fallback(tmp_path):
    # explicit corpus: bytes drive the pool and phase shapes
    (tmp_path / "a.bin").write_bytes(bytes(range(256)) * 64)
    (tmp_path / "b.bin").write_bytes(b"emergency" * 4096)
    p1, pool1 = generators.file_replay_workload(2, root=str(tmp_path))
    p2, pool2 = generators.file_replay_workload(2, root=str(tmp_path))
    assert [ph.name for ph in p1] == [ph.name for ph in p2]
    assert np.array_equal(pool1, pool2)
    assert len(p1) == 2 and pool1.dtype == np.uint32
    # the pool really carries the corpus bytes
    assert pool1.tobytes().startswith(bytes(range(256)))
    # no corpus anywhere -> deterministic synthetic fallback
    synth1 = generators.file_corpus(generators.SYNTHETIC_CORPUS)
    synth2 = generators.file_corpus(generators.SYNTHETIC_CORPUS)
    assert [n for n, _ in synth1] == [n for n, _ in synth2]
    assert all(d1 == d2 for (_, d1), (_, d2) in zip(synth1, synth2))


def test_render_and_synthesize_are_seed_deterministic(bank2):
    w = workloads.make_workload("flash-crowd", num_slots=2, num_queues=2)
    t1 = workloads.synthesize(w.phases, num_slots=2, num_queues=2, seed=9)
    t2 = workloads.synthesize(w.phases, num_slots=2, num_queues=2, seed=9)
    b1 = [s["rows"] for s in t1.steps if s["kind"] == "burst"]
    b2 = [s["rows"] for s in t2.steps if s["kind"] == "burst"]
    assert len(b1) == len(b2) and all(
        np.array_equal(x, y) for x, y in zip(b1, b2))
    t3 = workloads.synthesize(w.phases, num_slots=2, num_queues=2, seed=10)
    b3 = [s["rows"] for s in t3.steps if s["kind"] == "burst"]
    assert not all(np.array_equal(x, y) for x, y in zip(b1, b3))


# ---------------------------------------------------------------------------
# hypothesis: replay determinism over generated regimes
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(
    regime=st.sampled_from(["flash-crowd", "slot-thrash",
                            "chaos-queue-surge"]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_generated_regime_replay_is_deterministic(regime, seed):
    bank = executor.init_bank(jax.random.PRNGKey(0), 2)
    w = workloads.make_workload(regime, num_slots=2, num_queues=2)
    trace = workloads.synthesize(w.phases, num_slots=2, num_queues=2,
                                 seed=seed, name=regime)

    def run():
        rt = DataplaneRuntime(bank, num_queues=2, batch=64,
                              ring_capacity=256, record=True)
        rep = workloads.replay(trace, rt)
        return rt, rep

    rt1, rep1 = run()
    rt2, rep2 = run()
    assert rep1["ok"], rep1["mismatches"]
    assert rep1["digest"]["sha256"] == rep2["digest"]["sha256"]
    assert rt1.completed_verdicts == rt2.completed_verdicts
    assert sorted(rt1.dropped_seq) == sorted(rt2.dropped_seq)
