"""Continuous deployment subsystem (DESIGN.md §12): telemetry-attached
sampling, online fine-tuning, canary SwapSlot rollouts with
promote/rollback, auto-remediation, and the end-to-end audit story."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro import deploy
from repro.checkpoint import store
from repro.core import bank as bank_lib
from repro.core import executor
from repro.core import packet as pkt
from repro.dataplane import DataplaneRuntime, MeshDataplane, workloads
from repro.obs import AnomalyDetector, TelemetryStream, attach
from repro.obs import spans


@pytest.fixture(scope="module")
def bank2():
    return executor.init_bank(jax.random.PRNGKey(0), 2)


@functools.lru_cache(maxsize=1)
def _pool():
    return deploy.labeled_pool(samples_per_group=96, seed=0)


@pytest.fixture(scope="module")
def corpus():
    pool, labels = _pool()
    return pool, labels, deploy.LabelOracle(pool, labels)


@pytest.fixture(scope="module")
def trained(corpus):
    pool, labels, _ = corpus
    return deploy.OnlineTrainer(steps=24, seed=0).fine_tune(pool, labels)


@functools.lru_cache(maxsize=None)
def _rendered(regime, seed=0, queues=2):
    pool, _labels = _pool()
    w = workloads.make_workload(regime, num_slots=2, num_queues=queues)
    return workloads.render(list(w.phases), num_slots=2, seed=seed,
                            num_queues=queues, payload_pool=pool)


def _drive(driver, pool, rng, ticks, *, controller=None, n=192):
    """Feed pool-payload packets through dispatch/tick for ``ticks``."""
    for _ in range(ticks):
        idx = rng.integers(0, pool.shape[0], n)
        pkts = pkt.make_packets(rng.integers(0, 2, n), pool[idx])
        driver.dispatch(pkts)
        driver.tick()
        if controller is not None:
            controller.step()


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# runtime taps
# ---------------------------------------------------------------------------

def test_runtime_taps_account_for_every_row(bank2):
    rng = np.random.default_rng(0)
    rt = DataplaneRuntime(bank2, num_queues=2, batch=64, ring_capacity=128)
    retired, dropped = [], []
    rt.on_retire = lambda q, rows, s, v, a, t: retired.append(rows.shape[0])
    rt.on_drop = lambda q, rows: dropped.append(rows.shape[0])
    pool, _ = _pool()
    for _ in range(6):  # tiny rings: tail drops exercised too
        idx = rng.integers(0, pool.shape[0], 300)
        rt.dispatch(pkt.make_packets(rng.integers(0, 2, 300), pool[idx]))
        rt.tick()
    rt.drain()
    snap = rt.telemetry.snapshot()
    assert sum(retired) == snap["completed_total"] > 0
    assert sum(dropped) == snap["dropped_total"] > 0


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------

def test_label_oracle_survives_word0_twist(corpus):
    pool, labels, oracle = corpus
    twisted = pool.copy()
    twisted[:, 0] ^= np.arange(pool.shape[0], dtype=np.uint32) * 2654435761
    got = oracle.lookup(twisted[:64])
    np.testing.assert_array_equal(got, labels[:64])
    unknown = np.random.default_rng(0).integers(
        0, 2**32, (4, 256), dtype=np.uint32)
    assert (oracle.lookup(unknown) == -1).all()


def test_reservoir_bounded_over_unbounded_stream():
    r = deploy.Reservoir(64, 4, np.random.default_rng(0))
    for i in range(10):
        words = np.full((100, 4), i, np.uint32)
        r.add(words, np.ones(100, np.int8), np.zeros(100, np.int8), i)
    assert r.count == 64 and r.seen == 1000
    words, labels, verdicts = r.rows()
    assert words.shape == (64, 4) and (labels == 1).all()
    # late batches must actually displace early ones (uniform-ish sample)
    assert len(np.unique(words[:, 0])) > 3


def test_sampler_is_bounded_and_does_not_mutate_the_stream(bank2, corpus):
    pool, _labels, oracle = corpus
    trace = _rendered("emergency")
    kw = dict(num_queues=2, batch=128, ring_capacity=4096, record=True)

    rt_plain = DataplaneRuntime(bank2, **kw)
    workloads.play(rt_plain, trace)

    rt = DataplaneRuntime(bank2, **kw)
    sampler = deploy.PacketSampler(oracle, num_slots=2,
                                   capacity=256).attach(rt)
    workloads.play(rt, trace)
    sampler.detach()
    assert rt.on_retire is None and rt.on_drop is None

    # verdict/slot streams are bit-identical with the sampler attached
    assert rt.completed_verdicts == rt_plain.completed_verdicts
    assert rt.completed_slots == rt_plain.completed_slots
    st_ = sampler.stats()
    assert st_["seen"] == rt.telemetry.snapshot()["completed_total"]
    assert st_["labeled"] > 0 and st_["unknown"] == 0
    assert all(c <= 256 for c in st_["reservoir_rows"])
    words, labels = sampler.training_batch()
    assert words.shape[0] == labels.shape[0] > 0
    assert set(np.unique(labels)) <= {0, 1}


def test_sampler_harvests_ring_edge_drops(bank2, corpus):
    pool, _labels, oracle = corpus
    rng = np.random.default_rng(1)
    rt = DataplaneRuntime(bank2, num_queues=2, batch=32, ring_capacity=64)
    sampler = deploy.PacketSampler(oracle, num_slots=2).attach(rt)
    for _ in range(4):  # overrun the tiny rings without ticking
        idx = rng.integers(0, pool.shape[0], 512)
        rt.dispatch(pkt.make_packets(rng.integers(0, 2, 512), pool[idx]))
    rt.drain()
    sampler.detach()
    assert sampler.drops_seen > 0
    assert 0 < sampler.drop_reservoir.count <= sampler.drop_reservoir.capacity
    _words, labels = sampler.training_batch()
    assert labels.size > 0


def test_sampler_window_filters_by_tick(bank2, corpus):
    pool, _labels, oracle = corpus
    rng = np.random.default_rng(2)
    rt = DataplaneRuntime(bank2, num_queues=2, batch=128, ring_capacity=1024)
    sampler = deploy.PacketSampler(oracle, num_slots=2).attach(rt)
    _drive(rt, pool, rng, 4)
    cut = rt._tick_count
    _drive(rt, pool, rng, 3)
    rt.drain()
    sampler.detach()
    _w, _l, _v, _s = sampler.window_since(0)
    w2, l2, _v2, _s2 = sampler.window_since(cut)
    assert 0 < l2.size < _l.size
    assert (oracle.lookup(w2) == l2).all()


@pytest.mark.parametrize("depth", [None, 4])
def test_deferred_retires_respect_sampler_and_stream_bounds(bank2, corpus,
                                                            depth):
    """Ticks retired behind a later launch reach the deploy/obs taps back
    to back (the derived window hands them ticks in pairs, a fixed
    window of 4 and the final drain in runs): ``PacketSampler.max_pending``
    must still bound the labeling backlog, and ``TelemetryStream``
    overflow accounting must stay conserved
    (``next_sid == buffered + dropped_events``)."""
    pool, _labels, oracle = corpus
    rt = DataplaneRuntime(bank2, num_queues=2, strategy="fused", batch=16,
                          ring_capacity=1024, pipeline_depth=depth)
    rt.spans.enable()
    max_pending = 3
    sampler = deploy.PacketSampler(oracle, num_slots=2, per_tick=8,
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
        rt.dispatch(pkt.make_packets(rng.integers(0, 2, 48), pool[idx]))
        rt.tick()
        peak = max(peak, len(sampler._pending))
    rt.drain()
    peak = max(peak, len(sampler._pending))
    sampler.detach()  # final flush
    assert rt.spans.counters.get("dp.retire.deferred", 0) > 0
    completed = rt.snapshot()["completed_total"]
    assert completed > 0
    assert sampler.seen == completed
    # the backlog bound held across every back-to-back retire
    assert peak <= max_pending
    assert max(flush_sizes, default=0) <= max_pending
    assert sampler.labeled + sampler.unknown == sampler.sampled
    # stream conservation: every event is either retained or counted out
    s = stream.snapshot_stats()
    assert s["next_sid"] == s["buffered"] + s["dropped_events"]
    assert s["dropped_events"] > 0  # the tiny ring really overflowed


def test_double_attach_rejected(bank2):
    rt = DataplaneRuntime(bank2, num_queues=2)
    s1 = deploy.PacketSampler(None, num_slots=2).attach(rt)
    with pytest.raises(RuntimeError, match="already has a sampler tap"):
        deploy.PacketSampler(None, num_slots=2).attach(rt)
    s1.detach()


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def test_trainer_learns_and_checkpoints(corpus, tmp_path):
    pool, labels, _ = corpus
    trainer = deploy.OnlineTrainer(checkpoint_dir=str(tmp_path), steps=24,
                                   seed=0, keep_last=2)
    res = trainer.fine_tune(pool, labels)
    assert res.metrics["err"] <= 0.35          # beats coin-flip clearly
    assert res.metrics["f1"] > 0.5
    assert res.checkpoint_path and os.path.isdir(res.checkpoint_path)
    back, extra = store.restore(str(tmp_path), res.step, res.latent)
    assert _tree_equal(back, res.latent)
    assert "metrics" in extra and extra["metrics"]["samples"] == pool.shape[0]
    # successive fine-tunes advance the step and GC old checkpoints
    for _ in range(3):
        res = trainer.fine_tune(pool, labels, warm_latent=res.latent)
    assert store.list_steps(str(tmp_path)) == [2, 3]


def test_corrupt_params_invert_the_model(corpus, trained):
    pool, labels, _ = corpus
    good = deploy.paired_err(trained.params, pool, labels)
    bad = deploy.paired_err(deploy.corrupt_params(trained.params),
                            pool, labels)
    assert good < 0.35 and bad > 0.65 and abs(good + bad - 1.0) < 1e-6


# ---------------------------------------------------------------------------
# canary lifecycle
# ---------------------------------------------------------------------------

def test_canary_promote_installs_weights_and_restores_routing(
        bank2, corpus, trained):
    pool, _labels, oracle = corpus
    rng = np.random.default_rng(3)
    rt = DataplaneRuntime(bank2, num_queues=4, batch=128,
                          ring_capacity=2048, audit=True)
    sampler = deploy.PacketSampler(oracle, num_slots=2).attach(rt)
    ctl = deploy.CanaryController(rt, sampler, target_slot=0, bake_ticks=5,
                                  min_samples=16)
    prior_reta = np.asarray(rt.reta).copy()
    slot1_before = bank_lib.select_slot(rt.bank, 1)
    _drive(rt, pool, rng, 2)
    ctl.start(trained.params, reason="test")
    assert ctl.state == ctl.BAKING
    assert not np.array_equal(np.asarray(rt.reta), prior_reta)  # steered
    _drive(rt, pool, rng, 6, controller=ctl)
    rt.drain()
    assert ctl.state == ctl.IDLE and len(ctl.decisions) == 1
    rec = ctl.decisions[0]
    assert rec["event"] == "promoted", rec
    assert _tree_equal(bank_lib.select_slot(rt.bank, 0), trained.params)
    assert _tree_equal(bank_lib.select_slot(rt.bank, 1), slot1_before)
    assert np.array_equal(np.asarray(rt.reta), prior_reta)
    # both transitions are typed epochs in the control log
    kinds = [tuple(c["cmd"] for c in e["commands"])
             for e in rt.control.command_log()]
    assert ("swap_slot", "program_reta") in kinds            # canary_start
    assert ("swap_slot", "swap_slot", "program_reta") in kinds  # promote
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["wrong_verdict"] == 0
    assert rt.control.continuity_audit()["ok"]
    sampler.detach()


def test_canary_rolls_back_a_regression_bit_exactly(bank2, corpus, trained):
    pool, _labels, oracle = corpus
    rng = np.random.default_rng(4)
    rt = DataplaneRuntime(bank2, num_queues=4, batch=128,
                          ring_capacity=2048, audit=True)
    sampler = deploy.PacketSampler(oracle, num_slots=2).attach(rt)
    ctl = deploy.CanaryController(rt, sampler, target_slot=0, bake_ticks=5,
                                  min_samples=16)
    slot0_before = bank_lib.select_slot(rt.bank, 0)
    slot1_before = bank_lib.select_slot(rt.bank, 1)
    prior_reta = np.asarray(rt.reta).copy()
    _drive(rt, pool, rng, 2)
    ctl.start(deploy.corrupt_params(trained.params), reason="test")
    _drive(rt, pool, rng, 6, controller=ctl)
    rt.drain()
    rec = ctl.decisions[0]
    assert rec["event"] == "rolled_back"
    assert rec["metrics"]["err_new"] > rec["metrics"]["err_base"]
    assert _tree_equal(bank_lib.select_slot(rt.bank, 0), slot0_before)
    assert _tree_equal(bank_lib.select_slot(rt.bank, 1), slot1_before)
    assert np.array_equal(np.asarray(rt.reta), prior_reta)
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["wrong_verdict"] == 0
    assert rt.control.continuity_audit()["ok"]
    sampler.detach()


def test_canary_flush_forces_exactly_one_conservative_decision(
        bank2, trained):
    rt = DataplaneRuntime(bank2, num_queues=2)
    ctl = deploy.CanaryController(rt, None, target_slot=0, bake_ticks=50)
    ctl.start(trained.params)
    rec = ctl.flush()               # end of traffic mid-bake
    assert rec["event"] == "rolled_back"
    assert "insufficient" in rec["reason"]
    assert ctl.flush() is None and ctl.step() is None
    assert len(ctl.decisions) == 1
    events = [d["event"] for d in rt.deploy_log]
    assert events == ["canary_start", "rolled_back"]


def test_canary_guards(bank2, trained):
    bank1 = executor.init_bank(jax.random.PRNGKey(1), 1)
    with pytest.raises(ValueError, match=">= 2 resident slots"):
        deploy.CanaryController(DataplaneRuntime(bank1, num_queues=2), None)
    rt = DataplaneRuntime(bank2, num_queues=2)
    with pytest.raises(ValueError, match="must differ"):
        deploy.CanaryController(rt, None, target_slot=0, canary_slot=0)
    ctl = deploy.CanaryController(rt, None)
    ctl.start(trained.params)
    with pytest.raises(RuntimeError, match="already baking"):
        ctl.start(trained.params)
    ctl.flush()


def test_canary_on_mesh_promotes_mesh_wide(bank2, corpus, trained):
    pool, _labels, oracle = corpus
    rng = np.random.default_rng(5)
    mesh = MeshDataplane(bank2, hosts=2, num_queues=2, batch=128,
                         ring_capacity=2048)
    sampler = deploy.PacketSampler(oracle, num_slots=2).attach(mesh)
    ctl = deploy.CanaryController(mesh, sampler, target_slot=0,
                                  bake_ticks=4, min_samples=16)
    _drive(mesh, pool, rng, 2)
    ctl.start(trained.params)
    _drive(mesh, pool, rng, 5, controller=ctl)
    mesh.drain()
    assert ctl.decisions and ctl.decisions[0]["event"] == "promoted"
    for shard in mesh.shards:   # mesh-wide: every shard's bank updated
        assert _tree_equal(bank_lib.select_slot(shard.bank, 0),
                           trained.params)
    assert mesh.audit_conservation()["ok"]
    assert mesh.control.continuity_audit()["ok"]
    sampler.detach()


# ---------------------------------------------------------------------------
# auto-remediation
# ---------------------------------------------------------------------------

def _mix_shift_stream(ticks=16, flip=8):
    """Crafted delta stream whose slot mix flips halfway (detector fuel)."""
    stream = TelemetryStream()
    for tick in range(ticks):
        per_slot = [64, 0] if tick < flip else [0, 64]
        stream.push({"kind": "delta", "seq": tick, "tick": tick, "t_s": None,
                     "host": 0,
                     "queues": [{"queue": 0, "completed": 64, "dropped": 0,
                                 "per_slot": per_slot,
                                 "actions": [64, 0, 0], "depth": 0},
                                {"queue": 1, "completed": 60, "dropped": 0,
                                 "per_slot": per_slot,
                                 "actions": [60, 0, 0], "depth": 0}],
                     "events": {}})
    return stream


def test_auto_remediator_runs_retrain_canary_pipeline(bank2, corpus):
    pool, _labels, oracle = corpus
    rng = np.random.default_rng(6)
    rt = DataplaneRuntime(bank2, num_queues=2, batch=128,
                          ring_capacity=2048, audit=True)
    sampler = deploy.PacketSampler(oracle, num_slots=2).attach(rt)
    det = AnomalyDetector(_mix_shift_stream(), num_queues=2, num_slots=2,
                          window=4)
    rem = deploy.AutoRemediator(
        rt, det, sampler=sampler,
        trainer=deploy.OnlineTrainer(steps=16, seed=0),
        canary_kw=dict(bake_ticks=4, min_samples=16),
        min_retrain_samples=32)
    _drive(rt, pool, rng, 3)          # fill the reservoirs first
    rem.step()                        # proposal -> fine-tune -> canary
    events = [d["event"] for d in rt.deploy_log]
    assert events[:2] == ["retrain", "canary_start"]
    retrain = rt.deploy_log[0]
    assert retrain["reason"] == "slot_mix_shift" and retrain["slot"] == 1
    for _ in range(5):
        _drive(rt, pool, rng, 1)
        rem.step()
    rem.flush()
    rt.drain()
    events = [d["event"] for d in rt.deploy_log]
    assert sum(e in ("promoted", "rolled_back") for e in events) == 1
    # dedup: the same proposal never retrains twice
    rem.step()
    assert sum(e == "retrain" for e in
               [d["event"] for d in rt.deploy_log]) == 1
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["wrong_verdict"] == 0
    assert rt.control.continuity_audit()["ok"]
    sampler.detach()


def test_auto_remediator_submits_routing_proposals_as_epochs(bank2, corpus):
    pool, _labels, oracle = corpus
    rng = np.random.default_rng(7)
    rt = DataplaneRuntime(bank2, num_queues=4, batch=128,
                          ring_capacity=4096, audit=True)
    stream = TelemetryStream()
    from repro.obs import attach, detach
    attach(rt, stream)
    det = AnomalyDetector(stream, num_queues=4, num_slots=2)
    rem = deploy.AutoRemediator(rt, det)
    driver = deploy.DeployDriver(rt, rem)
    trace = _rendered("elephant-skew", 0, queues=4)
    workloads.play(driver, trace)
    driver.flush_deploy()
    detach(rt)
    acts = [d for d in rt.deploy_log if d["event"] == "auto_remediate"]
    assert acts and acts[0]["command"]["cmd"] == "program_reta"
    assert acts[0]["epoch"] is not None
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["wrong_verdict"] == 0
    assert rt.control.continuity_audit()["ok"]


# ---------------------------------------------------------------------------
# epoch-log provenance + record/replay
# ---------------------------------------------------------------------------

def test_epoch_log_doc_carries_deployments(bank2, trained):
    rt = DataplaneRuntime(bank2, num_queues=2)
    ctl = deploy.CanaryController(rt, None, bake_ticks=3)
    ctl.start(trained.params)
    ctl.flush()
    doc = spans.epoch_log_doc(rt)
    assert [d["event"] for d in doc["deployments"]] == \
        ["canary_start", "rolled_back"]
    assert doc["continuity"]["ok"]
    applied = {e["epoch"] for e in doc["epochs"]}
    for d in doc["deployments"]:
        assert d["epoch"] in applied   # every decision is a typed epoch


def test_recorded_deploy_run_replays_bit_exact(bank2, corpus):
    pool, _labels, oracle = corpus
    trace = _rendered("emergency")
    rt = DataplaneRuntime(bank2, num_queues=2, batch=128,
                          ring_capacity=4096, record=True)
    rec = workloads.record(rt)
    driver = deploy.DeployDriver(rec)
    sampler = deploy.PacketSampler(oracle, num_slots=2).attach(rt)
    pilot = deploy.ScheduledRollout(
        driver, sampler, deploy.OnlineTrainer(steps=8, seed=0),
        warmup_ticks=4, min_samples=24,
        canary_kw=dict(bake_ticks=4, min_samples=16))
    driver.add(pilot)
    workloads.play(driver, trace)
    driver.flush_deploy()
    sampler.detach()
    assert pilot.decision is not None
    saved = rec.finish(name="deploy-promote", seed=0)
    swap_epochs = [s for s in saved.steps if s["kind"] == "commands"
                   and any(type(c).__name__ == "SwapSlot"
                           for c in s["commands"])]
    assert len(swap_epochs) >= 2       # canary_start + decision recorded
    rep = workloads.replay(saved, workloads.make_runtime(saved))
    assert rep["ok"] and rep["digest_ok"]


# ---------------------------------------------------------------------------
# the canary-lifecycle property (ISSUE 8 satellite): every rollout ends
# in exactly one of promoted/rolled-back, with zero wrong verdicts and
# conservation intact across the bake window
# ---------------------------------------------------------------------------

PROPERTY_REGIMES = ("emergency", "flash-crowd", "slot-thrash")


@settings(max_examples=6, deadline=None)
@given(st.sampled_from(PROPERTY_REGIMES), st.booleans(), st.integers(0, 2))
def test_canary_rollout_property(bank2, corpus, regime, corrupt, seed):
    _pool_words, _labels, oracle = corpus
    trace = _rendered(regime, seed)
    rt = DataplaneRuntime(bank2, num_queues=2, batch=128,
                          ring_capacity=4096, audit=True)
    sampler = deploy.PacketSampler(oracle, num_slots=2, seed=seed).attach(rt)
    driver = deploy.DeployDriver(rt)
    pilot = deploy.ScheduledRollout(
        driver, sampler, deploy.OnlineTrainer(steps=12, seed=seed),
        warmup_ticks=4, min_samples=24, corrupt=corrupt,
        canary_kw=dict(bake_ticks=6, min_samples=16))
    driver.add(pilot)
    workloads.play(driver, trace)
    driver.flush_deploy()
    sampler.detach()

    events = [d["event"] for d in rt.deploy_log]
    terminal = [e for e in events if e in ("promoted", "rolled_back")]
    if pilot.canary is not None:          # a rollout actually started
        assert len(terminal) == 1, events
        if corrupt:
            assert terminal == ["rolled_back"], rt.deploy_log
    else:                                 # not enough labeled traffic
        assert terminal == []
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["wrong_verdict"] == 0
    assert rt.control.continuity_audit()["ok"]


# ---------------------------------------------------------------------------
# launch CLI end-to-end (--deploy-demo)
# ---------------------------------------------------------------------------

def test_cli_deploy_demo_promote(tmp_path):
    import json
    from repro.launch import dataplane as launch
    out = tmp_path / "epochs.json"
    launch.main(["--scenario", "emergency", "--queues", "2", "--slots", "2",
                 "--ring-capacity", "4096", "--deploy-demo", "promote",
                 "--deploy-warmup-ticks", "6", "--deploy-bake-ticks", "6",
                 "--deploy-steps", "8",
                 "--checkpoint-dir", str(tmp_path / "ckpt"),
                 "--epoch-log-json", str(out)])
    doc = json.loads(out.read_text())
    events = [d["event"] for d in doc["deployments"]]
    assert "promoted" in events and "retrain" in events
    assert doc["continuity"]["ok"]
    assert store.list_steps(str(tmp_path / "ckpt"))
