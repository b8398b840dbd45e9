"""Observability pipeline (DESIGN.md §11): telemetry delta streaming,
the dashboard API, replay-testable anomaly detection over the full
regime corpus, and the streaming trace codec."""

import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.control import FailQueues, ProgramReta, SwapSlot
from repro.core import executor
from repro.core import packet as pkt
from repro.dataplane import (DataplaneRuntime, MeshDataplane, faults,
                             telemetry as telemetry_mod, workloads)
from repro.dataplane.workloads import generators
from repro.dataplane.workloads import trace as trace_mod
from repro.obs import AnomalyDetector, TelemetryStream, attach, detach
from repro.obs import spans
from repro.obs.anomaly import RetrainRequest
from repro.obs.server import ObsServer


@pytest.fixture(scope="module")
def bank2():
    return executor.init_bank(jax.random.PRNGKey(0), 2)


#: regimes whose detection evidence needs the mesh + armed fault plan
MESH_REGIMES = ("cascading-failover", "chaos-host-failover",
                "barrier-straggler", "crash-mid-commit")


def _state_fingerprint(state: dict):
    """The routing-state keys shared by runtime and mesh snapshots."""
    return (np.asarray(state["reta"]).tolist(), sorted(state["failed"]),
            np.asarray(state["bucket_load"]).tolist(),
            state["slot_swaps"], state["reta_updates"])


def _regime_setup(bank, regime):
    hosts = 2 if regime in MESH_REGIMES else 1
    queues = 2 if regime in MESH_REGIMES else 4
    w = workloads.make_workload(
        regime, num_slots=2, num_queues=queues, hosts=hosts,
        corpus_root=generators.SYNTHETIC_CORPUS)
    trace = workloads.synthesize(
        w.phases, num_slots=2, num_queues=hosts * queues, seed=0,
        name=regime, payload_pool=w.payload_pool)
    kw = dict(batch=128, ring_capacity=4096, record=True)
    if hosts > 1:
        injector = (faults.FaultInjector(w.fault_plan)
                    if w.fault_plan is not None else None)
        rt = MeshDataplane(bank, hosts=hosts, num_queues=queues,
                           fault_injector=injector, **kw)
    else:
        rt = DataplaneRuntime(bank, num_queues=queues, **kw)
    return rt, trace, hosts, hosts * queues


def _packets(rng, n, num_slots=2):
    slots = rng.integers(0, num_slots, n)
    payload = rng.integers(0, 2**32, (n, pkt.PAYLOAD_WORDS), dtype=np.uint32)
    return pkt.make_packets(slots, payload)


# ---------------------------------------------------------------------------
# delta stream
# ---------------------------------------------------------------------------

def _fold(events):
    """Sum a delta-event list back into cumulative totals."""
    tot = {"completed": {}, "dropped": {}, "per_slot": {}, "actions": {},
           "events": {}}
    for ev in events:
        if ev.get("kind") != "delta":
            continue
        for q in ev["queues"]:
            qid = q["queue"]
            tot["completed"][qid] = tot["completed"].get(qid, 0) + q["completed"]
            tot["dropped"][qid] = tot["dropped"].get(qid, 0) + q["dropped"]
            tot["per_slot"][qid] = (np.asarray(q["per_slot"])
                                    + tot["per_slot"].get(qid, 0))
            tot["actions"][qid] = (np.asarray(q["actions"])
                                   + tot["actions"].get(qid, 0))
        for name, d in ev["events"].items():
            tot["events"][name] = tot["events"].get(name, 0) + d
    return tot


def _assert_stream_matches_snapshot(rt, events):
    snap = rt.telemetry.snapshot()
    tot = _fold(events)
    for q in snap["queues"]:
        qid = q["queue"]
        assert tot["completed"].get(qid, 0) == q["completed"]
        assert tot["dropped"].get(qid, 0) == q["dropped"]
        if q["completed"]:
            assert np.array_equal(tot["per_slot"][qid], q["per_slot_total"])
    for name in telemetry_mod.EVENT_COUNTERS:
        assert tot["events"].get(name, 0) == snap[name], name


def test_delta_stream_sums_to_snapshot_on_replay(bank2):
    rt, trace, _, _ = _regime_setup(bank2, "emergency")
    events = []
    rt_tele = rt.telemetry
    rt_tele.attach_sink(events.append)
    workloads.replay(trace, rt)
    assert events, "no deltas emitted"
    assert [e["seq"] for e in events] == list(range(len(events)))
    _assert_stream_matches_snapshot(rt, events)
    # rollback epochs may legitimately emit negative event deltas;
    # the stream must still SUM to the live counters (checked above)
    assert all(q["completed"] >= 0 for e in events for q in e["queues"])


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 80), st.booleans()),
                min_size=1, max_size=10),
       st.integers(0, 2**31 - 1))
def test_delta_stream_sum_property(bank2, plan, seed):
    """Any dispatch/tick interleaving: delta stream sums to snapshot()."""
    rng = np.random.default_rng(seed)
    rt = DataplaneRuntime(bank2, num_queues=3, batch=32, ring_capacity=64)
    events = []
    rt.telemetry.attach_sink(events.append)
    for n, do_tick in plan:
        rt.dispatch(_packets(rng, n))  # tiny ring: drops exercised too
        if do_tick:
            rt.tick()
    rt.drain()
    rt.retire_all()
    _assert_stream_matches_snapshot(rt, events)


def test_first_delta_carries_preattach_counters(bank2):
    rng = np.random.default_rng(1)
    rt = DataplaneRuntime(bank2, num_queues=2, batch=64, ring_capacity=256)
    rt.dispatch(_packets(rng, 32))
    rt.drain()
    events = []
    rt.telemetry.attach_sink(events.append)  # cursor resets on attach
    rt.dispatch(_packets(rng, 16))
    rt.drain()
    _assert_stream_matches_snapshot(rt, events)
    first_total = sum(q["completed"] for q in events[0]["queues"])
    assert first_total >= 32  # pre-attach work is in the first delta


def test_stream_ring_cursor_and_overflow():
    stream = TelemetryStream(capacity=8)
    for i in range(20):
        stream.push({"kind": "delta", "i": i})
    assert len(stream) == 8
    assert stream.dropped_events == 12
    events, cur = stream.tail(0)  # stale cursor resumes at oldest
    assert [e["i"] for e in events] == list(range(12, 20))
    assert cur == 20
    events, cur = stream.tail(cur)
    assert events == [] and cur == 20
    stream.push({"kind": "delta", "i": 20})
    events, cur = stream.tail(cur, limit=1)
    assert [e["i"] for e in events] == [20]


def test_epoch_and_health_spans_on_stream(bank2):
    rt, trace, _, _ = _regime_setup(bank2, "crash-mid-commit")
    stream = TelemetryStream()
    attach(rt, stream)
    workloads.replay(trace, rt)
    kinds = {e["kind"] for e in stream.latest(10_000)}
    assert {"delta", "epoch", "health"} <= kinds
    epochs = [e for e in stream.latest(10_000) if e["kind"] == "epoch"]
    for e in epochs:
        span = e["span"]
        assert span["outcome"] in ("atomic", "degraded", "rollback")
        if span["apply_us"] is not None:
            assert span["total_us"] >= span["apply_us"] >= 0
            assert span["queued_us"] >= 0
    # the mesh epoch log and the stream saw the same epochs
    assert len(epochs) == len(rt.control.log)
    detach(rt)
    assert not rt.shards[0].telemetry.has_sink


# ---------------------------------------------------------------------------
# telemetry merge under uneven host ticking
# ---------------------------------------------------------------------------

def test_merge_carries_event_counters_and_aligns_windows():
    a = telemetry_mod.Telemetry(2, 2)
    b = telemetry_mod.Telemetry(2, 2)
    a.runtime_ticks, b.runtime_ticks = 40, 3  # b stalled most of the run
    a.slot_swaps, b.slot_swaps = 2, 1
    a.reta_updates, b.reta_updates = 1, 0
    a.record_drops(0, 5, now=10.0)
    b.record_drops(1, 7, now=10.5)
    a.queues[0].record(np.array([0, 1]), np.array([False, False]),
                       np.array([0, 0]), np.array([1.0, 1.0]))
    a.touch(18.0)   # a covered 10.0 .. 18.0
    b.touch(11.0)   # b covered 10.5 .. 11.0 (crashed early)
    m = telemetry_mod.merge([a, b])
    assert m.runtime_ticks == 43
    assert m.slot_swaps == 3 and m.reta_updates == 1
    assert m.dropped_total == 12
    # union window, not either host's own: 10.0 .. 18.0
    assert m.window_start_s == 10.0 and m.window_last_s == 18.0
    snap = m.snapshot()
    assert snap["runtime_ticks"] == 43 and snap["dropped_total"] == 12
    assert snap["aggregate_pps"] == pytest.approx(2 / 8.0)


def test_mesh_snapshot_merge_matches_shard_sums(bank2):
    rt, trace, _, _ = _regime_setup(bank2, "chaos-host-failover")
    workloads.replay(trace, rt)
    snap = rt.snapshot()
    assert snap["runtime_ticks"] == sum(
        s.telemetry.runtime_ticks for s in rt.shards)
    assert snap["dropped_total"] == sum(
        s.telemetry.dropped_total for s in rt.shards)


# ---------------------------------------------------------------------------
# anomaly detection over the full corpus
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("regime", workloads.REGIME_NAMES)
def test_detector_classifies_regime(bank2, regime):
    rt, trace, hosts, num_queues = _regime_setup(bank2, regime)
    stream = TelemetryStream(capacity=1 << 16)
    attach(rt, stream)
    det = AnomalyDetector(stream, num_queues=num_queues, num_slots=2,
                          hosts=hosts)
    workloads.replay(trace, rt)
    det.poll()
    got = det.classify()
    assert got["regime"] == regime, (got["regime"], got["evidence"])
    assert det.detect_tick() is not None

    # proposals must stage-accept without mutating the control plane
    # (RetrainRequest is a deploy-plane proposal, not a control command;
    # SwapSlot proposals are specs — materialized before staging, the
    # trace-format convention)
    before = rt.control.stats()["epochs_applied"]
    state_before = _state_fingerprint(rt._control_state())
    for cmd in det.proposals():
        if isinstance(cmd, RetrainRequest):
            assert cmd.describe()["cmd"] == "retrain"
            continue
        assert isinstance(cmd, (ProgramReta, FailQueues, SwapSlot))
        rt._validate_command(workloads.materialize_command(cmd))
    assert rt.control.stats()["epochs_applied"] == before
    assert _state_fingerprint(rt._control_state()) == state_before


def test_detector_proposes_failover_for_silent_queue():
    """A backlogged queue that stops completing draws a FailQueues
    proposal (unit-level: crafted deltas, no runtime)."""
    stream = TelemetryStream()
    det = AnomalyDetector(stream, num_queues=2, num_slots=2,
                          silence_ticks=3)
    for tick in range(10):
        q1_done = 32 if tick < 3 else 0  # completes early, then stalls
        stream.push({"kind": "delta", "seq": tick, "tick": tick,
                     "t_s": None, "host": 0,
                     "queues": [{"queue": 0, "completed": 64, "dropped": 0,
                                 "per_slot": [32, 32], "actions": [64, 0, 0],
                                 "depth": 0},
                                {"queue": 1, "completed": q1_done,
                                 "dropped": 0, "per_slot": [q1_done, 0],
                                 "actions": [q1_done, 0, 0],
                                 "depth": 40}],
                     "events": {}})
    det.poll()
    assert any(f.detector == "queue_silence" for f in det.findings)
    props = det.proposals()
    fails = [c for c in props if isinstance(c, FailQueues)]
    assert fails and 1 in fails[0].queues


def _delta(tick, queues):
    return {"kind": "delta", "seq": tick, "tick": tick, "t_s": None,
            "host": 0, "queues": queues, "events": {}}


def test_detector_proposes_retrain_on_slot_mix_shift():
    """A flipped slot mix draws a SwapSlot *spec* (params=None) plus a
    RetrainRequest for the now-dominant slot (unit-level: crafted
    deltas, no runtime)."""
    stream = TelemetryStream()
    det = AnomalyDetector(stream, num_queues=2, num_slots=2, window=4)
    for tick in range(16):
        per_slot = [64, 0] if tick < 8 else [0, 64]  # mix flips at t=8
        stream.push(_delta(tick, [
            {"queue": 0, "completed": 64, "dropped": 0,
             "per_slot": per_slot, "actions": [64, 0, 0], "depth": 0},
            {"queue": 1, "completed": 60, "dropped": 0,
             "per_slot": per_slot, "actions": [60, 0, 0], "depth": 0}]))
    det.poll()
    assert any(f.detector == "slot_mix_shift" for f in det.findings)
    props = det.proposals()
    swaps = [c for c in props if isinstance(c, SwapSlot)]
    retrains = [c for c in props if isinstance(c, RetrainRequest)]
    assert swaps and swaps[0].slot == 1 and swaps[0].params is None
    assert retrains and retrains[0].slot == 1
    assert retrains[0].reason == "slot_mix_shift"
    assert retrains[0].describe()["cmd"] == "retrain"


def test_detector_proposes_retrain_on_drop_surge():
    """A sustained drop surge without routing skew (balanced queues)
    means the model, not the RETA, mismatches the traffic -> retrain."""
    stream = TelemetryStream()
    det = AnomalyDetector(stream, num_queues=2, num_slots=2, window=4)
    for tick in range(12):
        drops = 0 if tick < 6 else 24  # ring-edge drops start at t=6
        stream.push(_delta(tick, [
            {"queue": 0, "completed": 64, "dropped": drops,
             "per_slot": [64, 0], "actions": [64, 0, 0], "depth": 0},
            {"queue": 1, "completed": 60, "dropped": drops,
             "per_slot": [60, 0], "actions": [60, 0, 0], "depth": 0}]))
    det.poll()
    assert any(f.detector == "drop_surge" for f in det.findings)
    assert det.classify()["regime"] != "elephant-skew"
    retrains = [c for c in det.proposals()
                if isinstance(c, RetrainRequest)]
    assert retrains and retrains[0].slot == 0
    assert retrains[0].reason == "drop_surge"


# ---------------------------------------------------------------------------
# dashboard API
# ---------------------------------------------------------------------------

def test_server_endpoints(bank2):
    rt, trace, _, _ = _regime_setup(bank2, "emergency")
    stream = TelemetryStream()
    attach(rt, stream)
    det = AnomalyDetector(stream, num_queues=4, num_slots=2)
    with ObsServer(rt, stream, detector=det) as srv:
        workloads.replay(trace, rt)
        base = f"http://127.0.0.1:{srv.port}"

        def get(ep):
            return json.load(urllib.request.urlopen(base + ep, timeout=10))

        assert get("/healthz")["ok"]
        m = get("/metrics")
        snap = rt.telemetry.snapshot()
        assert m["totals"]["completed"] == snap["completed_total"]
        assert m["totals"]["dropped"] == snap["dropped_total"]
        assert len(m["queues"]) == 4
        e = get("/epochs")
        assert e["api_version"] == rt.control.API_VERSION
        assert len(e["epochs"]) == len(rt.control.log)
        assert all("span" in rec for rec in e["epochs"])
        # /epochs serves the SAME document --epoch-log-json writes
        from repro.obs.server import _json_default
        assert e == json.loads(json.dumps(
            spans.epoch_log_doc(rt), default=_json_default))
        a = get("/anomaly")
        assert a["enabled"] and a["regime"] == "emergency"
        assert all(isinstance(p, dict) and "cmd" in p
                   for p in a["proposals"])
        html = urllib.request.urlopen(base + "/", timeout=10).read()
        assert b"dataplane observer" in html
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)


# ---------------------------------------------------------------------------
# streaming trace codec
# ---------------------------------------------------------------------------

def _record_run(bank, path=None):
    w = workloads.make_workload("emergency", num_slots=2, num_queues=4)
    rendered = workloads.render(list(w.phases), num_slots=2, seed=3,
                                num_queues=4, payload_pool=w.payload_pool)
    rt = DataplaneRuntime(bank, num_queues=4, batch=128,
                          ring_capacity=4096, record=True)
    rec = workloads.record(rt, path=path)
    workloads.play(rec, rendered)
    return rec.finish(name="emergency", seed=3)


def test_streamed_recording_matches_buffered_save(bank2, tmp_path):
    buffered = _record_run(bank2)
    buf_path = str(tmp_path / "buffered.bswt")
    workloads.save(buffered, buf_path)
    stream_path = str(tmp_path / "streamed.bswt")
    streamed = _record_run(bank2, path=stream_path)
    assert isinstance(streamed, workloads.StreamedTrace)
    assert streamed.steps == len(buffered.steps)
    assert streamed.total_packets == buffered.total_packets
    assert (open(buf_path, "rb").read()
            == open(stream_path, "rb").read())
    loaded = workloads.load(stream_path)
    assert all(
        np.array_equal(s1["rows"], s2["rows"])
        for s1, s2 in zip(buffered.steps, loaded.steps)
        if s1["kind"] == "burst")
    rep = workloads.replay(loaded, workloads.make_runtime(loaded))
    assert rep["ok"] and rep["digest_ok"]


def test_v1_monolithic_traces_still_load(bank2, tmp_path):
    trace = _record_run(bank2)
    path = str(tmp_path / "old.bswt")
    trace_mod._save_v1(trace, path)
    with open(path, "rb") as f:
        assert f.read(9)[-1] == 1  # genuinely on-disk v1
    loaded = workloads.load(path)
    rep = workloads.replay(loaded, workloads.make_runtime(loaded))
    assert rep["ok"] and rep["digest_ok"]


def test_unfinished_streaming_recording_rejected(bank2, tmp_path):
    path = str(tmp_path / "partial.bswt")
    rt = DataplaneRuntime(bank2, num_queues=2, batch=64, ring_capacity=256)
    rec = workloads.record(rt, path=path)
    rng = np.random.default_rng(0)
    for _ in range(40):  # enough bytes to flush at least one chunk
        rec.dispatch(_packets(rng, 64))
        rec.tick()
    rec.abort()
    with pytest.raises(ValueError, match="tail chunk"):
        workloads.load(path)


def test_streaming_recorder_bounds_buffering(bank2, tmp_path):
    """Chunks hit the disk DURING the run, not at finish()."""
    import os
    path = str(tmp_path / "grow.bswt")
    rt = DataplaneRuntime(bank2, num_queues=2, batch=64, ring_capacity=1024)
    rec = workloads.record(rt, path=path, chunk_bytes=1 << 14)
    rng = np.random.default_rng(0)
    sizes = []
    for _ in range(12):
        rec.dispatch(_packets(rng, 64))
        rec.tick()
        sizes.append(os.path.getsize(path))
    assert sizes[-1] > sizes[0] > 0
    rec.finish(name="grow", seed=0)
    loaded = workloads.load(path)
    assert loaded.meta["name"] == "grow"


# ---------------------------------------------------------------------------
# launch CLI: --epoch-log-json
# ---------------------------------------------------------------------------

def test_cli_epoch_log_json(tmp_path, capsys):
    from repro.launch import dataplane as launch
    out = tmp_path / "epochs.json"
    snap = launch.main(["--scenario", "emergency", "--queues", "2",
                        "--slots", "2", "--ring-capacity", "2048",
                        "--epoch-log-json", str(out)])
    # the CLI turns the tick loop's spans on and reports them
    assert snap["tick_spans"][0]["ticks"] == snap["runtime_ticks"]
    assert "tick: " in capsys.readouterr().out
    doc = json.loads(out.read_text())
    assert doc["epochs"], "no epochs in log"
    assert doc["continuity"]["ok"]
    for rec in doc["epochs"]:
        assert "span" in rec and "commands" in rec
    assert doc["stats"]["epochs_applied"] >= len(
        [r for r in doc["epochs"] if r["commit_mode"] == "atomic"])


# ---------------------------------------------------------------------------
# host spans of the tick loop (HostSpans)
# ---------------------------------------------------------------------------

class _FakeClock:
    """Integer nanoseconds handed out from a script; counts its reads."""

    def __init__(self, times=()):
        self.times = list(times)
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.times.pop(0)


def test_host_spans_disabled_records_nothing():
    clock = _FakeClock()
    rec = spans.HostSpans(clock=clock)
    a, b = rec.span("dp.tick"), rec.span("dp.tick.pop")
    assert a is b                       # one shared no-op context
    with a:
        with b:
            rec.count("dp.rows_popped", 5)
    assert clock.reads == 0
    assert rec.snapshot() == {"spans": {}, "counters": {},
                              "slowest_ticks": []}
    assert spans.tick_summary(rec.snapshot()) is None


def test_host_spans_self_time_nests():
    # tick [0, 100]: pop [10, 30], launch [40, 90] holding h2d [50, 60]
    clock = _FakeClock([0, 10, 30, 40, 50, 60, 90, 100])
    rec = spans.HostSpans(clock=clock)
    rec.enable()
    with rec.span("dp.tick"):
        with rec.span("dp.tick.pop"):
            pass
        with rec.span("dp.tick.launch"):
            with rec.span("dp.tick.h2d"):
                pass
    s = rec.snapshot()["spans"]
    assert s["dp.tick"] == {"count": 1, "total_ns": 100, "self_ns": 30,
                            "max_ns": 100}
    assert s["dp.tick.pop"]["self_ns"] == 20
    assert (s["dp.tick.launch"]["total_ns"],
            s["dp.tick.launch"]["self_ns"]) == (50, 40)
    assert s["dp.tick.h2d"]["self_ns"] == 10
    (tick,) = rec.snapshot()["slowest_ticks"]
    assert tick == {"total_ns": 100, "self_ns": {
        "dp.tick": 30, "dp.tick.pop": 20, "dp.tick.launch": 40,
        "dp.tick.h2d": 10}}
    # the self times of a tick add up to its total
    assert sum(tick["self_ns"].values()) == tick["total_ns"]


def test_host_spans_keep_the_longest_ticks_bounded():
    times = []
    t = 0
    for d in range(1, 21):       # 20 ticks of 1..20 ns, each with a pop
        times += [t, t, t + d, t + d]
        t += 100
    rec = spans.HostSpans(clock=_FakeClock(times))
    rec.enable()
    for _ in range(20):
        with rec.span("dp.tick"):
            with rec.span("dp.tick.pop"):
                pass
    kept = rec.snapshot()["slowest_ticks"]
    assert len(kept) == spans.HostSpans.LONGEST == 8
    assert [k["total_ns"] for k in kept] == list(range(20, 12, -1))
    assert all(k["self_ns"] == {"dp.tick.pop": k["total_ns"], "dp.tick": 0}
               for k in kept)
    assert rec.snapshot()["spans"]["dp.tick"]["max_ns"] == 20
    rec.reset()
    assert rec.snapshot()["slowest_ticks"] == []


def test_host_spans_counters_add_up():
    rec = spans.HostSpans()
    rec.enable()
    for n in (3, 4, 5):
        rec.count("dp.rows_popped", n)
    rec.count("dp.kernel_rows", 160)
    assert rec.snapshot()["counters"] == {"dp.rows_popped": 12,
                                          "dp.kernel_rows": 160}
    rec.reset()
    assert rec.snapshot()["counters"] == {}


@pytest.mark.parametrize("fanout", ["auto", "loop"])
def test_runtime_tick_spans_once_per_nonempty_queue(bank2, fanout):
    """The per-queue retire spans open once per non-empty queue; the
    copy, launch and pull once per tick (``auto``: one launch over every
    queue's rows) or once per non-empty queue (``loop``)."""
    from repro.core import bank as bank_lib
    rt = DataplaneRuntime(bank2, num_queues=4, batch=32, ring_capacity=256,
                          fanout=fanout)
    rt.on_retire = lambda *a: None
    rt.spans.enable()
    rng = np.random.default_rng(0)
    queues = np.array([0] * 5 + [1] * 7 + [3] * 2)   # queue 2 stays empty
    rt.dispatch(_packets(rng, len(queues)), queues=queues)
    assert rt.tick() == len(queues)
    snap = rt.spans.snapshot()
    counts = {k: v["count"] for k, v in snap["spans"].items()}
    launches = 1 if fanout == "auto" else 3
    per_launch = ("dp.tick.h2d", "dp.tick.launch", "dp.retire.d2h")
    assert {k: counts[k] for k in per_launch} == dict.fromkeys(
        per_launch, launches)
    per_queue = ("dp.retire.tap", "dp.retire.telemetry")
    assert {k: counts[k] for k in per_queue} == dict.fromkeys(per_queue, 3)
    for name in ("dp.dispatch", "dp.tick", "dp.tick.control", "dp.tick.pop",
                 "dp.tick.pad", "dp.retire.wait"):
        assert counts[name] == 1, name
    assert "dp.retire.audit" not in counts       # audit is off
    assert "dp.dispatch.hash" not in counts      # queue ids were given
    assert counts["dp.dispatch.push"] == 1
    assert snap["counters"]["dp.rows_popped"] == len(queues)
    assert snap["counters"]["dp.ring_wait_ns"] > 0
    assert snap["counters"]["dp.queue_batches"] == 3
    rows_per_launch = 4 * 32 if fanout == "auto" else 32
    assert snap["counters"]["dp.kernel_rows"] == launches * (
        bank_lib.padded_rows(rows_per_launch, 2, 32))
    # every span of the tick ran inside it
    (tick,) = snap["slowest_ticks"]
    assert set(tick["self_ns"]) == {k for k in counts
                                    if k.startswith(("dp.tick",
                                                     "dp.retire."))}


@pytest.mark.parametrize("num_queues", [1, 4])
@pytest.mark.parametrize("num_slots", [1, 16])
def test_kernel_rows_counter_is_the_padded_grouping(num_slots, num_queues):
    import jax.numpy as jnp
    from repro.core import bank as bank_lib
    bank = executor.init_bank(jax.random.PRNGKey(1), num_slots)
    rt = DataplaneRuntime(bank, num_queues=num_queues, batch=128, block_b=32)
    rt.spans.enable()
    rng = np.random.default_rng(num_slots)
    n = 128 * num_queues
    rt.dispatch(_packets(rng, n, num_slots),
                queues=np.arange(n) % num_queues)
    rt.tick()
    b_pad = bank_lib.group_by_slot_padded(
        jnp.zeros(n, jnp.int32), num_slots, 32).b_pad
    assert b_pad == {(1, 1): 160, (16, 1): 640,
                     (1, 4): 544, (16, 4): 1024}[num_slots, num_queues]
    counters = rt.spans.snapshot()["counters"]
    assert counters["dp.kernel_rows"] == b_pad
    assert counters["dp.queue_batches"] == num_queues


def test_host_spans_reach_the_profiler_trace(bank2, tmp_path):
    import glob
    import os
    from jax.profiler import ProfileData
    rt = DataplaneRuntime(bank2, num_queues=2, batch=32)
    rng = np.random.default_rng(2)
    rt.dispatch(_packets(rng, 16))
    rt.tick()                                    # compile outside the trace
    rt.spans.enable(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rt.dispatch(_packets(rng, 16))
        rt.tick()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    recorded = set(rt.spans.snapshot()["spans"])
    assert {"dp.dispatch", "dp.tick", "dp.tick.launch",
            "dp.retire.wait"} <= recorded
    assert recorded <= names
