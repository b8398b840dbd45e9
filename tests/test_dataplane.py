"""Multi-queue dataplane: RSS determinism, ring/runtime packet
conservation, per-queue ordering, fan-out parity, and zero-wrong-verdict
continuity across online slot swaps (DESIGN.md §6)."""

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core import bank as bank_lib, executor, packet as pkt, switching
from repro.dataplane import (DataplaneRuntime, PacketRing, Phase,
                             emergency_phases, play, render, rss, scenarios)
from repro.obs import spans


@pytest.fixture(scope="module")
def bank2():
    return executor.init_bank(jax.random.PRNGKey(0), 2)


def small_phases(num_slots=2):
    """A fast 3-phase scenario exercising backpressure, failover and churn."""
    uniform = tuple(1.0 / num_slots for _ in range(num_slots))
    return [
        Phase("steady", ticks=2, burst=64, flows=16, slot_mix=uniform),
        Phase("crowd", ticks=2, burst=192, flows=4, slot_mix=uniform),
        Phase("churn", ticks=2, burst=64, flows=16, slot_mix=uniform,
              failed_queues=(0,), swap_slot=1),
    ]


def small_trace(num_slots=2, seed=0):
    return render(small_phases(num_slots), num_slots=num_slots, seed=seed)


# ---------------------------------------------------------------------------
# RSS dispatch
# ---------------------------------------------------------------------------

def _toeplitz_naive(words, key=rss.DEFAULT_KEY):
    """Independent per-bit reference implementation."""
    data = b"".join(int(w).to_bytes(4, "big") for w in words)
    keyval = int.from_bytes(key, "big")
    kbits = len(key) * 8
    out = 0
    for i, byte in enumerate(data):
        for b in range(8):
            if byte & (0x80 >> b):
                j = i * 8 + b
                out ^= (keyval >> (kbits - 32 - j)) & 0xFFFFFFFF
    return out


_RANDOM_KEY = np.random.default_rng(40).bytes(40)


@pytest.mark.parametrize("key", [rss.DEFAULT_KEY, _RANDOM_KEY],
                         ids=["default_key", "random_key"])
@pytest.mark.parametrize("width", [1, 2, 3, 4])
@pytest.mark.parametrize("batch", [0, 1, 33, 512, "1d"])
def test_toeplitz_matches_reference(batch, width, key):
    rng = np.random.default_rng(width)
    shape = (width,) if batch == "1d" else (batch, width)
    fw = rng.integers(0, 2**32, shape, dtype=np.uint32)
    h = rss.toeplitz_hash(fw, key)
    rows = fw.reshape(-1, width)
    assert h.dtype == np.uint32 and h.shape == (rows.shape[0],)
    assert [int(x) for x in h] == [_toeplitz_naive(r, key) for r in rows]


@pytest.mark.parametrize("width", [1, 4])
def test_toeplitz_key_length(width):
    fw = np.arange(3 * width, dtype=np.uint32).reshape(3, width) * 0x9E3779B9
    exact = _RANDOM_KEY[: 4 * width + 4]       # 32 bits past the input
    assert [int(x) for x in rss.toeplitz_hash(fw, exact)] == [
        _toeplitz_naive(r, exact) for r in fw]
    with pytest.raises(ValueError, match="key too short"):
        rss.toeplitz_hash(fw, exact[:-1])


def test_rss_deterministic_and_flow_affine(rng):
    fw = rng.integers(0, 2**32, (256, rss.FLOW_WORDS), dtype=np.uint32)
    pkts = pkt.make_packets(
        np.zeros(256, np.int64),
        rng.integers(0, 2**32, (256, pkt.PAYLOAD_WORDS), dtype=np.uint32))
    pkts[:, rss.FLOW_WORD_LO : rss.FLOW_WORD_LO + rss.FLOW_WORDS] = fw
    q1 = rss.queue_of(pkts, 4)
    q2 = rss.queue_of(pkts, 4)
    assert (q1 == q2).all()                     # stable across calls
    assert q1.min() >= 0 and q1.max() < 4
    assert len(np.unique(q1)) > 1               # flows actually spread
    # queue depends ONLY on the flow tuple: rewrite slot/payload words
    pkts2 = pkts.copy()
    pkts2[:, pkt.SLOT_WORD] = 1
    pkts2[:, pkt.META_WORDS :] = 0
    assert (rss.queue_of(pkts2, 4) == q1).all()
    # two packets sharing a flow tuple share a queue
    pkts3 = pkts.copy()
    pkts3[:, rss.FLOW_WORD_LO : rss.FLOW_WORD_LO + rss.FLOW_WORDS] = fw[0]
    assert len(np.unique(rss.queue_of(pkts3, 4))) == 1
    # non-power-of-two RETA: every bucket stays reachable (modulo, not mask)
    reta96 = np.arange(96, dtype=np.int32) % 4
    q96 = rss.queue_of(pkts, 4, reta=reta96)
    assert q96.min() >= 0 and q96.max() < 4
    h = rss.toeplitz_hash(fw)
    assert (q96 == reta96[h % np.uint32(96)]).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 8))
def test_rss_property_stable_in_range(seed, num_queues):
    rng = np.random.default_rng(seed)
    fw = rng.integers(0, 2**32, (64, rss.FLOW_WORDS), dtype=np.uint32)
    h = rss.toeplitz_hash(fw)
    assert (h == rss.toeplitz_hash(fw.copy())).all()
    reta = rss.indirection_table(num_queues)
    q = reta[h & np.uint32(rss.RETA_SIZE - 1)]
    assert q.min() >= 0 and q.max() < num_queues


def test_failover_table_moves_only_dead_buckets():
    reta = rss.indirection_table(4)
    fo = rss.failover_table(reta, (0,))
    assert not (fo == 0).any()                  # dead queue fully drained
    live = reta != 0
    assert (fo[live] == reta[live]).all()       # survivors keep affinity
    with pytest.raises(ValueError):
        rss.failover_table(rss.indirection_table(1), (0,))


# ---------------------------------------------------------------------------
# rings
# ---------------------------------------------------------------------------

def test_ring_fifo_tail_drop_and_conservation(rng):
    ring = PacketRing(8, packet_words=4)
    rows = np.arange(12, dtype=np.uint32).reshape(12, 1) * np.ones(
        (1, 4), np.uint32)
    admitted = ring.push(rows)
    assert admitted == 8 and ring.counters.dropped == 4
    out, _ = ring.pop(5)
    assert (out[:, 0] == np.arange(5)).all()    # FIFO, prefix admitted
    ring.mark_completed(5)
    # wraparound: push into freed space
    assert ring.push(rows[:4]) == 4
    out2, _ = ring.pop(100)
    assert (out2[:, 0] == np.r_[np.arange(5, 8), np.arange(4)]).all()
    ring.mark_completed(out2.shape[0])
    s = ring.conservation()
    assert s["producer_ok"] and s["consumer_ok"]
    assert s["offered"] == 16 and s["dropped"] == 4 and s["completed"] == 12


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 32), st.lists(st.integers(0, 20), min_size=1,
                                    max_size=30))
def test_ring_property_conservation(capacity, burst_sizes):
    ring = PacketRing(capacity, packet_words=1)
    seq = 0
    popped = []
    for i, n in enumerate(burst_sizes):
        rows = np.arange(seq, seq + n, dtype=np.uint32)[:, None]
        seq += n
        ring.push(rows)
        if i % 2:
            out, _ = ring.pop(capacity // 2 + 1)
            ring.mark_completed(out.shape[0])
            popped.extend(out[:, 0].tolist())
    out, _ = ring.pop(capacity)
    ring.mark_completed(out.shape[0])
    popped.extend(out[:, 0].tolist())
    s = ring.conservation()
    assert s["producer_ok"] and s["consumer_ok"] and s["occupancy"] == 0
    assert s["offered"] == seq and s["completed"] == len(popped)
    assert sorted(popped) == popped             # FIFO never reorders
    assert len(set(popped)) == len(popped)      # never duplicates


# ---------------------------------------------------------------------------
# runtime: conservation, ordering, fan-out parity
# ---------------------------------------------------------------------------

def run_trace(bank, trace, **kw):
    kw.setdefault("num_queues", 4)
    kw.setdefault("batch", 32)
    kw.setdefault("ring_capacity", 128)
    kw.setdefault("record", True)
    rt = DataplaneRuntime(bank, **kw)
    play(rt, trace)
    return rt


def test_runtime_conservation_and_per_queue_order(bank2):
    trace = small_trace()
    rt = run_trace(bank2, trace, strategy="fused", ring_capacity=64)
    aud = rt.audit_conservation()
    assert aud["ok"], aud
    t = aud["totals"]
    assert t["offered"] == t["completed"] + t["dropped"] == trace.total_packets
    assert t["dropped"] > 0                     # crowd phase forced drops
    # within a queue: sequence stamps strictly increase (no reorder/dup)
    for seqs in rt.completed_seq:
        assert (np.diff(np.asarray(seqs)) > 0).all()
    # across queues + drops: every offered packet accounted exactly once
    completed = [s for qs in rt.completed_seq for s in qs]
    allseq = completed + rt.dropped_seq
    assert len(allseq) == len(set(allseq)) == trace.total_packets


def test_runtime_fanout_parity(bank2):
    trace = small_trace(seed=7)
    kw = dict(ring_capacity=4096)               # no drops: exact comparison
    base = run_trace(bank2, trace, strategy="take", fanout="loop", **kw)
    for strategy, fanout in [("take", "vmap"), ("take", "shard_map"),
                             ("fused", "loop"), ("fused", "vmap"),
                             ("fused", "shard_map")]:
        rt = run_trace(bank2, trace, strategy=strategy, fanout=fanout, **kw)
        assert rt.completed_seq == base.completed_seq, (strategy, fanout)
        assert rt.completed_verdicts == base.completed_verdicts, (
            strategy, fanout)
        assert rt.completed_slots == base.completed_slots, (strategy, fanout)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_all_queue_launch_matches_per_queue_launches(bank2, backend):
    """One launch over every queue's rows a tick gives, per queue, the
    slots, verdicts and actions of one launch per queue and of the exact
    ``take`` path, on ticks with empty queues, partial batches, a
    ``FailQueues`` failover and a mid-run ``SwapSlot`` epoch."""
    trace = small_trace(seed=3)
    kw = dict(strategy="fused", backend=backend, ring_capacity=4096)
    runs = {}
    for name, over in [("all", {}), ("loop", {"fanout": "loop"}),
                       ("take", {"strategy": "take"})]:
        rt = DataplaneRuntime(bank2, **dict(kw, **over), num_queues=4,
                              batch=32, record=True)
        served = []
        rt.on_retire = (lambda q, rows, s, v, a, t, served=served:
                        served.append((t, q, rows[:, scenarios.SEQ_WORD],
                                       s, v, a)))
        play(rt, trace)
        runs[name] = rt, served
    all_rt, all_served = runs["all"]
    assert all_rt.fanout == "vmap"
    assert all_rt.telemetry.slot_swaps == 1
    assert all_rt.failed_queues == {0}
    by_tick = {}
    for t, q, seq, *_ in all_served:
        by_tick.setdefault(t, {})[q] = len(seq)
    assert any(len(qs) < 4 for qs in by_tick.values())          # empty queue
    assert any(n < 32 for qs in by_tick.values() for n in qs.values())
    for name in ("loop", "take"):
        rt, served = runs[name]
        assert rt.completed_seq == all_rt.completed_seq, name
        assert rt.completed_slots == all_rt.completed_slots, name
        assert rt.completed_verdicts == all_rt.completed_verdicts, name
        assert len(served) == len(all_served), name
        for got, want in zip(served, all_served):
            assert got[:2] == want[:2], name
            for g, w in zip(got[2:], want[2:]):
                np.testing.assert_array_equal(g, w, err_msg=name)


def test_retire_taps_get_fresh_arrays_every_tick(bank2, rng):
    """The taps keep references without copying, so no tick's arrays may
    alias another tick's."""
    rt = DataplaneRuntime(bank2, num_queues=2, batch=16, ring_capacity=256,
                          record=True)
    taps = []
    rt.on_retire = lambda q, rows, s, v, a, t: taps.append((s, v, a))
    for _ in range(3):
        rows = pkt.make_packets(
            rng.integers(0, 2, 24),
            rng.integers(0, 2**32, (24, pkt.PAYLOAD_WORDS), dtype=np.uint32))
        rt.dispatch(rows, queues=np.arange(24) % 2)
        rt.tick()
    assert len(taps) == 6
    arrays = [a for tap in taps for a in tap]
    assert len({id(a) for a in arrays}) == len(arrays)
    for i, (s0, v0, a0) in enumerate(taps):
        for s1, v1, a1 in taps[i + 2::2]:           # later ticks
            for x in (s0, v0, a0):
                for y in (s1, v1, a1):
                    assert not np.shares_memory(x, y)
    # what the taps hold still reads as what was retired
    flat = [int(x) for q in range(2) for s, _, _ in taps[q::2] for x in s]
    assert flat == [x for q in range(2) for x in rt.completed_slots[q]]


def test_runtime_failover_drains_dead_queue(bank2):
    trace = small_trace(seed=1)
    rt = DataplaneRuntime(bank2, num_queues=4, strategy="take", batch=32,
                          ring_capacity=4096)
    rt.fail_queues((0,))
    for burst in trace.bursts[0]:
        rt.dispatch(burst)
    assert rt.rings[0].counters.offered == 0
    assert sum(r.counters.offered for r in rt.rings) > 0
    # skewed RETA: failing the only *referenced* queue must still remap
    # onto the live-but-unreferenced queues, not raise
    rt2 = DataplaneRuntime(bank2, num_queues=4, strategy="take", batch=32,
                           ring_capacity=4096)
    rt2.set_reta(np.zeros(rss.RETA_SIZE, np.int32))
    rt2.fail_queues((0,))
    assert not (rt2.reta == 0).any()
    assert set(rt2.reta) <= {1, 2, 3}


def test_rows_hashed_counter(bank2):
    rt = DataplaneRuntime(bank2, num_queues=4, batch=32, ring_capacity=256)
    rt.spans.enable()
    rng = np.random.default_rng(3)
    bursts = [pkt.make_packets(
        rng.integers(0, 2, n),
        rng.integers(0, 2**32, (n, pkt.PAYLOAD_WORDS), dtype=np.uint32))
        for n in (7, 128, 0, 300)]                 # the last one overflows
    for b in bursts:
        rt.dispatch(b)
    counters = rt.spans.snapshot()["counters"]
    assert counters["dp.rows_hashed"] == sum(len(b) for b in bursts)
    # precomputed queue ids bypass the hash, so count nothing
    rt.dispatch(bursts[1], queues=np.zeros(len(bursts[1]), np.int64))
    assert rt.spans.snapshot()["counters"]["dp.rows_hashed"] == 435
    rt.spans.disable()
    rt.dispatch(bursts[0])
    assert rt.spans.snapshot()["counters"]["dp.rows_hashed"] == 435


def test_telemetry_snapshot(bank2):
    rt = DataplaneRuntime(bank2, num_queues=4, batch=32, ring_capacity=4096,
                          record=True, strategy="fused")
    rt.spans.enable()
    play(rt, small_trace(seed=2))
    snap = rt.snapshot()
    assert snap["completed_total"] == sum(
        q["completed"] for q in snap["queues"])
    assert snap["slot_swaps"] == 1 and snap["reta_updates"] >= 2
    busy = [q for q in snap["queues"] if q["completed"]]
    assert busy
    # the tick loop's span summary replaces the old per-queue busy rate
    ticks = spans.tick_summary(rt.spans.snapshot())
    assert ticks["ticks"] >= max(q["ticks"] for q in busy)
    assert 0 < ticks["mean_us"] <= ticks["max_us"]
    # one launch and one pull a non-empty tick, serving every non-empty
    # queue batch of it
    snap_spans = rt.spans.snapshot()
    launches = snap_spans["spans"]["dp.tick.launch"]["count"]
    assert launches == snap_spans["spans"]["dp.retire.d2h"]["count"]
    assert max(q["ticks"] for q in busy) <= launches
    assert snap_spans["counters"]["dp.queue_batches"] == sum(
        q["ticks"] for q in busy)
    for q in busy:
        assert q["latency_p50_us"] <= q["latency_p99_us"]
        assert sum(q["per_slot_total"]) == q["completed"]
        acts = q["actions"]
        assert acts["forward"] + acts["drop"] + acts["flag"] == q["completed"]


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_snapshot_reports_resolved_backend(bank2, rng, backend):
    """The run report names the backend the ticks ran on, and no
    execution-engine key: the tick loop is the only engine."""
    rt = DataplaneRuntime(bank2, num_queues=2, strategy="fused", batch=8,
                          backend=backend)
    rows = pkt.make_packets(
        rng.integers(0, 2, 16),
        rng.integers(0, 2**32, (16, pkt.PAYLOAD_WORDS), dtype=np.uint32))
    rt.dispatch(rows, queues=np.arange(16) % 2)
    rt.drain()
    snap = rt.snapshot()
    assert snap["backend"] == backend
    assert "engine" not in snap
    assert snap["completed_total"] == 16 and snap["conservation"]["ok"]


# ---------------------------------------------------------------------------
# continuity: online slot swap under multi-queue churn
# ---------------------------------------------------------------------------

def test_zero_wrong_verdict_across_online_swap(bank2):
    """Multi-queue extension of the replay_trace zero-wrong-verdict
    regression: audit mode re-scores every tick through the exact take
    path while the slot-churn phase swaps a resident slot online, with
    the replacement weights delivered through the control-plane
    serialize -> deserialize channel."""
    trace = small_trace(seed=4)
    rt = DataplaneRuntime(bank2, num_queues=4, strategy="fused", batch=32,
                          ring_capacity=64, audit=True, record=True)

    def delivery(slot):
        fresh = executor.init_params(jax.random.PRNGKey(100 + slot))
        return switching._deserialize(switching._serialize(fresh), fresh)

    play(rt, trace, swap_delivery=delivery)
    aud = rt.audit_conservation()
    assert aud["ok"], aud
    assert aud["wrong_verdict"] == 0
    assert rt.telemetry.slot_swaps == 1


def test_swap_leaves_other_slots_verdicts_unchanged(bank2, rng):
    """Packets of the untouched slot get identical verdicts before and
    after another slot is hot-swapped (resident continuity)."""
    payload = rng.integers(0, 2**32, (64, pkt.PAYLOAD_WORDS), dtype=np.uint32)
    rows = pkt.make_packets(np.zeros(64, np.int64), payload)
    rows[:, rss.FLOW_WORD_LO : rss.FLOW_WORD_LO + rss.FLOW_WORDS] = \
        rng.integers(0, 2**32, (64, rss.FLOW_WORDS), dtype=np.uint32)
    rows[:, scenarios.SEQ_WORD] = np.arange(64, dtype=np.uint32)

    rt = DataplaneRuntime(bank2, num_queues=2, strategy="fused", batch=64,
                          ring_capacity=256, record=True)
    rt.dispatch(rows)
    rt.drain()
    before = {s: v for qs, qv in zip(rt.completed_seq, rt.completed_verdicts)
              for s, v in zip(qs, qv)}
    rt.swap_slot(1, executor.init_params(jax.random.PRNGKey(99)))
    rows2 = rows.copy()
    rows2[:, scenarios.SEQ_WORD] += 64
    rt.dispatch(rows2)
    rt.drain()
    after = {s - 64: v
             for qs, qv in zip(rt.completed_seq, rt.completed_verdicts)
             for s, v in zip(qs, qv) if s >= 64}
    assert before == after


# ---------------------------------------------------------------------------
# scenario engine
# ---------------------------------------------------------------------------

def test_scenarios_replayable_and_stamped():
    t1 = render(emergency_phases(2), num_slots=2, seed=5)
    t2 = render(emergency_phases(2), num_slots=2, seed=5)
    flat1 = [b for ph in t1.bursts for b in ph]
    flat2 = [b for ph in t2.bursts for b in ph]
    assert all((a == b).all() for a, b in zip(flat1, flat2))
    seqs = np.concatenate([b[:, scenarios.SEQ_WORD] for b in flat1])
    assert (seqs == np.arange(t1.total_packets)).all()
    t3 = render(emergency_phases(2), num_slots=2, seed=6)
    assert any((a != b).any()
               for a, b in zip(flat1, [b for ph in t3.bursts for b in ph]))


def test_emergency_phase_shapes():
    phases = emergency_phases(4, scale=2)
    names = [p.name for p in phases]
    assert names == ["steady", "flash_crowd", "link_failover", "slot_churn"]
    crowd = phases[1]
    assert crowd.burst > phases[0].burst        # surge
    assert crowd.flows < phases[0].flows        # elephant flows
    assert phases[2].failed_queues == (0,)
    assert phases[3].swap_slot is not None
    for p in phases:
        assert abs(sum(p.slot_mix) - 1.0) < 1e-9


# ---------------------------------------------------------------------------
# structural audit: one fused launch per queue-block, and per tick
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_queues", [None, 4])
def test_one_fused_launch_per_queue_block(bank2, rng, num_queues):
    """One queue's block (``packet_step``) and a whole tick of ``num_queues``
    queue blocks (``packet_step_queues``) each trace to ONE Pallas launch
    with no payload-sized scatter or gather."""
    common = pytest.importorskip("benchmarks.common")
    from repro.core import pipeline

    q = num_queues or 1
    packets = pkt.make_packets(
        np.arange(32 * q) % 2,
        rng.integers(0, 2**32, (32 * q, pkt.PAYLOAD_WORDS), dtype=np.uint32))
    kw = dict(num_slots=2, strategy="fused", backend="pallas", block_b=16)

    def queue_block_step(p):
        return pipeline.packet_step(bank2, p, **kw)

    def tick_step(p):
        return pipeline.packet_step_queues(bank2, p, **kw)

    import jax.numpy as jnp
    if num_queues is None:
        step, x = queue_block_step, jnp.asarray(packets)
    else:
        step, x = tick_step, jnp.asarray(packets.reshape(q, 32, -1))
    stats = common.jaxpr_stats(
        step, x, payload_threshold=32 * pkt.PAYLOAD_WORDS * 4)
    assert stats["kernel_launches"] == 1
    assert stats["payload_roundtrip_bytes"] == 0
