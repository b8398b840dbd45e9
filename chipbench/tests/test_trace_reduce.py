"""The reduction from a profiler trace to metrics.

Hand-made intervals check the arithmetic; a trace of eight ticks of the
``h32-k16-q4.saturate`` cell, recorded on a TPU v5e by
``record_trace.py`` and kept in ``data/``, checks the reading of a real
trace against numbers read from it once by hand.  A second, of four
ticks of the same configuration with 16 queues over four chips, checks
that a cell's chips are read alone and each aligned with its own
launches.
"""

import os

import pytest

from chipbench import tracing
from chipbench.metrics import kernel_ns_per_pkt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tick_trace.xplane.pb")
DATA4 = os.path.join(os.path.dirname(DATA), "tick_trace_4chips.xplane.pb")


def _reduced(ops, spans, window=(0, 100)):
    dev = tracing.Device(name="/device:TPU:0", ops=ops, modules=[],
                         aligned=True)
    return tracing.Reduced(window=window, spans=spans, devices=[dev])


def test_busy_is_the_union_clipped_to_the_window():
    r = _reduced([(-10, 5, "a"), (20, 40, "b"), (30, 50, "c"), (90, 120, "d")],
                 [])
    # [0,5] + [20,50] + [90,100] = 45 ns
    assert r.busy_s() == pytest.approx(45e-9)
    assert r.window_s == pytest.approx(100e-9)


def test_idle_gaps_split_by_host_span():
    r = _reduced([(10, 20, "k"), (60, 70, "k")],
                 [(0, 30, "tick"), (30, 45, "dispatch"), (45, 80, "tick")])
    # gaps: [0,10] tick; [20,60] = tick 10 + dispatch 15 + tick 15;
    # [70,100] = tick 10 + other 20
    got = dict((k, v * 1e9) for k, v in r.idle_by_span())
    assert got == pytest.approx({"tick": 45, "dispatch": 15, "other": 20})
    assert sum(got.values()) == pytest.approx(100 - 20)


def test_op_seconds_by_pattern_and_module():
    dev = tracing.Device(
        name="/device:TPU:0",
        ops=[(10, 20, "%fused_forward.1 = x custom-call(), "
              'custom_call_target="tpu_custom_call"'),
             (20, 25, "%sort.0 = sort()"), (50, 55, "%other = add()")],
        modules=[(5, 30, "jit_packet_step(1)"), (45, 60, "jit_other(2)")],
        aligned=True)
    r = tracing.Reduced(window=(0, 100), spans=[], devices=[dev])
    assert r.op_seconds(kernel_ns_per_pkt.match) == pytest.approx(10e-9)
    assert r.op_seconds(lambda t: not kernel_ns_per_pkt.match(t),
                        within="jit_packet_step") == pytest.approx(5e-9)
    assert r.top_ops(2) == [["%fused_forward.1", 10e-9], ["%sort.0", 5e-9]]


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(DATA):
        pytest.fail(f"missing recorded trace {DATA}")
    return tracing.reduce(DATA)


def test_recorded_trace_planes_and_alignment(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    dev = recorded.devices[0]
    assert dev.aligned
    # 8 ticks x 4 queues, plus the drain's partial ticks
    assert len(dev.modules) >= 32
    assert all(n.startswith("jit_packet_step") for _, _, n in dev.modules)
    assert {n for _, _, n in recorded.spans} <= set(tracing.SPANS)


def test_recorded_trace_readings(recorded):
    w = recorded.window_s
    busy = recorded.busy_s()
    kernel = recorded.op_seconds(kernel_ns_per_pkt.match)
    assert 0 < kernel < busy < w
    assert recorded.top_ops(1)[0][0].startswith("%fused_forward")
    idle = dict(recorded.idle_by_span())
    assert sum(idle.values()) == pytest.approx(w - busy, rel=1e-6)
    assert max(idle, key=idle.get) == "tick"


@pytest.mark.parametrize("device_ids", [None, [0]])
def test_recorded_trace_reduces_as_before(device_ids):
    """The one-chip trace reads the numbers it read before chips were
    named: the same plane, shift and sums."""
    r = tracing.reduce(DATA, device_ids=device_ids)
    assert [d.name for d in r.devices] == ["/device:TPU:0"]
    assert r.devices[0].modules[0][:2] == (53519906.0, 53586448.0)
    assert r.window_s == pytest.approx(0.091586969, abs=1e-12)
    assert r.busy_s() == pytest.approx(0.00211702, abs=1e-12)
    assert r.op_seconds(kernel_ns_per_pkt.match) == pytest.approx(
        0.001281361, abs=1e-12)
    assert r.op_seconds(lambda t: not kernel_ns_per_pkt.match(t),
                        within="jit_packet_step") == pytest.approx(
        0.000910331, abs=1e-12)
    assert dict(r.idle_by_span()) == pytest.approx(
        {"tick": 0.07404474, "dispatch": 0.010557168,
         "generate": 0.004263422, "other": 0.000604619}, abs=1e-12)


@pytest.fixture(scope="module")
def four_chips():
    if not os.path.exists(DATA4):
        pytest.fail(f"missing recorded trace {DATA4}")
    return tracing.reduce(DATA4, device_ids=[0, 1, 2, 3])


def test_four_chip_trace_aligns_every_chip(four_chips):
    names = [d.name for d in four_chips.devices]
    assert names == [f"/device:TPU:{i}" for i in range(4)]
    assert all(d.aligned for d in four_chips.devices)
    # 4 ticks: the sharded step once on every chip, and on chip 0 also
    # the slicing of the tick's batch into the four shards
    steps = [[s for s, _, n in d.modules if n.startswith("jit_packet_step")]
             for d in four_chips.devices]
    assert [len(s) for s in steps] == [4, 4, 4, 4]
    assert [len(d.modules) for d in four_chips.devices] == [8, 4, 4, 4]
    # on the host's clock, each launch starts its shards on the four
    # chips within a millisecond of each other
    for tick in zip(*steps):
        assert max(tick) - min(tick) < 1e6
    kernel = four_chips.op_seconds(kernel_ns_per_pkt.match)
    assert 0 < kernel and 0 < four_chips.busy_s() < four_chips.window_s


def test_only_the_named_chips_are_read(four_chips):
    two = tracing.reduce(DATA4, device_ids=[1, 3])
    assert [d.name for d in two.devices] == ["/device:TPU:1", "/device:TPU:3"]
    assert all(d.aligned for d in two.devices)
    by_name = {d.name: d for d in four_chips.devices}
    for d in two.devices:
        assert d.ops == by_name[d.name].ops
    one = [tracing.Reduced(window=four_chips.window, spans=[], devices=[d])
           for d in two.devices]
    assert two.op_seconds(kernel_ns_per_pkt.match) == pytest.approx(
        sum(r.op_seconds(kernel_ns_per_pkt.match) for r in one))
    assert two.busy_s() == pytest.approx(
        sum(r.busy_s() for r in one) / 2)
