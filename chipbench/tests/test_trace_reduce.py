"""The reduction from a profiler trace to metrics.

Hand-made intervals check the arithmetic; a trace of eight ticks of the
``h32-k16-q4.saturate`` cell, recorded on a TPU v5e by
``record_trace.py`` and kept in ``data/``, checks the reading of a real
trace against numbers read from it once by hand.
"""

import os

import pytest

from chipbench import tracing
from chipbench.metrics import kernel_ns_per_pkt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tick_trace.xplane.pb")


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
