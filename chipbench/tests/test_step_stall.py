"""The reader of ``step_stall_ns_per_pkt``: time a served step's module is
open on a chip with none of that chip's ops running.

Hand-made intervals check the arithmetic; the four-chip trace recorded
on a TPU v5e (four ticks of 16 queues over four chips, ``data/``) checks
it against a count made module by module, and against the numbers that
count gave once.
"""

import os
import types

import pytest

from chipbench import tracing
from chipbench.metrics import step_stall_ns_per_pkt as stall

DATA4 = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tick_trace_4chips.xplane.pb")


def _ctx(devices, window=(0, 1000), retired=10):
    trace = tracing.Reduced(window=window, spans=[], devices=devices)
    return types.SimpleNamespace(trace=trace, retired_in_window=retired)


def _dev(ops, modules, name="/device:TPU:0"):
    return tracing.Device(name=name, ops=ops, modules=modules, aligned=True)


def test_open_step_with_no_op_running():
    # step [10, 100]: ops cover [30, 50] (nested) and [90, 100] of it
    d = _dev(ops=[(30, 40, "a"), (35, 50, "b"), (90, 120, "c"),
                  (200, 260, "d")],
             modules=[(10, 100, "jit_packet_step_queues(1)"),
                      (150, 300, "jit__multi_slice(2)")])
    assert stall.stall_ns(d, (0, 1000)) == 90 - 20 - 10
    # the window clips the module: [60, 100] less [90, 100]
    assert stall.stall_ns(d, (60, 1000)) == 30
    # chips sum; the reading is per timed packet retired
    d2 = _dev(ops=[], modules=[(0, 40, "jit_packet_step_queues(1)")],
              name="/device:TPU:1")
    assert stall.read(_ctx([d, d2], retired=10)) == pytest.approx(
        (60 + 40) / 10)


def test_nothing_to_read():
    d = _dev(ops=[(0, 5, "a")], modules=[(0, 10, "jit_other(3)")])
    assert stall.stall_ns(d, (0, 100)) is None
    assert stall.read(_ctx([d])) is None
    assert stall.read(types.SimpleNamespace(trace=None,
                                            retired_in_window=5)) is None
    assert stall.read(_ctx([], retired=0)) is None


def _by_module(device, window) -> float:
    """The hand count: each step module's length less the union of the
    ops clipped to it."""
    total = 0
    for s, e, name in tracing._clip(device.modules, window):
        if not name.startswith("jit_packet_step"):
            continue
        inside = [(max(a, s), min(b, e), t) for a, b, t in device.ops
                  if a < e and b > s]
        total += (e - s) - sum(b - a for a, b in tracing._union(inside))
    return total


def test_recorded_four_chip_stall():
    if not os.path.exists(DATA4):
        pytest.fail(f"missing recorded trace {DATA4}")
    r = tracing.reduce(DATA4, device_ids=[0, 1, 2, 3])
    per_chip = [stall.stall_ns(d, r.window) for d in r.devices]
    assert per_chip == [_by_module(d, r.window) for d in r.devices]
    # read once by hand from this trace: chip 0, which first stages the
    # whole batch and slices it to the others, waits longest
    assert per_chip == [1345712.0, 446318.0, 66606.0, 374796.0]
    assert max(per_chip) == per_chip[0]
    assert sum(per_chip) == pytest.approx(2.2e6, rel=0.02)
    ctx = types.SimpleNamespace(trace=r, retired_in_window=4 * 2048)
    assert stall.read(ctx) == pytest.approx(sum(per_chip) / (4 * 2048))
    # every chip's best step still waits ~3 us for its first op
    assert all(v > 0 for v in per_chip)
