"""The reading of the program's own ``dp.`` host spans: hand-made
intervals for the idle split, a hand-made snapshot for the readers."""

import types

import pytest

from chipbench import cell, program_spans, tracing


def _reduced(ops, window=(0, 100)):
    dev = tracing.Device(name="/device:TPU:0", ops=ops, modules=[],
                         aligned=True)
    return tracing.Reduced(window=window, spans=[], devices=[dev])


def test_innermost_splits_nested_spans():
    events = [(0, 80, "dp.tick"), (10, 20, "dp.tick.pop"),
              (30, 70, "dp.retire.wait"), (40, 50, "dp.inner")]
    assert program_spans.innermost(events) == [
        (0, 10, "dp.tick"), (10, 20, "dp.tick.pop"), (20, 30, "dp.tick"),
        (30, 40, "dp.retire.wait"), (40, 50, "dp.inner"),
        (50, 70, "dp.retire.wait"), (70, 80, "dp.tick")]


def test_idle_goes_to_the_innermost_open_span():
    # device busy [5, 15] and [60, 65]; host: tick [0, 80] holding
    # pop [10, 30] and wait [40, 70]; nothing open after 80
    r = _reduced([(5, 15, "k"), (60, 65, "k")])
    events = [(0, 80, "dp.tick"), (10, 30, "dp.tick.pop"),
              (40, 70, "dp.retire.wait")]
    got = {k: v * 1e9 for k, v in
           program_spans.idle_by_program_span(r, events)}
    # gaps [0,5] tick; [15,60] = pop 15 + tick 10 + wait 20;
    # [65,100] = wait 5 + tick 10 + other 20
    assert got == pytest.approx({"dp.tick": 25, "dp.tick.pop": 15,
                                 "dp.retire.wait": 25, "other": 20})
    assert sum(got.values()) == pytest.approx(100 - 15)


def test_idle_with_no_span_open_is_other():
    r = _reduced([(40, 60, "k")])
    got = dict(program_spans.idle_by_program_span(r, []))
    assert got == pytest.approx({"other": 80e-9})


def _ctx(program, retired=100):
    return types.SimpleNamespace(program=program, retired_in_window=retired,
                                 spans={"tick": (1e-6 * 3, 2)})


SNAP = {"spans": {
    "dp.tick": {"count": 2, "total_ns": 3000, "self_ns": 100, "max_ns": 2000},
    "dp.tick.control": {"count": 2, "total_ns": 50, "self_ns": 50, "max_ns": 30},
    "dp.tick.pop": {"count": 2, "total_ns": 150, "self_ns": 150, "max_ns": 90},
    "dp.tick.pad": {"count": 8, "total_ns": 100, "self_ns": 100, "max_ns": 20},
    "dp.tick.h2d": {"count": 8, "total_ns": 400, "self_ns": 400, "max_ns": 60},
    "dp.tick.launch": {"count": 8, "total_ns": 800, "self_ns": 800, "max_ns": 110},
    "dp.retire.wait": {"count": 2, "total_ns": 900, "self_ns": 900, "max_ns": 500},
    "dp.retire.d2h": {"count": 8, "total_ns": 200, "self_ns": 200, "max_ns": 30},
    "dp.retire.tap": {"count": 8, "total_ns": 120, "self_ns": 120, "max_ns": 20},
    "dp.retire.telemetry": {"count": 8, "total_ns": 180, "self_ns": 180,
                            "max_ns": 30}},
    "counters": {"dp.rows_popped": 100, "dp.ring_wait_ns": 5_000_000,
                 "dp.kernel_rows": 500},
    "slowest_ticks": []}


def test_readers_of_the_program_spans():
    ctx = _ctx(SNAP)
    got = {m: cell.reader(m).read(ctx) for m in program_spans.METRICS}
    assert got == pytest.approx({
        "tick_prep_ns_per_pkt.tput": 3.0, "h2d_ns_per_pkt.tput": 4.0,
        "launch_ns_per_pkt.tput": 8.0, "device_wait_ns_per_pkt.tput": 9.0,
        "d2h_ns_per_pkt.tput": 2.0, "retire_host_ns_per_pkt.tput": 3.0,
        "ring_wait_us.tput": 50.0, "kernel_rows_per_pkt.tput": 5.0})
    # the six tick phases and the tick's own self time make its total
    six = sum(got[m] for m in program_spans.TICK_PHASES)
    assert six + 100 / 100 == pytest.approx(3000 / 100)


@pytest.mark.parametrize("program", [None, {"spans": {}, "counters": {},
                                            "slowest_ticks": []}])
def test_readers_read_nothing_without_the_spans(program):
    ctx = _ctx(program)
    for m in program_spans.METRICS:
        assert cell.reader(m).read(ctx) is None, m
    bare = types.SimpleNamespace(retired_in_window=100)
    assert cell.reader(program_spans.METRICS[0]).read(bare) is None


def test_rehearsal_prints_the_program_spans():
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(cell.ROOT, "chipbench",
                                      "program_spans.py"),
         "--workload", "h32-k16-q4.saturate", "--seed", str(2**31 + 9),
         "--seconds", "0.3", "--trace", "1", "--rehearse"],
        cwd=cell.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["result"]["correct"] is True
    prog = out["program"]
    assert set(program_spans.METRICS) <= set(prog["metrics"])
    # one launch a tick: 512 rows of 16 slots pad to 1,024 kernel rows
    assert prog["metrics"]["kernel_rows_per_pkt.tput"] == 2.0
    assert 0.5 < prog["tick_split"]["over_harness_tick"] <= 1.0
    assert prog["slowest_ticks"] and "dp.tick" in prog["slowest_ticks"][0][
        "self_us"]
    assert out["idle_by_program_span"] == []     # the CPU has no device plane
