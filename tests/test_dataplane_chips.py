"""Sixteen queues over four of eight devices: the ``shard_map`` fan-out
with ``devices=`` serves the streams of the flat launch and of the exact
``take`` path through a swap and a failover, keeps the bank and the
results on exactly those devices, and makes no implicit transfer at a
launch.  Each case runs in a subprocess with eight virtual CPU devices
(XLA reads the device count when JAX starts), as
``tests/test_distributed.py`` does."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {src!r})
import json
import jax, numpy as np
from repro.control import SwapSlot
from repro.core import executor, packet as pkt
from repro.dataplane import DataplaneRuntime, Phase, play, render
from repro.dataplane.workloads.phases import default_swap_delivery
from repro.launch import mesh as mesh_lib

devs = jax.devices()
assert len(devs) == 8, devs
bank = executor.init_bank(jax.random.PRNGKey({seed}), 4)


def ids(sharding):
    return sorted(d.id for d in sharding.device_set)


def bank_ids(rt):
    leaves = jax.tree_util.tree_leaves(rt.bank)
    assert all(l.sharding.is_fully_replicated for l in leaves)
    return sorted({{i for l in leaves for i in ids(l.sharding)}})
"""


def _run(body: str, seed: int) -> dict:
    script = (_PRELUDE.format(src=SRC, seed=seed)
              + textwrap.dedent(body))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_STREAMS = """
uniform = (0.25,) * 4
trace = render([
    Phase("steady", ticks=3, burst=384, flows=64, slot_mix=uniform),
    Phase("churn", ticks=3, burst=384, flows=64, slot_mix=uniform,
          failed_queues=(0, 5, 9), swap_slot=2),
], num_slots=4, seed={seed})
kw = dict(num_queues=16, batch=32, ring_capacity=4096, record=True,
          backend={backend!r})
runs = {{}}
for name, over in [("sharded", dict(fanout="shard_map", devices=devs[:4])),
                   ("flat", {{}}), ("take", dict(strategy="take"))]:
    rt = DataplaneRuntime(bank, **dict(kw, **over))
    play(rt, trace)
    runs[name] = rt
sh = runs["sharded"]
print(json.dumps({{
    "streams": {{name: [rt.completed_seq, rt.completed_slots,
                        rt.completed_verdicts] for name, rt in runs.items()}},
    "fanouts": {{name: rt.fanout for name, rt in runs.items()}},
    "swaps": sh.telemetry.slot_swaps, "failed": sorted(sh.failed_queues),
    "partial": any(0 < len(s) % 32 for s in sh.completed_seq),
    "served": sum(len(s) for s in sh.completed_seq),
    "offered": trace.total_packets,
    "bank_devices": bank_ids(sh),
}}))
"""


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_sharded_streams_match_flat_and_take(backend):
    """Per queue, the sharded launch over ``devices[:4]`` gives the
    sequence numbers, slots and verdicts of the flat one-device launch
    and of the exact ``take`` path, through a ``SwapSlot`` epoch and a
    ``FailQueues`` failover (the Pallas kernel in interpret mode)."""
    got = _run(_STREAMS.format(seed=17, backend=backend), seed=3)
    assert got["fanouts"] == {"sharded": "shard_map", "flat": "vmap",
                              "take": "vmap"}
    assert got["swaps"] == 1 and got["failed"] == [0, 5, 9]
    assert got["partial"]
    assert got["served"] == got["offered"]
    streams = got["streams"]
    assert streams["sharded"] == streams["flat"] == streams["take"]
    assert got["bank_devices"] == [0, 1, 2, 3]


_WINDOW = """
uniform = (0.25,) * 4
# 96 rows a queue a tick against a batch of 32: the rings stay backlogged
trace = render([
    Phase("surge", ticks=4, burst=1536, flows=64, slot_mix=uniform),
    Phase("churn", ticks=2, burst=1536, flows=64, slot_mix=uniform,
          swap_slot=2),
], num_slots=4, seed={seed})
out = {{}}
for depth in (1, None):
    rt = DataplaneRuntime(bank, num_queues=16, batch=32, ring_capacity=4096,
                          record=True, backend="ref", fanout="shard_map",
                          devices=devs[:4], pipeline_depth=depth)
    rt.spans.enable()
    play(rt, trace)
    out[str(depth)] = {{
        "streams": [rt.completed_seq, rt.completed_slots,
                    rt.completed_verdicts],
        "deferred": rt.spans.counters.get("dp.retire.deferred", 0),
        "served": sum(len(s) for s in rt.completed_seq),
        "ok": rt.audit_conservation()["ok"]}}
out["offered"] = trace.total_packets
print(json.dumps(out))
"""


def test_sharded_derived_window_matches_synchronous():
    """Under a backlogged stream the derived in-flight window
    (``pipeline_depth=None``) defers ticks over the four-chip mesh and
    serves the same seqs, slots and verdicts as ``pipeline_depth=1``."""
    got = _run(_WINDOW.format(seed=23), seed=6)
    sync, derived = got["1"], got["None"]
    assert sync["ok"] and derived["ok"]
    assert sync["served"] == derived["served"] == got["offered"]
    assert sync["deferred"] == 0 and derived["deferred"] > 0
    assert derived["streams"] == sync["streams"]


_PLACEMENT = """
B = 128
rng = np.random.default_rng({seed})


def burst():
    n = 16 * B
    rows = pkt.make_packets(
        rng.integers(0, 4, n),
        rng.integers(0, 2**32, (n, pkt.PAYLOAD_WORDS), dtype=np.uint32))
    return rows, np.arange(n) % 16


out = {{}}
rt = DataplaneRuntime(bank, num_queues=16, batch=B, fanout="shard_map",
                      devices=devs[:4])
out["puts_at_construction"] = rt._bankbuf.puts
out["bank_before"] = bank_ids(rt)
seen = []
step = rt._step


def guarded(bank_, x):
    # a launch whose inputs are not already where the step wants them
    # (the bank replicated, the batch in per-chip shards) would move
    # them implicitly, which the guard refuses
    with jax.transfer_guard("disallow"):
        y = step(bank_, x)
    seen.append((ids(x.sharding), x.sharding == rt._batch_sharding,
                 ids(y.sharding)))
    return y


rt._step = guarded
rt.spans.enable()
h2d, puts = [], []
for t in range(20):
    rows, qs = burst()
    rt.dispatch(rows, queues=qs)
    before = rt.spans.counters.get("dp.h2d_bytes", 0)
    rt.tick()
    h2d.append(rt.spans.counters["dp.h2d_bytes"] - before)
    puts.append(rt.spans.counters.get("dp.bank_puts", 0))
out["h2d_per_tick"] = sorted(set(h2d))
out["puts_over_20_ticks"] = sorted(set(puts))
rt.control.submit(SwapSlot(1, default_swap_delivery(1)))
out["puts_after_submit"] = rt.spans.counters.get("dp.bank_puts", 0)
rows, qs = burst()
rt.dispatch(rows, queues=qs)
rt.tick()
rt.control.submit(SwapSlot(3, default_swap_delivery(3)))
for t in range(20):
    rows, qs = burst()
    rt.dispatch(rows, queues=qs)
    rt.tick()
out["puts_after_two_swaps"] = rt.spans.counters.get("dp.bank_puts", 0)
out["swaps"] = rt.telemetry.slot_swaps
out["bank_after"] = bank_ids(rt)
out["launches"] = sorted({{(tuple(a), b, tuple(c)) for a, b, c in seen}})
out["kernel_rows"] = rt.spans.counters["dp.kernel_rows"]
out["ticks"] = rt.spans.snapshot()["spans"]["dp.tick.launch"]["count"]

refused = []
for make in (lambda: mesh_lib.make_queue_mesh(16, devs[:3]),
             lambda: DataplaneRuntime(bank, num_queues=16,
                                      fanout="shard_map", devices=devs[:3]),
             lambda: DataplaneRuntime(bank, num_queues=16, devices=devs[:4])):
    try:
        make()
        refused.append(False)
    except ValueError:
        refused.append(True)
out["refused"] = refused
default = DataplaneRuntime(bank, num_queues=16, fanout="shard_map")
today, axis = mesh_lib.make_queue_mesh(16)
out["default_mesh"] = [default._mesh == today, default._axis == axis,
                       default._mesh.devices.size]
out["default_bank"] = bank_ids(default)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def placement():
    return _run(_PLACEMENT.format(seed=5), seed=4)


def test_sharded_launch_stays_on_its_devices(placement):
    """The batch goes into per-chip shards on ``devices[:4]``, the packed
    result lies on exactly those four and on none of the other four, and
    after two swaps the active bank is still replicated on them."""
    assert placement["bank_before"] == [0, 1, 2, 3]
    assert placement["bank_after"] == [0, 1, 2, 3]
    assert placement["swaps"] == 2
    assert placement["launches"] == [[[0, 1, 2, 3], True, [0, 1, 2, 3]]]


def test_sharded_ticks_move_no_bank_bytes(placement):
    """Bank bytes go to the chips at construction (both buffers) and once
    per staged slot, never at a tick; each tick puts exactly its padded
    (16, 128, 272) batch, and every launch ran under a transfer guard."""
    assert placement["puts_at_construction"] == 2
    assert placement["puts_over_20_ticks"] == [0]
    assert placement["h2d_per_tick"] == [16 * 128 * 1088]
    assert placement["puts_after_submit"] == 1
    assert placement["puts_after_two_swaps"] == 2
    assert placement["ticks"] == 41
    # on each of 4 chips, 4 queues' 512 rows padded by slot over 4 slots
    # in 32-row blocks: 640 rows
    assert placement["kernel_rows"] == 41 * 4 * 640


def test_device_lists_that_cannot_split_the_queues_are_refused(placement):
    """Three devices for 16 queues are refused by the mesh and by the
    runtime; ``devices=`` without ``shard_map`` is refused; without
    ``devices`` the sharded runtime builds today's mesh over all eight."""
    assert placement["refused"] == [True, True, True]
    assert placement["default_mesh"] == [True, True, 8]
    assert placement["default_bank"] == list(range(8))
