#!/usr/bin/env python3
"""Smoke test of the packet data plane on a TPU, at the paper's full width.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # only the four-chip queue-sharded phase

The model is the H32 BNN (8192 input bits, 32 hidden, 1 output) with 16
resident slots, random weights from a fixed seed.  One chip runs:

1. ``structure``: the compiled ``packet_step`` for the run's shapes holds
   the Pallas kernel (``tpu_custom_call``), so interpret mode did not run.
2. ``parity``: one 128-packet batch through the fused kernel and through
   the exact ``take`` path on the ``ref`` backend; slots, scores, verdicts
   and actions must be equal.
3. ``emergency``: ``repro.launch.dataplane`` with ``--slots 16 --queues 4
   --scenario emergency --audit`` (8,192 packets: flash crowd with
   monitor-only traffic, a queue failover, a ``SwapSlot`` epoch), then the
   same with ``--slot-cache 32 --prefetch`` (residency churn through the
   donated staging path).  Each needs zero wrong verdicts and a packet
   conservation audit that holds.
4. ``inference``: ``pipeline.inference_only`` on 256 packets of 1,024-byte
   payloads (the ``bnn_xnor`` kernel) against the ``ref`` backend.

``--chips 4`` runs the emergency trace through ``DataplaneRuntime`` with
``fanout="shard_map"`` over four chips and with ``fanout="loop"`` on one,
and requires equal slot and verdict streams and results spread over all
four chips.

Everything runs in this one process, which holds the chip.  The script
refuses any platform but a TPU and exits nonzero on any failure; its last
line on success is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
import traceback

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))
SLOTS, QUEUES, BATCH, BLOCK_B = 16, 4, 128, 32
EMERGENCY = ["--slots", str(SLOTS), "--queues", str(QUEUES),
             "--scenario", "emergency", "--audit"]


class CompileClock:
    """Seconds the backend spent compiling, from JAX's own events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def lap(self):
        return self.seconds, self.programs


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def structure_phase(bank):
    """The served step, compiled for this run's shapes, holds the kernel."""
    import jax.numpy as jnp
    from repro.core import packet as pkt, pipeline
    from repro.kernels import ops
    _check(ops._resolve("auto") == "pallas", "auto backend is not pallas")
    _check(not ops.interpret_mode(), "Pallas interpret mode is on")
    packets = jax.ShapeDtypeStruct((BATCH, pkt.PACKET_WORDS), jnp.uint32)
    hlo = pipeline.packet_step.lower(
        bank, packets, num_slots=SLOTS, strategy="fused",
        block_b=BLOCK_B).compile().as_text()
    _check("tpu_custom_call" in hlo, "no Pallas kernel in the served step")
    print("structure: packet_step(strategy=fused, backend=auto) -> pallas, "
          "compiled step holds tpu_custom_call, interpret=False")


def parity_phase(bank, rng):
    """Fused kernel against the exact take/ref path on one batch."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import packet as pkt, pipeline
    payload = rng.integers(0, 2**32, (BATCH, pkt.PAYLOAD_WORDS),
                           dtype=np.uint32)
    slots = rng.integers(0, SLOTS, BATCH)
    control = np.where(rng.random(BATCH) < 0.5, int(pkt.CTRL_MONITOR_ONLY), 0)
    p = pkt.make_packets(slots, payload)
    p[:, pkt.CONTROL_WORD_LO] = control.astype(np.uint32)
    p = jnp.asarray(p)
    got = pipeline.packet_step(bank, p, num_slots=SLOTS, strategy="fused",
                               block_b=BLOCK_B)
    want = pipeline.packet_step(bank, p, num_slots=SLOTS, strategy="take",
                                backend="ref")
    diff = float(np.max(np.abs(np.asarray(got.scores)
                               - np.asarray(want.scores))))
    for field in ("slots", "verdicts", "actions"):
        _check(np.array_equal(np.asarray(getattr(got, field)),
                              np.asarray(getattr(want, field))),
               f"fused {field} differ from take/ref")
    _check(diff == 0.0, f"fused scores differ from take/ref by {diff}")
    print(f"parity: {BATCH} packets, fused kernel == take/ref "
          f"(slots, verdicts, actions equal; max |score diff| = {diff}, "
          f"{int(np.asarray(got.verdicts).sum())} malicious)")


def emergency_phase(name, argv, clock):
    """One in-process run of the data-plane launcher."""
    from repro.launch import dataplane
    c0, n0 = clock.lap()
    t0 = time.perf_counter()
    print(f"--- {name}: python -m repro.launch.dataplane {' '.join(argv)}",
          flush=True)
    try:
        snap = dataplane.main(argv)
    except SystemExit as e:
        raise AssertionError(f"{name}: launcher exited {e.code}") from None
    c1, n1 = clock.lap()
    aud = snap["conservation"]
    tot = aud["totals"]
    print(f"{name}: packets={tot['offered']} completed={tot['completed']} "
          f"dropped={tot['dropped']} wrong_verdict={aud['wrong_verdict']} "
          f"conservation ok={aud['ok']} "
          f"backend={snap['backend']} compile_s={c1 - c0:.1f} "
          f"({n1 - n0} programs) wall_s={time.perf_counter() - t0:.1f}")
    _check(tot["offered"] == 8192, f"{name}: offered {tot['offered']}")
    _check(aud["ok"], f"{name}: conservation audit failed")
    _check(aud["wrong_verdict"] == 0, f"{name}: wrong verdicts")
    _check(snap["backend"] == "pallas", f"{name}: backend {snap['backend']}")
    _check(snap["slot_swaps"] >= 1, f"{name}: no SwapSlot committed")
    cache = snap.get("slot_cache")
    if cache is not None:
        print(f"{name}: slot_swaps={snap['slot_swaps']} "
              f"cache hits={cache['hits']} misses={cache['misses']} "
              f"evictions={cache['evictions']}")
        _check(cache["misses"] > 0 and cache["evictions"] > 0,
               f"{name}: residency did not churn")
    return snap


def inference_phase(rng):
    """``bnn_xnor`` on the chip: single-slot inference against ref."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import executor, packet as pkt, pipeline
    params = executor.init_params(jax.random.PRNGKey(1))
    x = jnp.asarray(rng.integers(0, 2**32, (256, pkt.PAYLOAD_WORDS),
                                 dtype=np.uint32))
    lowered = pipeline.inference_only.lower(params, x)
    _check("tpu_custom_call" in lowered.compile().as_text(),
           "no Pallas kernel in inference_only")
    got = np.asarray(pipeline.inference_only(params, x))
    want = np.asarray(pipeline.inference_only(params, x, backend="ref"))
    _check(got.shape == (256, 1) and np.isfinite(got).all(),
           f"inference_only output {got.shape}")
    _check(np.array_equal(got, want), "inference_only differs from ref: "
           f"max {np.max(np.abs(got - want))}")
    print(f"inference: 256 x 1024 B payloads through bnn_xnor == ref "
          f"(bit-identical), {int((got[:, 0] > 0).sum())} malicious")


def one_chip(clock):
    import numpy as np
    from repro.core import executor
    bank = executor.init_bank(jax.random.PRNGKey(0), SLOTS)
    rng = np.random.default_rng(0)
    structure_phase(bank)
    parity_phase(bank, rng)
    emergency_phase("emergency", EMERGENCY, clock)
    emergency_phase("slot-churn", EMERGENCY + ["--slot-cache", "32",
                                               "--prefetch"], clock)
    inference_phase(rng)


def four_chips(clock):
    """The emergency trace over four chips (shard_map) against one (loop)."""
    import jax.numpy as jnp
    from repro.core import executor, packet as pkt
    from repro.dataplane import DataplaneRuntime, workloads
    _check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    bank = executor.init_bank(jax.random.PRNGKey(0), SLOTS)
    wl = workloads.make_workload("emergency", num_slots=SLOTS,
                                 num_queues=QUEUES)
    trace = workloads.render(list(wl.phases), num_slots=SLOTS, seed=0,
                             num_queues=QUEUES)
    streams = {}
    for fanout in ("loop", "shard_map"):
        c0, _ = clock.lap()
        rt = DataplaneRuntime(bank, num_queues=QUEUES, fanout=fanout,
                              batch=BATCH, ring_capacity=1024, audit=True,
                              record=True)
        workloads.play(rt, trace)
        snap = rt.snapshot()
        aud = snap["conservation"]
        print(f"{fanout}: packets={aud['totals']['offered']} "
              f"completed={aud['totals']['completed']} "
              f"wrong_verdict={aud['wrong_verdict']} "
              f"conservation ok={aud['ok']} "
              f"backend={snap['backend']} "
              f"compile_s={clock.lap()[0] - c0:.1f}")
        _check(aud["ok"] and aud["wrong_verdict"] == 0,
               f"{fanout}: audit failed")
        streams[fanout] = (rt.completed_seq, rt.completed_slots,
                           rt.completed_verdicts)
        if fanout == "shard_map":
            probe = rt._step(rt.bank, jnp.zeros(
                (QUEUES, BATCH, pkt.PACKET_WORDS), jnp.uint32))
            placed = {s.device for s in probe.addressable_shards}
            _check(placed == set(jax.devices()),
                   f"shard_map results on {len(placed)} device(s)")
            print(f"shard_map: results sharded over {len(placed)} devices "
                  f"({probe.sharding})")
    n = sum(len(q) for q in streams["loop"][0])
    _check(streams["loop"] == streams["shard_map"],
           "slot/verdict streams differ between loop and shard_map")
    mal = sum(sum(q) for q in streams["loop"][2])
    digest = hashlib.sha256(repr(streams["loop"]).encode()).hexdigest()
    print(f"mesh: {n} packets, slot and verdict streams equal "
          f"(loop on 1 chip == shard_map on 4), {mal} malicious, "
          f"sha256={digest[:16]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip queue-sharded phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={device['platform']} "
          f"device_kind={device['kind']} count={device['count']}",
          flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; refusing to run elsewhere",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.cache import enable_compile_cache
        print(f"compile cache: {enable_compile_cache()}")
        clock = CompileClock()
        (four_chips if args.chips == 4 else one_chip)(clock)
        print(f"compile: {clock.programs} programs, "
              f"{clock.seconds:.1f} s in the backend compiler")
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
