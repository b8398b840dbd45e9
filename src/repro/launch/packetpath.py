"""The paper's end-to-end driver: the BoundSwitch packet path.

Trains the two slot models (recall / precision oriented) on the synthetic
IoT-23-like workload, preloads them into the resident bank, and replays a
boundary stream through the shared forwarding pipeline — reporting the
paper's headline metrics (throughput, selection cost, continuity).

The default strategy is ``fused`` — the one-launch Pallas megakernel is
the hot path (PR 1); the exact per-row ``take`` baseline stays reachable
via ``--strategy take``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bank as bank_lib
from repro.core import packet as pkt
from repro.core import pipeline, switching
from repro.data import packets as pk
from repro.launch.cache import enable_compile_cache
from repro.train import bnn


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--packets", type=int, default=8192)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--samples-per-group", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--strategy", default="fused",
                    choices=["take", "onehot", "grouped", "grouped_staged",
                             "fused"],
                    help="fused (default) runs the one-launch megakernel "
                         "hot path; take is the exact per-row baseline")
    ap.add_argument("--stream", action="store_true",
                    help="streaming replay: async dispatch with a bounded "
                         "in-flight window instead of per-batch blocking")
    args = ap.parse_args()

    print("== training resident slot models (STE, pos_weight 4.0 / 0.5) ==")
    slot0, slot1 = bnn.train_slot_pair(
        epochs=args.epochs, samples_per_group=args.samples_per_group)
    bank = bank_lib.stack_bank([slot0, slot1])
    print(f"resident bank: 2 slots, {bank_lib.bank_bytes(bank)} bytes")

    xb, yb = pk.load_split("val", 1024, 0)
    w = pk.to_payload_words(xb)
    for name, slot in (("slot0", slot0), ("slot1", slot1)):
        m = bnn.evaluate(slot, w, yb)
        print(f"{name}: precision={m['precision']:.3f} recall={m['recall']:.3f} "
              f"f1={m['f1']:.3f}")

    print("== boundary replay ==")
    payload = w[np.arange(args.packets) % w.shape[0]]
    trace = switching.boundary_trace(args.packets, payload)
    t0 = time.perf_counter()
    res = pipeline.packet_step(
        bank, jnp.asarray(trace), num_slots=2, strategy=args.strategy)
    res.scores.block_until_ready()
    # batched-throughput measurement
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        pipeline.packet_step(
            bank, jnp.asarray(trace), num_slots=2, strategy=args.strategy
        ).scores.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    mpps = args.packets / dt / 1e6
    print(f"batched pipeline: {mpps:.3f} Mpps ({dt/args.packets*1e6:.3f} us/pkt), "
          f"{mpps * pkt.PAYLOAD_BYTES * 8 / 1e3:.2f} Gbps @1024B payload")

    rr = switching.replay_trace(bank, trace[:1024], num_slots=2,
                                strategy=args.strategy, stream=args.stream)
    g = rr.gap_stats_us()
    k = rr.rate_kpps()
    print(f"per-packet replay: wrong_slot={rr.wrong_slot} "
          f"wrong_verdict={rr.wrong_verdict} "
          f"median_gap={g['median_gap_us']:.2f}us boundary_gap={g['boundary_gap_us']:.2f}us "
          f"rate before/after boundary: {k['before_kpps']:.1f}/{k['after_kpps']:.1f} kpps")


if __name__ == "__main__":
    main()
