"""Fig. 8 (repo extension) — multi-queue data-plane structural audits.

Runs the emergency scenario (steady -> flash crowd -> link failover ->
slot churn) through the multi-queue runtime and checks three hard
structural properties:

  * **one fused launch per queue-block** — the traced per-queue program
    (backend pinned to pallas) contains exactly ONE ``pallas_call``;
  * **packet conservation** — ``offered == completed + dropped`` per
    queue and in aggregate across every scenario phase (flash crowd is
    sized to force real tail-drops, so the dropped leg is non-trivial);
  * **swap continuity** — zero wrong-verdict packets while the slot-churn
    phase replaces a resident slot online (audit mode re-scores every
    tick through the exact ``take`` path).

Run standalone with ``--json BENCH_2.json`` for the machine-readable
map, or through ``python -m benchmarks.run --only fig8``.
"""

from __future__ import annotations

import os
import sys

if __package__ in (None, ""):  # invoked as `python benchmarks/fig8_dataplane.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(1, os.path.join(_root, "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, jaxpr_stats, standalone_json_main
from repro.core import executor, packet as pkt, pipeline
from repro.dataplane import (DataplaneRuntime, emergency_phases, play, render)

NUM_SLOTS = 4
BATCH = 128
BLOCK_B = 32


def main():
    bank = executor.init_bank(jax.random.PRNGKey(0), NUM_SLOTS)
    trace = render(emergency_phases(NUM_SLOTS), num_slots=NUM_SLOTS, seed=0)

    # -- structural audit: ONE fused launch per queue-block ---------------
    qpackets = jnp.asarray(pkt.make_packets(
        np.arange(BATCH) % NUM_SLOTS,
        np.random.default_rng(0).integers(
            0, 2**32, (BATCH, pkt.PAYLOAD_WORDS), dtype=np.uint32)))

    def queue_block_step(p):
        return pipeline.packet_step(
            bank, p, num_slots=NUM_SLOTS, strategy="fused",
            backend="pallas", block_b=BLOCK_B)

    stats = jaxpr_stats(
        queue_block_step, qpackets,
        payload_threshold=BATCH * pkt.PAYLOAD_WORDS * 4)
    emit("fig8.audit.launches_per_queue_block",
         stats["kernel_launches"], "expect=1")
    emit("fig8.audit.payload_roundtrip_bytes",
         stats["payload_roundtrip_bytes"], "expect=0")
    assert stats["kernel_launches"] == 1, stats
    assert stats["payload_roundtrip_bytes"] == 0, stats

    # -- conservation under backpressure + swap continuity ----------------
    # small rings force real tail-drops during the flash crowd; audit mode
    # cross-checks every verdict against the exact path, including across
    # the online slot swap in the slot_churn phase.
    rt = DataplaneRuntime(
        bank, num_queues=4, strategy="fused", batch=BATCH, block_b=BLOCK_B,
        ring_capacity=512, audit=True)
    reports = play(rt, trace)
    aud = rt.audit_conservation()
    assert aud["ok"], aud
    t = aud["totals"]
    assert t["offered"] == t["completed"] + t["dropped"], t
    assert t["offered"] == trace.total_packets, t
    crowd = next(r for r in reports if r["phase"] == "flash_crowd")
    emit("fig8.audit.flash_crowd_dropped", crowd["dropped"],
         "counted tail-drops under backpressure")
    emit("fig8.audit.wrong_verdict_during_swap", aud["wrong_verdict"],
         "expect=0 across online slot swap")
    assert crowd["dropped"] > 0, crowd
    assert aud["wrong_verdict"] == 0, aud


if __name__ == "__main__":
    standalone_json_main(main, __doc__)
