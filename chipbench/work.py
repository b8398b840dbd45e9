"""Operations and bytes of the served path, counted from the algorithm's
shapes -- never from what implements it -- so a roofline share or
``step_mfu`` reads the same work whether layer 1 runs as a VPU popcount,
an MXU +-1 matmul or something deduplicated.

* Ops per packet: ``2 d_bits hidden + 2 hidden n_out``; each +-1 or f32
  multiply-accumulate counts as 2 (524,352 for H32).  The int8 peak is the
  compute roof, since +-1 operands are exact in int8.
* Bytes per tick: the real packets read (1,088 B each), each distinct
  slot's weights read once, and each packet's score and action written
  (8 B).  A tick is the unit the algorithm serves at once, so the count
  does not change with how many launches the program splits it into.
* Chips: ``least_time`` is one chip's.  A tick split over N chips, each
  reading its own packets and the weights of the slots they use, has at
  best a least time of ``least_time / N`` (its weights read once, not on
  every chip), which is the roof ``kernel_roofline`` divides by once it
  sums the kernel's time over the N chips.
"""

from __future__ import annotations

#: f32 score + i32 action written per packet.
OUT_BYTES = 8


def ops_per_packet(cfg: dict) -> int:
    return 2 * cfg["d_bits"] * cfg["hidden"] + 2 * cfg["hidden"] * cfg["n_out"]


def slot_bytes(cfg: dict) -> int:
    """One slot model: packed layer-1 bits, b1, w2 and b2 (f32)."""
    h, c = cfg["hidden"], cfg["n_out"]
    return h * cfg["d_bits"] // 8 + 4 * (h + c * h + c)


def tick_bytes(cfg: dict, packets: int, distinct_slots: int) -> int:
    return (packets * (cfg["packet_bytes"] + OUT_BYTES)
            + distinct_slots * slot_bytes(cfg))


def least_time(cfg: dict, peaks: dict, ticks) -> tuple[float, str]:
    """Least seconds the chip needs for ``ticks`` -- an iterable of
    ``(packets, distinct_slots)``, one per tick -- as the sum over ticks
    of ``max(ops / int8 peak, bytes / HBM bandwidth)``, and which roof
    bounds most of that time (``compute`` or ``memory``)."""
    total = by_compute = 0.0
    for n, k in ticks:
        t_ops = n * ops_per_packet(cfg) / peaks["int8_ops_per_s"]
        t_mem = tick_bytes(cfg, n, k) / peaks["hbm_bytes_per_s"]
        total += max(t_ops, t_mem)
        by_compute += t_ops if t_ops >= t_mem else 0.0
    return total, ("compute" if by_compute * 2 > total else "memory")
