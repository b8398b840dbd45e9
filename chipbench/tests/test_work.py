"""Work and peak arithmetic against values worked out by hand for H32."""

import pytest

from chipbench import peaks, work

H32 = {"d_bits": 8192, "hidden": 32, "n_out": 1, "packet_bytes": 1088}
V5E = peaks.for_kind("TPU v5 lite")


def test_ops_per_packet_h32():
    # 2 * 8192 * 32 (layer 1) + 2 * 32 * 1 (layer 2)
    assert work.ops_per_packet(H32) == 524_352


def test_slot_bytes_h32():
    # 32 x 8192 bits = 32,768 B, plus b1 (32), w2 (32) and b2 (1) in f32
    assert work.slot_bytes(H32) == 32_768 + 4 * 65 == 33_028


def test_tick_bytes_16_slots():
    # 128 packets of 1,088 B in, 8 B out each, 16 slot models read once
    assert work.tick_bytes(H32, 128, 16) == 128 * 1096 + 16 * 33_028 == 668_736


def test_per_packet_roofs_are_nearly_equal():
    t_ops = work.ops_per_packet(H32) / V5E["int8_ops_per_s"]
    t_mem = H32["packet_bytes"] / V5E["hbm_bytes_per_s"]
    assert t_ops == pytest.approx(1.3342e-9, rel=1e-3)
    assert t_mem == pytest.approx(1.3284e-9, rel=1e-3)


def test_least_time_memory_bound_with_16_slots():
    least, bound = work.least_time(H32, V5E, [(128, 16), (128, 16)])
    assert least == pytest.approx(2 * 668_736 / 819e9)
    assert bound == "memory"


def test_h32_stays_memory_bound_at_any_batch():
    # with its 8 B of output a packet moves 1,096 B: 1.338 ns > 1.334 ns
    n = 100_000
    least, bound = work.least_time(H32, V5E, [(n, 1)])
    assert least == pytest.approx((n * 1096 + 33_028) / 819e9)
    assert bound == "memory"


def test_least_time_compute_bound_for_a_wider_hidden_layer():
    h64 = dict(H32, hidden=64)
    n = 100_000
    least, bound = work.least_time(h64, V5E, [(n, 1)])
    assert least == pytest.approx(n * (2 * 8192 * 64 + 128) / 393e12)
    assert bound == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
