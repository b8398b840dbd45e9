"""Fused forwarding megakernel: bit-exact parity vs the ref oracle across
slot counts, ragged traces, and both input modes; streaming replay
regression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bank as bank_lib
from repro.core import executor, packet as pkt, pipeline, switching
from repro.kernels import fused_forward as ff
from repro.kernels import ops, ref

CFG = executor.BNNConfig(d_bits=64 * 32, hidden=16, n_out=1)  # small h16


def _bank(num_slots):
    return executor.init_bank(jax.random.PRNGKey(7), num_slots, CFG)


def _payload(rng, b, words=CFG.d_bits // 32):
    return jnp.asarray(rng.integers(0, 2**32, (b, words), dtype=np.uint32))


@pytest.mark.parametrize("num_slots", [1, 4, 16])
def test_fused_gather_bit_exact_vs_oracle(num_slots):
    """interpret=True kernel output == pure-jnp oracle, bit for bit."""
    rng = np.random.default_rng(num_slots)
    bank = _bank(num_slots)
    b, bb = 48, 8
    x = _payload(rng, b)
    slots = jnp.asarray(rng.integers(0, num_slots, b), jnp.int32)
    g = bank_lib.group_by_slot_padded(slots, num_slots, bb)

    got = ops.bnn_forward_fused(bank, x, g.block_slots, g.row_ids,
                                block_b=bb, backend="pallas")
    want = ops.bnn_forward_fused(bank, x, g.block_slots, g.row_ids,
                                 block_b=bb, backend="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the de-padded result matches the per-row oracle exactly
    back = np.asarray(jnp.take(got, g.result_rows, axis=0))
    oracle = ref.banked_xnor_forward_ref(
        bank["w1p"], bank["b1"], bank["w2"], bank["b2"], x, slots)
    np.testing.assert_array_equal(back, np.asarray(oracle))


@pytest.mark.parametrize("kind", ["hotspot", "random", "round_robin"])
def test_fused_ragged_traces(kind):
    """Ragged slot distributions from the paper's access traces."""
    num_slots, b, bb = 8, 64, 8
    bank = _bank(num_slots)
    rng = np.random.default_rng(3)
    x = _payload(rng, b)
    slots = jnp.asarray(
        switching.access_trace(kind, b, num_slots, seed=1), jnp.int32)
    g = bank_lib.group_by_slot_padded(slots, num_slots, bb)
    got = ops.bnn_forward_fused(bank, x, g.block_slots, g.row_ids,
                                block_b=bb, backend="pallas")
    oracle = ref.banked_xnor_forward_ref(
        bank["w1p"], bank["b1"], bank["w2"], bank["b2"], x, slots)
    np.testing.assert_array_equal(
        np.asarray(jnp.take(got, g.result_rows, axis=0)), np.asarray(oracle))


def test_fused_contiguous_mode_matches_grouped_kernel():
    """row_ids=None path (pre-grouped rows) == staged grouped kernel entry."""
    num_slots, b, bb = 4, 32, 8
    bank = _bank(num_slots)
    rng = np.random.default_rng(5)
    slots = jnp.asarray(rng.integers(0, num_slots, b), jnp.int32)
    x = _payload(rng, b)
    g = bank_lib.group_by_slot_padded(slots, num_slots, bb)
    x_pad = bank_lib.scatter_padded(x, g)
    fused = ops.bnn_forward_grouped(bank, x_pad, g.block_slots,
                                    block_b=bb, backend="pallas")
    want = ops.bnn_forward_grouped(bank, x_pad, g.block_slots,
                                   block_b=bb, backend="ref")
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(want))


def test_packet_forward_fused_inline_actions():
    """The megakernel's in-kernel parse + Pi matches the staged pipeline,
    including the monitor-only control bit, at the paper's H32 width.
    Scores compare exactly too: every backend runs layer 2 as the same
    fixed-order sum (``ref.dense_pm1``)."""
    num_slots, b = 4, 48
    bank = executor.init_bank(jax.random.PRNGKey(0), num_slots)
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 2**32, (b, pkt.PAYLOAD_WORDS), dtype=np.uint32)
    slots = rng.integers(0, num_slots, b)
    for control in (0, int(pkt.CTRL_MONITOR_ONLY)):
        p = jnp.asarray(pkt.make_packets(slots, payload, control=control))
        base = pipeline.packet_step(bank, p, num_slots=num_slots,
                                    strategy="take")
        for backend in ("pallas", "ref"):
            res = pipeline.packet_step(bank, p, num_slots=num_slots,
                                       strategy="fused", backend=backend,
                                       block_b=8)
            np.testing.assert_array_equal(np.asarray(res.slots),
                                          np.asarray(base.slots))
            np.testing.assert_array_equal(np.asarray(res.scores),
                                          np.asarray(base.scores))
            np.testing.assert_array_equal(np.asarray(res.verdicts),
                                          np.asarray(base.verdicts))
            np.testing.assert_array_equal(np.asarray(res.actions),
                                          np.asarray(base.actions))


@pytest.mark.parametrize("strategy", ["grouped", "grouped_staged"])
def test_executor_grouped_strategies_agree(strategy):
    num_slots, b = 16, 64
    bank = _bank(num_slots)
    rng = np.random.default_rng(9)
    x = _payload(rng, b)
    slots = jnp.asarray(rng.integers(0, num_slots, b), jnp.int32)
    base = executor.forward_banked(bank, x, slots, strategy="take")
    got = executor.forward_banked(bank, x, slots, strategy=strategy,
                                  block_b=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_actions_ref_constants_mirror_packet_layout():
    assert ff.CTRL_WORD == pkt.CONTROL_WORD_LO
    assert ff.CTRL_MONITOR_ONLY == int(pkt.CTRL_MONITOR_ONLY)
    assert (ff.ACTION_FORWARD, ff.ACTION_DROP, ff.ACTION_FLAG) == (
        pkt.ACTION_FORWARD, pkt.ACTION_DROP, pkt.ACTION_FLAG)


def test_fused_rejects_bad_shapes():
    bank = _bank(2)
    rng = np.random.default_rng(1)
    x = _payload(rng, 16)
    with pytest.raises(ValueError, match="row_ids"):
        ff.fused_forward(x, bank["w1p"], bank["b1"], bank["w2"], bank["b2"],
                         jnp.zeros(2, jnp.int32), jnp.zeros(5, jnp.int32),
                         block_b=8, interpret=True)
    with pytest.raises(ValueError, match="with_actions"):
        ff.fused_forward(x, bank["w1p"], bank["b1"], bank["w2"], bank["b2"],
                         jnp.zeros(2, jnp.int32), block_b=8, interpret=True,
                         with_actions=True)


def test_streaming_replay_boundary_regression():
    """Streaming replay engine must preserve exact continuity semantics:
    zero wrong slots / verdicts on the boundary trace."""
    bank = executor.init_bank(jax.random.PRNGKey(0), 2)
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 2**32, (64, pkt.PAYLOAD_WORDS), dtype=np.uint32)
    tr = switching.boundary_trace(64, payload)
    res = switching.replay_trace(bank, tr, num_slots=2, batch=8,
                                 stream=True, stream_window=4)
    assert res.wrong_slot == 0
    assert res.wrong_verdict == 0
    assert res.boundary_index == 32
    assert np.all(np.diff(res.timestamps_us) >= 0)  # retire order is monotone


def test_streaming_replay_fused_strategy():
    bank = executor.init_bank(jax.random.PRNGKey(0), 2)
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 2**32, (32, pkt.PAYLOAD_WORDS), dtype=np.uint32)
    tr = switching.boundary_trace(32, payload)
    res = switching.replay_trace(bank, tr, num_slots=2, batch=8,
                                 strategy="fused", stream=True)
    assert res.wrong_slot == 0 and res.wrong_verdict == 0
