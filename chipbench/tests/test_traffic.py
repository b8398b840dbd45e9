"""The traffic generator: determinism, unique payloads, balanced RSS
buckets, and the same set of arrival gaps for every seed."""

import numpy as np

from chipbench.traffic import packets as tp, poisson, rss

BIG_SEED = 2**31 + 12345


def _source(seed=BIG_SEED, slots=16):
    return tp.PacketSource(slots=slots, flows=4096, monitor_share=0.1,
                           seed=seed)


def test_same_seed_same_packets_any_order():
    a, b = _source(), _source()
    seqs = np.array([5, 70_000, 3, 8191, 8192, 67_000_000 - 1])
    assert np.array_equal(a.packets(seqs), b.packets(seqs))
    run = a.run(8100, 200)
    assert np.array_equal(run, b.packets(np.arange(8100, 8300)[::-1])[::-1])
    buf = np.zeros((512, tp.PACKET_WORDS), np.uint32)
    assert np.array_equal(a.run(8100, 200, buf), run)


def test_seeds_differ():
    assert not np.array_equal(_source(1).run(0, 64), _source(2).run(0, 64))


def test_header_fields():
    rows = _source().run(0, 20_000)
    assert rows[:, tp.VERSION_WORD].min() == rows[:, tp.VERSION_WORD].max() == 1
    assert np.array_equal(rows[:, tp.SEQ_WORD], np.arange(20_000))
    assert set(np.unique(rows[:, tp.SLOT_WORD])) == set(range(16))
    share = (rows[:, tp.CONTROL_WORD] & 1).mean()
    assert 0.09 < share < 0.11


def test_payloads_and_their_suffixes_are_unique():
    rows = _source().run(0, 3 * tp.POOL_A)   # crosses both pools' wraps
    pay = rows[:, tp.META_WORDS:]
    assert np.unique(pay, axis=0).shape[0] == pay.shape[0]
    assert np.unique(pay[:, -2:], axis=0).shape[0] == pay.shape[0]


def test_flows_fill_rss_buckets_evenly_under_the_program_hash():
    from repro.dataplane import rss as prog_rss
    src = _source()
    h = prog_rss.toeplitz_hash(src.flow_table)
    assert np.array_equal(h, rss.toeplitz(src.flow_table))
    counts = np.bincount(prog_rss.bucket_index(h, 128), minlength=128)
    assert counts.min() == counts.max() == 4096 // 128
    rows = src.run(0, 4096)
    q = prog_rss.indirection_table(4)[prog_rss.bucket_index(
        prog_rss.toeplitz_hash(prog_rss.flow_words_of(rows)), 128)]
    assert np.array_equal(np.bincount(q), [1024] * 4)


def test_arrivals_same_gaps_other_order():
    a = poisson.arrivals(1000.0, 2.0, 1)
    b = poisson.arrivals(1000.0, 2.0, BIG_SEED)
    assert a.shape == b.shape == (2000,)
    assert a[0] == b[0] == 0.0
    n = 2000
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= 2.0 / gaps.sum()
    for due in (a, b):   # every gap but the one after the last arrival
        d = np.diff(due)
        assert np.abs(d[:, None] - gaps[None, :]).min(axis=1).max() < 1e-12
    assert not np.allclose(np.diff(a), np.diff(b))
    assert 1.99 < a[-1] < 2.0
