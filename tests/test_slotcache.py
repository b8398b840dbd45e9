"""Zero-copy model switching (DESIGN.md §14): DoubleBufferedBank
staging/flip/rollback semantics, SlotCache LRU/pinning/prefetch, and the
property that any swap/traffic interleaving under the cache yields
verdicts bit-identical to the re-staging commit path with zero
wrong-verdict packets."""

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.control import CacheError, SlotCache, SlotMixPrefetcher, SwapSlot
from repro.core import bank as bank_lib, executor, packet as pkt
from repro.dataplane import DataplaneRuntime


@pytest.fixture(scope="module")
def bank4():
    return executor.init_bank(jax.random.PRNGKey(0), 4)


@pytest.fixture(scope="module")
def params_pool():
    return [executor.init_params(jax.random.PRNGKey(100 + i))
            for i in range(6)]


def banks_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def host_copy(tree):
    return jax.tree_util.tree_map(lambda l: np.asarray(l).copy(), tree)


# ---------------------------------------------------------------------------
# DoubleBufferedBank unit semantics
# ---------------------------------------------------------------------------

def test_stage_commit_matches_update_slot(bank4, params_pool):
    dbb = bank_lib.DoubleBufferedBank(bank4)
    assert dbb.stage(2, params_pool[0], token="t", epoch=1)
    assert dbb.has_staged
    new = dbb.commit()
    assert not dbb.has_staged and dbb.committed("t")
    assert banks_equal(new, bank_lib.update_slot(bank4, 2, params_pool[0]))


def test_sequential_swaps_resync_dirty_slots(bank4, params_pool):
    """The second flip's demoted buffer is dirty at the first swap's
    slot; stage() must resync it so only the staged slot differs."""
    dbb = bank_lib.DoubleBufferedBank(bank4)
    dbb.stage(1, params_pool[0], token="a", epoch=1)
    dbb.commit()
    dbb.stage(3, params_pool[1], token="b", epoch=2)
    new = dbb.commit()
    want = bank_lib.update_slot(
        bank_lib.update_slot(bank4, 1, params_pool[0]), 3, params_pool[1])
    assert banks_equal(new, want)


def test_one_staged_epoch_policy(bank4, params_pool):
    dbb = bank_lib.DoubleBufferedBank(bank4)
    assert dbb.stage(0, params_pool[0], token="a", epoch=1)
    # a different epoch scope is refused without force
    assert not dbb.stage(1, params_pool[1], token="b", epoch=2)
    # apply-time wins: force discards the earlier staged entry
    assert dbb.stage(1, params_pool[1], token="b", epoch=2, force=True)
    new = dbb.commit()
    assert banks_equal(new, bank_lib.update_slot(bank4, 1, params_pool[1]))
    assert dbb.committed("b") and not dbb.committed("a")


def test_mark_restore_rolls_back_a_flip(bank4, params_pool):
    dbb = bank_lib.DoubleBufferedBank(bank4)
    before = host_copy(dbb.active)
    m = dbb.mark()
    dbb.stage(2, params_pool[0], token="x", epoch=1)
    dbb.commit()
    dbb.restore(m)
    dbb.discard_staged()
    assert banks_equal(dbb.active, before)
    # the buffer dirtied by the rollback is resynced on the next stage
    dbb.stage(0, params_pool[1], token="y", epoch=2)
    assert banks_equal(dbb.commit(),
                       bank_lib.update_slot(bank4, 0, params_pool[1]))


def test_pin_forces_copy_on_write(bank4, params_pool):
    """A pinned buffer that becomes the staging shadow after a flip must
    be un-aliased, not mutated — its holder may still read it."""
    dbb = bank_lib.DoubleBufferedBank(bank4)
    handle = dbb.pin_active()
    snapshot = host_copy(handle.tree)
    dbb.stage(1, params_pool[0], token="a", epoch=1)
    dbb.commit()                       # pinned buffer is now the shadow
    dbb.stage(2, params_pool[1], token="b", epoch=2)
    dbb.commit()
    assert banks_equal(handle.tree, snapshot)
    assert dbb.unalias_copies >= 1
    dbb.unpin(handle)


def test_runtime_flip_equals_restage(bank4, params_pool):
    banks = {}
    for db in (True, False):
        rt = DataplaneRuntime(bank4, num_queues=2, strategy="take",
                              batch=32, double_buffer=db)
        rt.control.submit(SwapSlot(1, params_pool[0]))
        rt.flush_control()
        banks[db] = rt.bank
    assert banks_equal(banks[True], banks[False])
    assert banks_equal(banks[True],
                       bank_lib.update_slot(bank4, 1, params_pool[0]))


# ---------------------------------------------------------------------------
# SlotCache: LRU, pinning, prefetch
# ---------------------------------------------------------------------------

def _cache_rt(num_slots=2, **kw):
    bank = executor.init_bank(jax.random.PRNGKey(1), num_slots)
    kw.setdefault("num_queues", 2)
    kw.setdefault("strategy", "take")
    kw.setdefault("batch", 32)
    return DataplaneRuntime(bank, **kw)


def _registered_cache(rt, n_models):
    cache = SlotCache(rt)
    for i in range(n_models):
        cache.register(f"m{i}", executor.init_params(
            jax.random.PRNGKey(50 + i)))
    return cache


def test_cache_lru_eviction_order():
    rt = _cache_rt(2)
    cache = _registered_cache(rt, 4)
    s0 = cache.ensure("m0")
    s1 = cache.ensure("m1")
    assert {s0, s1} == {0, 1} and cache.misses == 2
    assert cache.ensure("m0") == s0 and cache.hits == 1
    # m1 is now least-recently used -> m2 takes its slot
    assert cache.ensure("m2") == s1
    assert not cache.is_resident("m1") and cache.evictions == 1
    rt.flush_control()
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["wrong_verdict"] == 0


def test_evict_pinned_slot_rejected():
    rt = _cache_rt(2)
    cache = _registered_cache(rt, 4)
    cache.ensure("m0")
    cache.ensure("m1")
    cache.pin("m0")
    with pytest.raises(CacheError):
        cache.evict("m0")
    cache.pin("m1")
    with pytest.raises(CacheError):   # miss with every slot pinned
        cache.ensure("m2")
    cache.unpin("m1")
    assert cache.ensure("m2") == 1    # m1's slot, the only evictable one
    cache.unpin("m0")
    assert cache.evict("m0") == 0
    with pytest.raises(CacheError):
        cache.evict("m0")             # no longer resident


def test_prefetch_promotes_to_flip_only_miss():
    rt = _cache_rt(2)
    cache = _registered_cache(rt, 4)
    cache.ensure("m0")
    cache.ensure("m1")
    rt.flush_control()                      # commit the fills; shadow free
    assert cache.prefetch("m2") is True     # staged into the shadow
    reserved_slot = cache._prefetched["m2"][0]
    assert cache.ensure("m2") == reserved_slot
    assert cache.prefetch_hits == 1
    rt.flush_control()
    assert banks_equal(
        bank_lib.select_slot(rt.bank, reserved_slot),
        cache._models["m2"])


def test_prefetcher_predicts_periodic_demand():
    rt = _cache_rt(2)
    cache = _registered_cache(rt, 3)
    pf = SlotMixPrefetcher(cache, horizon=8)
    for m in ("m0", "m1", "m2", "m0", "m1", "m2", "m0"):
        cache.ensure(m)
    rt.flush_control()        # commit pending swaps; shadow free to stage
    issued = pf.poll()
    # m1/m2 are the non-resident models with a learned period; the one
    # due back soonest is pre-staged before its miss arrives
    assert issued and issued[0] in ("m1", "m2")
    assert cache.prefetch_issued >= 1


# ---------------------------------------------------------------------------
# property: cache churn is bit-identical across flip vs re-stage commits
# ---------------------------------------------------------------------------

_OP = st.sampled_from(["dispatch", "tick", "ensure", "prefetch", "pinflip"])


def _drive(ops, seed, bank4, params_pool, double_buffer):
    rng = np.random.default_rng(seed)
    rt = DataplaneRuntime(bank4, num_queues=2, strategy="take", batch=32,
                          ring_capacity=4096, record=True, audit=True,
                          double_buffer=double_buffer)
    cache = SlotCache(rt)
    names = [f"m{i}" for i in range(len(params_pool))]
    for n, p in zip(names, params_pool):
        cache.register(n, p)
    pinned = None
    for op in ops:
        if op == "dispatch":
            burst = pkt.make_packets(
                rng.integers(0, 4, 16),
                rng.integers(0, 2**32, (16, pkt.PAYLOAD_WORDS),
                             dtype=np.uint32))
            rt.dispatch(burst)
        elif op == "tick":
            rt.tick()
        elif op == "ensure":
            try:
                cache.ensure(names[rng.integers(len(names))])
            except CacheError:
                pass                      # every slot pinned: rejected
        elif op == "prefetch":
            cache.prefetch(names[rng.integers(len(names))])
        elif op == "pinflip":
            m = names[rng.integers(len(names))]
            if pinned == m:
                cache.unpin(m)
                pinned = None
            elif pinned is None and cache.is_resident(m):
                cache.pin(m)
                pinned = m
    rt.drain()
    aud = rt.audit_conservation()
    assert aud["ok"] and aud["wrong_verdict"] == 0, aud
    stats = cache.stats()
    # prefetch_hits counts actual shadow staging, which only exists on
    # the double-buffered stack — every packet-observable quantity and
    # the hit/miss/eviction economics must still match exactly
    stats.pop("prefetch_hits")
    return (rt.completed_seq, rt.completed_verdicts, rt.completed_slots,
            [cache.model_at(i) for i in range(rt.num_slots)],
            stats)


@settings(max_examples=8, deadline=None)
@given(ops=st.lists(_OP, min_size=4, max_size=20), seed=st.integers(0, 2**31))
def test_cache_interleaving_flip_equals_restage(ops, seed, bank4,
                                                params_pool):
    """Any interleaving of traffic with cache hits, misses, evictions,
    prefetches, and pin churn scores every packet bit-identically
    whether swaps commit by pointer flip or by re-staging — and neither
    path ever produces a wrong verdict."""
    flip = _drive(ops, seed, bank4, params_pool, double_buffer=True)
    restage = _drive(ops, seed, bank4, params_pool, double_buffer=False)
    assert flip == restage
