"""Tier-2 cache subsystem (core/cache.py): policy × size correctness and
accounting invariants.

The paper's flexibility property is that caching is *optional*: any policy
at any size (including 0 = disabled) must produce exactly the count of the
cache-free engine.  The accounting invariant hits + misses == probes is
what the dynamic sizing controller steers on, so it is load-bearing."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (CacheConfig, CachePolicy, choose_plan, clftj_count,
                        cycle_query, lftj_count, lollipop_query, star_query)
from repro.core.cache import DeviceCache
from repro.core.cached_frontier import JaxCachedTrieJoin

POLICY_CONFIGS = [
    CacheConfig(policy="direct", slots=0),          # disabled
    CacheConfig(policy="direct", slots=64),
    CacheConfig(policy="direct", slots=1 << 12),
    CacheConfig(policy="setassoc", slots=64, assoc=4),
    CacheConfig(policy="setassoc", slots=1 << 12, assoc=8),
    CacheConfig(policy="costaware", slots=64, assoc=2),
    CacheConfig(policy="costaware", slots=1 << 12, assoc=4),
    CacheConfig(policy="setassoc", slots=64, assoc=4, dynamic=True,
                budget=1 << 12, min_slots=16, resize_interval=2),
]


def _ids(cfg: CacheConfig) -> str:
    tag = f"{cfg.policy}-s{cfg.slots}-w{cfg.ways}"
    return tag + ("-dyn" if cfg.dynamic else "")


@pytest.mark.parametrize("cfg", POLICY_CONFIGS, ids=_ids)
@pytest.mark.parametrize("qf", [lambda: cycle_query(5),
                                lambda: lollipop_query(3, 2),
                                lambda: star_query(3)])
def test_policy_and_size_never_change_counts(small_graphs, cfg, qf):
    """Every policy × slots point == the cache-free, dedup-free engine."""
    q = qf()
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    baseline = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 9,
                                 dedup=False,
                                 cache=CacheConfig(slots=0)).count()
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 9, cache=cfg)
    assert eng.count() == baseline


@pytest.mark.parametrize("cfg", POLICY_CONFIGS, ids=_ids)
def test_probe_accounting_invariant(small_graphs, cfg):
    """tier2_hits + tier2_misses == tier2_probes, for every policy."""
    q = cycle_query(5)
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 9, cache=cfg)
    eng.count()
    s = eng.stats
    assert s["tier2_hits"] + s["tier2_misses"] == s["tier2_probes"]
    if cfg.slots == 0:
        assert s["tier2_probes"] == 0 and s["tier2_slots"] == 0


def test_dynamic_sizing_respects_budget_and_resizes(small_graphs):
    q = cycle_query(5)
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    cfg = CacheConfig(policy="setassoc", slots=16, assoc=4, dynamic=True,
                      budget=256, min_slots=8, resize_interval=1,
                      grow_below_hit_rate=1.0)  # always under target → grow
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8, cache=cfg)
    want = lftj_count(q, order, db)
    assert eng.count() == want
    assert eng.stats["tier2_resizes"] > 0
    # hard budget (the one-set-per-node floor is far below 256 here)
    assert eng.cache.total_slots() <= 256
    for t in eng.cache.tables.values():
        assert t.n_slots <= cfg.max_slots


def test_device_cache_set_fills_all_ways_and_hits():
    """A batch of same-set keys must fill every way, not just one (the
    multi-round insert), and then hit on re-probe."""
    with jax.enable_x64(True):
        from repro.core.cache import _hash_sets
        cfg = CacheConfig(policy="setassoc", slots=16, assoc=4)
        t = DeviceCache.create(cfg)
        n_sets = t.keys.shape[0]
        ks, k = [], 1
        while len(ks) < 4:  # 4 distinct keys, all in set 0
            if int(_hash_sets(jnp.asarray([k], jnp.int64), n_sets)[0]) == 0:
                ks.append(k)
            k += 1
        keys = jnp.asarray(ks, jnp.int64)
        vals = jnp.arange(4, dtype=jnp.int64) + 10
        t.insert(keys, vals, jnp.ones(4, bool))
        assert t.occupancy() == 4 and bool(t.used[0].all())
        hit, got = t.probe(keys, jnp.ones(4, bool))
        assert bool(hit.all())
        assert np.asarray(got).tolist() == [10, 11, 12, 13]
        assert t.hits + t.misses == t.probes == 4


def test_device_cache_lru_evicts_oldest():
    with jax.enable_x64(True):
        from repro.core.cache import _hash_sets
        cfg = CacheConfig(policy="setassoc", slots=8, assoc=2)
        t = DeviceCache.create(cfg)
        n_sets = t.keys.shape[0]
        ks, k = [], 1
        while len(ks) < 3:
            if int(_hash_sets(jnp.asarray([k], jnp.int64), n_sets)[0]) == 0:
                ks.append(k)
            k += 1
        one = jnp.ones(1, bool)
        t.insert(jnp.asarray(ks[:1], jnp.int64), jnp.asarray([1], jnp.int64),
                 one)
        t.insert(jnp.asarray(ks[1:2], jnp.int64), jnp.asarray([2], jnp.int64),
                 one)
        t.probe(jnp.asarray(ks[:1], jnp.int64), one)   # touch key0 → key1 LRU
        t.insert(jnp.asarray(ks[2:3], jnp.int64), jnp.asarray([3], jnp.int64),
                 one)                                   # evicts key1
        hit0, _ = t.probe(jnp.asarray(ks[:1], jnp.int64), one)
        hit1, _ = t.probe(jnp.asarray(ks[1:2], jnp.int64), one)
        hit2, _ = t.probe(jnp.asarray(ks[2:3], jnp.int64), one)
        assert bool(hit0[0]) and bool(hit2[0]) and not bool(hit1[0])
        assert t.evictions == 1


def test_device_cache_costaware_protects_expensive():
    with jax.enable_x64(True):
        from repro.core.cache import _hash_sets
        cfg = CacheConfig(policy="costaware", slots=4, assoc=1)
        t = DeviceCache.create(cfg)
        n_sets = t.keys.shape[0]
        ks, k = [], 1
        while len(ks) < 2:
            if int(_hash_sets(jnp.asarray([k], jnp.int64), n_sets)[0]) == 0:
                ks.append(k)
            k += 1
        one = jnp.ones(1, bool)
        t.insert(jnp.asarray(ks[:1], jnp.int64),
                 jnp.asarray([1000], jnp.int64), one)   # expensive resident
        t.insert(jnp.asarray(ks[1:2], jnp.int64),
                 jnp.asarray([1], jnp.int64), one)      # cheap: refused
        hit0, v = t.probe(jnp.asarray(ks[:1], jnp.int64), one)
        hit1, _ = t.probe(jnp.asarray(ks[1:2], jnp.int64), one)
        assert bool(hit0[0]) and int(v[0]) == 1000 and not bool(hit1[0])


def test_tier1_dedup_independent_of_tier2(small_graphs):
    """slots=0 disables only tier 2 — tier-1 dedup must still run."""
    q = cycle_query(5)
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 10,
                            cache=CacheConfig(slots=0), dedup=True)
    assert eng.count() == lftj_count(q, order, db)
    assert eng.stats["tier1_rows_collapsed"] > 0
    assert eng.stats["tier2_probes"] == 0


def test_sub_associativity_slots_round_up_to_one_set(small_graphs):
    """A positive slots request below one set must not silently disable
    the cache."""
    cfg = CacheConfig(policy="setassoc", slots=2, assoc=4)
    assert cfg.initial_slots() == 4
    q = cycle_query(5)
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 9, cache=cfg)
    assert eng.count() == lftj_count(q, order, db)
    assert eng.stats["tier2_probes"] > 0


def test_ref_engine_cost_policy_matches(small_graphs):
    """Host-engine analogue: 'cost' eviction preserves counts too."""
    q = cycle_query(5)
    db = small_graphs[1]
    td, order = choose_plan(q, db.stats())
    want = lftj_count(q, order, db)
    for cap in (0, 2, 8):
        pol = CachePolicy(capacity=cap, evict="cost")
        assert clftj_count(q, td, order, db, pol) == want


def test_cache_policy_from_cache_config():
    pol = CachePolicy.from_cache_config(
        CacheConfig(policy="costaware", slots=128, assoc=4))
    assert pol.evict == "cost" and pol.capacity == 128
    pol = CachePolicy.from_cache_config(
        CacheConfig(policy="setassoc", slots=64, budget=32))
    assert pol.evict == "lru" and pol.capacity == 32


# ---------------------------------------------------------------------------
# Row-block payload region (DESIGN.md §2.6)
# ---------------------------------------------------------------------------

def _payload_table(slots=8, assoc=2, payload_rows=32, policy="setassoc"):
    cfg = CacheConfig(policy=policy, slots=slots, assoc=assoc,
                      cache_payloads=True, payload_rows=payload_rows)
    t = DeviceCache.create(cfg)
    t.ensure_slab(width=2)
    return t


def _np(x):
    return np.asarray(x)


@pytest.mark.tier1
def test_payload_roundtrip_and_count_only_miss():
    """A payload insert is hit by probe_payload; a count-only insert on the
    same table is NOT (the -1 sentinel) while the plain probe still hits."""
    with jax.enable_x64(True):
        t = _payload_table()
        keys = jnp.asarray([3, 4], jnp.int64)
        active = jnp.asarray([True, True])
        lens = jnp.asarray([2, 0], jnp.int64)
        poff_np, admit = t.alloc_blocks(_np(lens), _np(active))
        assert list(admit) == [True, False]
        t.slab = t.slab.at[poff_np[0]:poff_np[0] + 2].set(
            jnp.asarray([[7, 8], [9, 10]], jnp.int32))
        t.insert(keys, lens, jnp.asarray(admit),
                 poff=jnp.asarray(poff_np), plen=lens.astype(jnp.int32))
        hit, poff, plen = t.probe_payload(keys, active)
        assert list(_np(hit)) == [True, False]
        assert int(_np(plen)[0]) == 2
        block = _np(t.slab)[int(_np(poff)[0]):int(_np(poff)[0]) + 2]
        assert block.tolist() == [[7, 8], [9, 10]]
        # count-only insert of a NEW key on the same table: plain probe
        # hits it, payload probe refuses it
        t.insert(jnp.asarray([5, 0], jnp.int64), jnp.asarray([6, 0]),
                 jnp.asarray([True, False]))
        hit2, vals2 = t.probe(jnp.asarray([5, 0], jnp.int64),
                              jnp.asarray([True, False]))
        assert list(_np(hit2)) == [True, False] and int(_np(vals2)[0]) == 6
        hit3, _, _ = t.probe_payload(jnp.asarray([5, 0], jnp.int64),
                                     jnp.asarray([True, False]))
        assert list(_np(hit3)) == [False, False]


@pytest.mark.tier1
def test_payload_flush_on_arena_exhaustion():
    """When a batch exceeds the remaining arena the table epoch-flushes:
    every payload is invalidated, keys/counts stay resident."""
    with jax.enable_x64(True):
        t = _payload_table(payload_rows=8)
        k1 = jnp.asarray([11, 12], jnp.int64)
        lens = jnp.asarray([4, 4], jnp.int64)
        act = jnp.asarray([True, True])
        poff_np, admit = t.alloc_blocks(_np(lens), _np(act))
        assert list(admit) == [True, True] and t.slab_bump == 8
        t.insert(k1, lens, jnp.asarray(admit), poff=jnp.asarray(poff_np),
                 plen=lens.astype(jnp.int32))
        # next batch cannot fit → flush, then admit from offset 0
        poff2, admit2 = t.alloc_blocks(np.asarray([6, 0]),
                                       np.asarray([True, False]))
        assert t.payload_flushes == 1 and list(admit2) == [True, False]
        assert poff2[0] == 0 and t.slab_bump == 6
        hit, _, _ = t.probe_payload(k1, act)
        assert not _np(hit).any(), "flushed payloads must not hit"
        hit_c, vals = t.probe(k1, act)
        assert list(_np(hit_c)) == [True, True]
        assert list(_np(vals)) == [4, 4], "counts survive the flush"


@pytest.mark.tier1
def test_payload_eviction_invalidates_block_metadata():
    """An evicting write must take the payload planes with it: after a
    count-only insert evicts a payload entry (direct-mapped, same set),
    the new key must not inherit the victim's block."""
    with jax.enable_x64(True):
        cfg = CacheConfig(policy="direct", slots=1, cache_payloads=True,
                          payload_rows=16)
        t = DeviceCache.create(cfg)
        t.ensure_slab(width=2)
        one = jnp.asarray([True])
        k_old = jnp.asarray([21], jnp.int64)
        lens = jnp.asarray([3], jnp.int64)
        poff_np, admit = t.alloc_blocks(_np(lens), _np(one))
        t.insert(k_old, lens, jnp.asarray(admit), poff=jnp.asarray(poff_np),
                 plen=lens.astype(jnp.int32))
        assert _np(t.probe_payload(k_old, one)[0]).all()
        # count-only insert of a different key lands in the only slot
        k_new = jnp.asarray([22], jnp.int64)
        t.insert(k_new, jnp.asarray([9], jnp.int64), one)
        hit_new, _, _ = t.probe_payload(k_new, one)
        assert not _np(hit_new).any(), "stale block reachable under new key"
        hit_old, _, _ = t.probe_payload(k_old, one)
        assert not _np(hit_old).any()


@pytest.mark.tier1
def test_payload_attaches_to_count_only_resident():
    """A payload-bearing insert may refresh a key first seen by count():
    afterwards the payload probe hits it."""
    with jax.enable_x64(True):
        t = _payload_table(slots=8, assoc=2)
        one = jnp.asarray([True])
        k = jnp.asarray([31], jnp.int64)
        t.insert(k, jnp.asarray([5], jnp.int64), one)  # count-only
        assert not _np(t.probe_payload(k, one)[0]).any()
        lens = jnp.asarray([2], jnp.int64)
        poff_np, admit = t.alloc_blocks(_np(lens), _np(one))
        t.insert(k, lens, jnp.asarray(admit), poff=jnp.asarray(poff_np),
                 plen=lens.astype(jnp.int32))
        hit, _, plen = t.probe_payload(k, one)
        assert _np(hit).all() and int(_np(plen)[0]) == 2


def test_payload_survives_dynamic_resize(small_graphs):
    """The sizing controller's rehash carries payload metadata; answers and
    the accounting invariant hold with payloads + dynamic sizing."""
    q = star_query(3)
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    cfg = CacheConfig(policy="setassoc", slots=16, assoc=4, dynamic=True,
                      budget=512, min_slots=8, resize_interval=1,
                      grow_below_hit_rate=1.0, cache_payloads=True,
                      payload_rows=1 << 12)
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8, cache=cfg)
    n1 = sum(b.shape[0] for b in eng.evaluate())
    n2 = sum(b.shape[0] for b in eng.evaluate())
    assert n1 == n2 == lftj_count(q, order, db)
    s = eng.stats
    assert s["tier2_hits"] + s["tier2_misses"] == s["tier2_probes"]
    assert s["tier2_replay_hits"] > 0


@pytest.mark.tier1
def test_payload_store_throttle():
    """A table with many evaluation probes and a negligible payload hit
    rate must throttle block storage; a recovering rate re-opens it."""
    cfg = CacheConfig(policy="setassoc", slots=64, assoc=4,
                      cache_payloads=True, payload_rows=64,
                      payload_throttle_probes=1000,
                      payload_throttle_hit_rate=0.01)
    t = DeviceCache.create(cfg)
    t.eval_probes_h, t.eval_hits_h = 500, 0
    assert not t.store_throttled(), "below the probe floor"
    t.eval_probes_h = 2000
    assert t.store_throttled(), "0% hits past the floor"
    t.eval_hits_h = 200
    assert not t.store_throttled(), "recovered hit rate re-opens storage"


def test_payload_throttle_end_to_end_still_correct(small_graphs):
    """With the throttle forced on from the first fold (floor 0) and
    probation off, answers are unchanged, the throttle is visibly
    engaged, and nothing is ever stored."""
    q = star_query(3)
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    cfg = CacheConfig(policy="setassoc", slots=256, assoc=4,
                      cache_payloads=True, payload_rows=1 << 12,
                      payload_throttle_probes=0,
                      payload_throttle_hit_rate=1.0,
                      payload_probation=0)
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8, cache=cfg)
    n1 = sum(b.shape[0] for b in eng.evaluate())
    n2 = sum(b.shape[0] for b in eng.evaluate())
    assert n1 == n2 == lftj_count(q, order, db)
    assert eng.stats["tier2_payload_throttled"] > 0
    assert eng.stats["tier2_slab_rows"] == 0, "throttle must stop stores"


@pytest.mark.tier1
def test_payload_dedup_off_no_duplicate_blocks(small_graphs):
    """With tier-1 dedup off, duplicate adhesion keys in one chunk must
    not each burn arena rows: one block per distinct key is stored, and
    answers still match."""
    q = star_query(3)
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    want = lftj_count(q, order, db)
    cfg = CacheConfig(policy="setassoc", slots=256, assoc=4,
                      cache_payloads=True, payload_rows=1 << 13)
    on = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 9, dedup=True,
                           cache=cfg)
    off = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 9, dedup=False,
                            cache=cfg)
    assert sum(b.shape[0] for b in on.evaluate()) == want
    assert sum(b.shape[0] for b in off.evaluate()) == want
    # duplicate keys collapse host-side: dedup-off stores the same arena
    # volume as dedup-on (no per-duplicate leak)
    assert off.stats["tier2_slab_rows"] == on.stats["tier2_slab_rows"]
    assert sum(b.shape[0] for b in off.evaluate()) == want
    assert off.stats["tier2_replay_hits"] > 0


@pytest.mark.tier1
def test_alloc_oversized_block_neither_flushes_nor_vetoes():
    """A block larger than the whole arena is refused outright: it must
    not epoch-flush resident payloads nor veto admissible candidates
    behind it in the same batch."""
    with jax.enable_x64(True):
        t = _payload_table(payload_rows=8)
        t.alloc_blocks(np.asarray([3]), np.asarray([True]))  # bump = 3
        # a never-fit block alone must not flush resident payloads
        _, admit0 = t.alloc_blocks(np.asarray([99]), np.asarray([True]))
        assert list(admit0) == [False] and t.payload_flushes == 0
        # ...nor veto an admissible candidate behind it in the same batch
        offs, admit = t.alloc_blocks(np.asarray([99, 2]),
                                     np.asarray([True, True]))
        assert list(admit) == [False, True]
        assert t.payload_flushes == 0 and offs[1] == 3
        # a batch that genuinely needs space still flushes, and after the
        # flush its first candidate is guaranteed to admit
        offs2, admit2 = t.alloc_blocks(np.asarray([7]), np.asarray([True]))
        assert t.payload_flushes == 1 and list(admit2) == [True]
        assert offs2[0] == 0


@pytest.mark.tier1
def test_throttled_table_still_shrinks_under_dynamic_sizing(small_graphs):
    """The sizing controller must keep running while the store throttle
    is engaged: an insert-less (fully throttled) table with near-zero
    occupancy hands its slots back."""
    q = star_query(3)
    db = small_graphs[2]
    td, order = choose_plan(q, db.stats())
    cfg = CacheConfig(policy="setassoc", slots=256, assoc=4, dynamic=True,
                      min_slots=8, resize_interval=1,
                      cache_payloads=True, payload_rows=1 << 12,
                      payload_throttle_probes=0,
                      payload_throttle_hit_rate=1.0, payload_probation=0)
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8, cache=cfg)
    n = sum(b.shape[0] for b in eng.evaluate())
    assert n == lftj_count(q, order, db)
    assert eng.stats["tier2_payload_throttled"] > 0
    assert eng.stats["tier2_resizes"] > 0, "controller frozen while throttled"
    assert eng.stats["tier2_slots"] < 256, "empty table did not shrink"
