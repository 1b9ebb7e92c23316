"""Query-serving tier (DESIGN.md §2.9): the lock-down suite for
``repro/serve``.

Five groups:

* **canonical keys** — plan-cache key derivation is isomorphism-invariant
  (variable renamings + atom shuffles key identically), faithful (equal
  keys only for genuinely isomorphic queries — the key *is* the canonical
  serialization), idempotent, and TD-numbering-insensitive.  The
  generative half runs under hypothesis when installed; a fixed seed
  corpus drives the same assertions otherwise.
* **plan cache** — isomorphic lookups hit and share one engine; a cached
  plan's results are bit-identical to a cold compile of the same plan;
  LRU eviction honors ``max_plans`` (0 = always-cold regime).
* **sessions** — N client threads streaming a Zipf-mixed query workload
  each match the serial one-shot oracle; the admission bound is never
  exceeded (``in_flight_high_water``); rejection carries a positive
  ``retry_after_s`` and the server recovers; per-session blocking syncs
  stay within the O(op-runs) budget; the worker's syncs do NOT leak into
  client-thread SyncCounters (thread-local scopes).
* **persistence** — a snapshot written by a *separate process* warms a
  fresh server (plan-cache hit + ``tier2_replay_hits > 0`` on its first
  query); truncated / corrupt / wrong-version / wrong-config snapshots
  fall back cold without raising.
* **slab epoch** — importing table state whose slab epoch cannot cover
  its resident payload blocks cold-starts the payload region only
  ("flushed"), keys stay warm, and results remain exact (the stale-splice
  regression this PR's ``import_state`` validation closes).
"""
import dataclasses
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.configs.paper_clftj import TPU_SERVE
from repro.core import choose_plan, cycle_query, engine, path_query
from repro.core.cq import CQ, Atom
from repro.core.db import graph_db
from repro.core.hostsync import SyncCounter
from repro.core.td import TreeDecomposition
from repro.serve import (JoinServer, PlanCache, SessionRejected,
                         canonical_cq, canonical_td)
from repro.serve.canonical import rename_query

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.serve

# small tables so tests stay fast; payloads on so replay paths execute
CFG = dataclasses.replace(TPU_SERVE, cache_slots=512, cache_assoc=4,
                          payload_rows=1 << 13, frontier_capacity=1 << 14)


@pytest.fixture(scope="module")
def db():
    from repro.data.graphs import zipf_graph
    return graph_db(zipf_graph(16, 110, 1.1, seed=314))


def _aligned(res):
    """Result rows with columns sorted by variable name — comparable
    across engines that picked different output orders."""
    idx = [res.order.index(v) for v in sorted(res.order)]
    rows = np.asarray(res.tuples)[:, idx]
    return {tuple(map(int, r)) for r in rows.tolist()}


def _aligned_blocks(order, blocks):
    idx = [order.index(v) for v in sorted(order)]
    if not blocks:
        return set()
    rows = np.concatenate(blocks, axis=0)[:, idx]
    return {tuple(map(int, r)) for r in rows.tolist()}


# ---------------------------------------------------------------------------
# canonical keys
# ---------------------------------------------------------------------------

def _scramble(q: CQ, seed: int) -> CQ:
    """A uniformly random isomorphic copy: rename vars + shuffle atoms."""
    rng = np.random.default_rng(seed)
    variables = list(q.variables)
    names = [f"s{i}" for i in rng.permutation(len(variables))]
    mapping = dict(zip(variables, names))
    atoms = list(rename_query(q, mapping).atoms)
    rng.shuffle(atoms)
    return CQ(tuple(atoms))


def _corpus_query(seed: int) -> CQ:
    rng = np.random.default_rng(seed)
    kind = int(rng.integers(0, 3))
    if kind == 0:
        return path_query(int(rng.integers(2, 6)))
    if kind == 1:
        return cycle_query(int(rng.integers(3, 6)))
    from repro.core import random_graph_query
    return random_graph_query(int(rng.integers(3, 6)), 0.6, seed=seed)


def _check_canonical_invariants(q: CQ, seed: int) -> None:
    canon, pos, key = canonical_cq(q)
    # pos is a bijection onto 0..n-1 and the key is a faithful
    # serialization: renaming q through pos reproduces the canon atoms
    assert sorted(pos.values()) == list(range(len(q.variables)))
    renamed = rename_query(q, {v: f"v{i}" for v, i in pos.items()})
    akey = lambda a: (a.relation, a.vars)
    assert sorted(renamed.atoms, key=akey) == sorted(canon.atoms, key=akey)
    # isomorphism-invariance: any scrambled copy keys identically
    canon2, pos2, key2 = canonical_cq(_scramble(q, seed))
    assert key2 == key
    assert canon2 == canon
    # idempotence: the canonical form is a fixpoint
    canon3, pos3, key3 = canonical_cq(canon)
    assert key3 == key and canon3 == canon
    assert all(pos3[f"v{i}"] == i for i in range(len(q.variables)))


def test_canonical_key_invariant_deterministic_corpus():
    for seed in range(40):
        _check_canonical_invariants(_corpus_query(seed), seed * 7 + 1)


def test_distinct_shapes_key_distinct():
    shapes = [path_query(2), path_query(3), path_query(4), cycle_query(3),
              cycle_query(4), cycle_query(5),
              CQ((Atom("E", ("x", "y")), Atom("E", ("x", "z")))),
              CQ((Atom("R", ("x", "y")), Atom("E", ("y", "z"))))]
    keys = [canonical_cq(q)[2] for q in shapes]
    assert len(set(keys)) == len(keys)


def test_canonical_td_numbering_insensitive(db):
    q = path_query(4)
    td, order = choose_plan(q, db.stats())
    _, pos, _ = canonical_cq(q)
    _, key_a = canonical_td(td, pos)
    # renumber the same tree: reverse the child-visit order
    n = len(td.bags)
    perm = list(range(n))
    if n > 2:
        perm = [0] + list(reversed(range(1, n)))
    inv = {old: new for new, old in enumerate(perm)}
    bags = [td.bags[old] for old in perm]
    parent = [inv[td.parent[old]] if td.parent[old] >= 0 else -1
              for old in perm]
    td2 = TreeDecomposition(bags, parent)
    _, key_b = canonical_td(td2, pos)
    assert key_a == key_b


@pytest.mark.slow
@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_canonical_key_invariant_generative():
    @settings(max_examples=150, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(0, 2**31 - 1))
    def prop(qseed, sseed):
        _check_canonical_invariants(_corpus_query(qseed), sseed)

    prop()


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_iso_hit_and_bit_identical_results(db):
    pc = PlanCache(db, CFG, max_plans=8)
    q = path_query(3)
    e1, hit1, pos1 = pc.lookup(q)
    assert not hit1 and len(pc) == 1
    cold = np.concatenate(list(e1.engine.evaluate()), axis=0)
    # an isomorphic copy hits the same entry...
    e2, hit2, pos2 = pc.lookup(_scramble(q, 5))
    assert hit2 and e2 is e1 and len(pc) == 1
    # ...and the warm engine (tier-2 replay active) reproduces the cold
    # pass bit-identically: same rows, same order
    warm = np.concatenate(list(e2.engine.evaluate()), axis=0)
    assert np.array_equal(cold, warm)
    # against a fresh cold compile of the same canonical plan
    from repro.core.cached_frontier import JaxCachedTrieJoin
    fresh = JaxCachedTrieJoin(e1.cq, e1.td, e1.order, db,
                              capacity=CFG.frontier_capacity,
                              dedup=CFG.dedup, impl=CFG.impl,
                              cache=CFG.cache_config(),
                              expand_kernel=CFG.expand_kernel,
                              emit_in_flight=CFG.emit_in_flight)
    ref = np.concatenate(list(fresh.evaluate()), axis=0)
    assert np.array_equal(cold, ref)
    # count mode agrees too (warm cached engine vs cold compile)
    assert e1.engine.count() == fresh.count() == len(ref)


def test_plan_cache_lru_and_cold_regime(db):
    pc = PlanCache(db, CFG, max_plans=1)
    pc.lookup(path_query(2))
    pc.lookup(cycle_query(3))          # evicts the path plan
    assert len(pc) == 1
    _, hit, _ = pc.lookup(path_query(2))
    assert not hit                     # was evicted
    cold = PlanCache(db, CFG, max_plans=0)
    for _ in range(2):
        _, hit, _ = cold.lookup(path_query(2))
        assert not hit
    assert len(cold) == 0


def test_config_keys_separate_plans(db):
    # same query, different engine config → different key space: a plan
    # compiled for one table geometry must not serve another
    from repro.serve import config_key
    other = dataclasses.replace(CFG, cache_slots=CFG.cache_slots * 2)
    assert config_key(CFG) != config_key(other)
    assert PlanCache(db, CFG).cfg_key != PlanCache(db, other).cfg_key


def test_snapshot_carries_autotune_entries(db, tmp_path):
    from repro.kernels import registry
    spec = registry.ExpandSpec(capacity=1 << 30, n_vars=3, n_atoms=2,
                               n_others=1, dtype="int32", x64=True)
    entry = {"spec": dataclasses.asdict(spec), "platform": "serving-test",
             "choice": "xla"}
    assert registry.merge_autotune_entries([entry]) == 1
    try:
        snap = str(tmp_path / "auto.npz")
        with JoinServer(db, CFG) as srv:
            srv.count(path_query(2))
            srv.save_snapshot(snap)
        registry.clear_autotune_cache()
        assert entry not in registry.autotune_entries()
        with JoinServer(db, CFG) as srv:
            summary = srv.load_snapshot(snap)
        assert summary["autotune"] >= 1
        assert entry in registry.autotune_entries()
    finally:
        registry.clear_autotune_cache()


def test_explicit_td_and_auto_key_separate(db):
    pc = PlanCache(db, CFG, max_plans=8)
    q = path_query(3)
    td, order = choose_plan(q, db.stats())
    _, hit_a, _ = pc.lookup(q)
    _, hit_b, _ = pc.lookup(q, td, order)
    assert not hit_a and not hit_b and len(pc) == 2


# ---------------------------------------------------------------------------
# sessions
# ---------------------------------------------------------------------------

def test_concurrent_sessions_match_serial_oracle(db):
    base = [path_query(3), cycle_query(3), path_query(4)]
    rng = np.random.default_rng(99)
    # Zipf-mixed workload of isomorphic variants, one stream per query
    work = []
    for i in range(18):
        j = min(int(rng.zipf(1.8)) - 1, len(base) - 1)
        work.append(_scramble(base[j], 1000 + i))
    # one oracle per *variant*: isomorphic queries share a plan but their
    # labeled answer sets differ (variable roles swap under renaming)
    oracle = {}
    for q in work:
        if q not in oracle:
            oracle[q] = _aligned(engine.evaluate(q, db))
    failures = []
    with JoinServer(db, CFG, max_sessions=3, max_plans=8,
                    block_queue=4) as srv:
        def client(tid, queries):
            for q in queries:
                while True:
                    try:
                        sess = srv.submit(q, "stream")
                        break
                    except SessionRejected as e:
                        threading.Event().wait(min(e.retry_after_s, 0.05))
                blocks = list(sess.blocks())
                res = sess.result(timeout=120)
                got = _aligned_blocks(res.order, blocks)
                if got != oracle[q]:
                    failures.append((tid, q))
                # per-session blocking syncs: O(op runs), never O(chunks)
                r = sess.op_runs
                budget = (3 * r.get("expand", 0) + r.get("fold", 0)
                          + r.get("span", 0) + r.get("emit", 0) + 10)
                if sess.sync.count > budget:
                    failures.append((tid, "sync", sess.sync.count, budget))

        threads = [threading.Thread(target=client, args=(t, work[t::4]))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not failures, failures[:3]
        stats = srv.stats()
    assert stats["in_flight_high_water"] <= 3
    assert stats["completed"] == len(work)
    assert stats["failed"] == 0
    assert stats["plan_cache"]["hits"] >= len(work) - len(base)


def test_admission_bound_rejection_and_recovery(db):
    with JoinServer(db, CFG, max_sessions=2, max_plans=4) as srv:
        srv.count(path_query(3))      # warm the plan first
        # stall the worker at the execution gate so both admitted
        # sessions stay in flight deterministically
        srv._exec_lock.acquire()
        try:
            s1 = srv.submit(path_query(3), "stream")
            s2 = srv.submit(path_query(3), "stream")
            with pytest.raises(SessionRejected) as exc:
                srv.submit(path_query(3), "stream")
            assert exc.value.retry_after_s > 0
            assert srv.stats()["rejected"] == 1
            s2.cancel()               # abandoned while still queued
        finally:
            srv._exec_lock.release()
        rows = sum(b.shape[0] for b in s1.blocks())
        assert rows == s1.result(timeout=120).count
        with pytest.raises(Exception):
            s2.result(timeout=120)
        # slots freed: the server keeps serving
        r = srv.count(path_query(3))
        assert r.count == engine.count(path_query(3), db).count
        assert srv.stats()["in_flight"] == 0


def test_worker_syncs_do_not_leak_into_client_counter(db):
    with JoinServer(db, CFG, max_sessions=2) as srv:
        with SyncCounter() as sc:
            srv.evaluate(path_query(3))
        # execution happens on the worker thread; its device syncs must
        # land in the session's counter, not this thread's
        assert sc.count == 0


def test_session_result_order_uses_client_names(db):
    q = CQ((Atom("E", ("b", "q")), Atom("E", ("z", "b")),
            Atom("E", ("a", "z"))))
    with JoinServer(db, CFG) as srv:
        res = srv.evaluate(q)
        assert set(res.order) == {"a", "b", "q", "z"}
        assert _aligned(res) == _aligned(engine.evaluate(q, db))
        assert res.plan_cache_hit in (False,)  # first query is a miss
        res2 = srv.evaluate(_scramble(q, 3))
        assert res2.plan_cache_hit


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

_WRITER = r"""
import sys
sys.path.insert(0, {src!r})
import numpy as np
from repro.configs.paper_clftj import TPU_SERVE
import dataclasses
from repro.core import path_query
from repro.core.db import graph_db
from repro.core.engine import serve
from repro.serve import save_snapshot
from repro.data.graphs import zipf_graph

CFG = dataclasses.replace(TPU_SERVE, cache_slots=512, cache_assoc=4,
                          payload_rows=1 << 13, frontier_capacity=1 << 14)
db = graph_db(zipf_graph(16, 110, 1.1, seed=314))
with serve(db, CFG) as srv:
    r = srv.evaluate(path_query(3))
    assert r.tuples is not None and len(r.tuples) > 0
    save_snapshot({snap!r}, srv.plan_cache)
print("WROTE")
"""


def test_snapshot_from_other_process_serves_warm(db, tmp_path):
    snap = str(tmp_path / "serve_snap.npz")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _WRITER.format(src=src, snap=snap)
    # the writer forces the CPU: this process may hold the chip
    proc = subprocess.run([sys.executable, "-c", code],
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "WROTE" in proc.stdout
    with JoinServer(db, CFG) as srv:
        summary = srv.load_snapshot(snap)
        assert summary["status"] == "ok"
        assert summary["plans"] >= 1 and summary["tables"] >= 1
        assert summary["flushed"] == 0
        # the FIRST query of this process: auto-keyed lookup must hit the
        # loaded plan and replay persisted payload blocks
        q = _scramble(path_query(3), 11)
        res = srv.evaluate(q)
        assert res.plan_cache_hit
        assert res.tier2_replay_hits > 0
        assert _aligned(res) == _aligned(engine.evaluate(q, db))


@pytest.fixture(scope="module")
def warm_snapshot(db, tmp_path_factory):
    """An in-process snapshot with resident payload state, for the
    corruption/fallback tests (cheaper than a subprocess per test)."""
    snap = str(tmp_path_factory.mktemp("serve") / "warm.npz")
    with JoinServer(db, CFG) as srv:
        srv.evaluate(path_query(3))
        srv.evaluate(cycle_query(3))
        srv.save_snapshot(snap)
    return snap


@pytest.mark.parametrize("mangle", ["truncate", "garbage", "version"])
def test_unusable_snapshot_falls_back_cold(db, warm_snapshot, tmp_path,
                                           mangle):
    bad = str(tmp_path / f"bad_{mangle}.npz")
    raw = open(warm_snapshot, "rb").read()
    if mangle == "truncate":
        open(bad, "wb").write(raw[: len(raw) // 3])
    elif mangle == "garbage":
        open(bad, "wb").write(b"\x00\xde\xad\xbe\xef" * 64)
    else:
        import json
        man = {"version": 99, "cfg_key": "", "autotune": [], "plans": []}
        arr = np.frombuffer(json.dumps(man).encode(), np.uint8).copy()
        np.savez_compressed(bad, manifest=arr)
    with JoinServer(db, CFG) as srv:
        with pytest.warns(UserWarning):
            summary = srv.load_snapshot(bad)
        assert summary["status"] == "cold"
        assert summary["plans"] == 0
        res = srv.evaluate(path_query(3))     # cold but fully functional
        assert not res.plan_cache_hit
        assert _aligned(res) == _aligned(engine.evaluate(path_query(3), db))


def test_config_mismatch_transfers_autotune_only(db, warm_snapshot):
    other = dataclasses.replace(CFG, cache_slots=256)
    with JoinServer(db, other) as srv:
        summary = srv.load_snapshot(warm_snapshot)
        assert summary["status"] == "config-mismatch"
        assert summary["plans"] == 0
        res = srv.count(path_query(3))
        assert res.count == engine.count(path_query(3), db).count


def test_snapshot_roundtrip_in_process(db, warm_snapshot):
    with JoinServer(db, CFG) as srv:
        summary = srv.load_snapshot(warm_snapshot)
        assert summary["status"] == "ok"
        assert summary["plans"] == 2 and summary["flushed"] == 0
        res = srv.evaluate(path_query(3))
        assert res.plan_cache_hit and res.tier2_replay_hits > 0


# ---------------------------------------------------------------------------
# slab epoch (eval-mode cold/warm asymmetry regression)
# ---------------------------------------------------------------------------

def _resident_payload_state(pc):
    """(entry, node, state) for some table with resident payload blocks."""
    for entry in pc.entries():
        for node, st in entry.engine.cache.export_state().items():
            pay_len = np.asarray(st.get("pay_len", -1))
            used = np.asarray(st.get("used", False))
            if pay_len.ndim and (used & (pay_len >= 0)).any():
                return entry, node, st
    raise AssertionError("no table with resident payload blocks")


def test_stale_slab_epoch_flushes_payload_only(db):
    pc = PlanCache(db, CFG, max_plans=4)
    entry, _, _ = pc.lookup(path_query(3))
    ref = np.concatenate(list(entry.engine.evaluate()), axis=0)
    entry, node, st = _resident_payload_state(pc)
    tbl = entry.engine.cache.get(node)
    flushes0 = tbl.payload_flushes
    # a snapshot whose epoch was lost: bump says "nothing allocated" while
    # pay_len still claims blocks — the stale-splice hazard
    bad = dict(st)
    bad["slab_bump"] = 0
    assert tbl.import_state(bad) == "flushed"
    assert tbl.payload_flushes == flushes0 + 1
    assert tbl.slab_bump == 0
    # payload region is cold (no block can replay-splice stale rows) but
    # the key/count planes stayed warm and results are exact
    assert int(np.asarray(tbl.pay_len).max()) == -1
    again = np.concatenate(list(entry.engine.evaluate()), axis=0)
    assert np.array_equal(ref, again)


def test_block_past_epoch_also_flushes(db):
    pc = PlanCache(db, CFG, max_plans=4)
    e0, _, _ = pc.lookup(path_query(3))
    list(e0.engine.evaluate())          # populate payload blocks
    entry, node, st = _resident_payload_state(pc)
    tbl = entry.engine.cache.get(node)
    bad = dict(st)
    # claim a block that ends past the allocated prefix
    off = np.array(bad["pay_off"], np.int32, copy=True)
    ln = np.array(bad["pay_len"], np.int32, copy=True)
    used = np.asarray(bad["used"])
    r, w = np.argwhere(used & (ln >= 0))[0]
    off[r, w] = int(bad["slab_bump"])
    ln[r, w] = 4
    bad["pay_off"], bad["pay_len"] = off, ln
    assert tbl.import_state(bad) == "flushed"
    ref = engine.evaluate(path_query(3), db)
    got = np.concatenate(list(entry.engine.evaluate()), axis=0)
    assert len(got) == len(ref.tuples)


def test_rejected_import_leaves_table_unchanged(db):
    pc = PlanCache(db, CFG, max_plans=4)
    entry, _, _ = pc.lookup(path_query(3))
    entry.engine.count()
    states = entry.engine.cache.export_state()
    node, st = next(iter(states.items()))
    tbl = entry.engine.cache.get(node)
    keys0 = np.asarray(tbl.keys).copy()
    bad = dict(st)
    bad["keys"] = np.zeros((3, 3), np.int64)   # wrong geometry
    assert tbl.import_state(bad) == "rejected"
    assert np.array_equal(np.asarray(tbl.keys), keys0)
