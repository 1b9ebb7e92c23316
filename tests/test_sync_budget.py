"""Host-sync regression guard: the schedule executor batches its chunk
admission, so device→host syncs scale with *op executions* (span
interiors re-run once per parent morsel), never with the number of chunks
inside one op execution.

Every deliberate sync in the engine goes through ``hostsync.device_get``
(the funnel); a :class:`SyncCounter` around a query counts them.  The
budget is derived from the executor's own op-run counters: at most 3
syncs per EXPAND run (planning fetch, split fetch, admission), 1 per FOLD
run (replay planning in evaluate mode), 1 per span close (continuation
admission), plus emission and stats finalization.  If someone
reintroduces a per-chunk ``bool(...)`` these fail with the offending
label in ``label_counts``."""
import numpy as np
import pytest

from repro.core import (CacheConfig, SyncCounter, choose_plan, cycle_query,
                        lftj_count, path_query)
from repro.core.cached_frontier import JaxCachedTrieJoin
from repro.core.frontier import JaxTrieJoin


@pytest.fixture(scope="module")
def db():
    rng = np.random.default_rng(1729)
    from repro.core.db import graph_db
    return graph_db(rng.integers(0, 40, size=(400, 2)))


def _budget(eng, stats_slack: int = 6) -> int:
    r = eng.last_executor.op_runs
    return 3 * r["expand"] + r["fold"] + r["span"] + r["emit"] + stats_slack


def test_triangle_stays_under_sync_budget(db):
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 12)
    want = lftj_count(q, order, db)
    with SyncCounter() as sc:
        got = eng.count()
    assert got == want
    assert sc.count <= _budget(eng), sc.label_counts


@pytest.mark.parametrize("cap", [1 << 13, 1 << 9, 1 << 7])
def test_sync_budget_scales_with_op_runs_not_chunks(db, cap):
    """Shrinking capacity multiplies the morsel count; syncs must track
    the op-run budget at every capacity (a per-chunk sync would blow it
    as soon as one op execution carries many chunks)."""
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=cap)
    with SyncCounter() as sc:
        eng.count()
    assert sc.count <= _budget(eng), (cap, sc.label_counts)


@pytest.mark.parametrize("cap", [1 << 11, 1 << 7])
def test_multibag_td_sync_budget(db, cap):
    """ENTER/FOLD spans add O(1) syncs per parent morsel (probe/dedup/
    insert are all device-side; cache stats accumulate on device) — also
    at a capacity small enough to force multiple parents per span."""
    q = path_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(
        q, td, order, db, capacity=cap,
        cache=CacheConfig(policy="setassoc", slots=256, assoc=4))
    want = lftj_count(q, order, db)
    with SyncCounter() as sc:
        got = eng.count()
    assert got == want
    assert sc.count <= _budget(eng), sc.label_counts


def test_evaluate_mode_sync_budget(db):
    """Materialization adds one replay-planning fetch per FOLD run and a
    single batched row fetch at the end — still op-run bounded."""
    q = path_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 10)
    with SyncCounter() as sc:
        blocks = list(eng.evaluate())
    n = sum(b.shape[0] for b in blocks)
    assert n == lftj_count(q, order, db)
    assert sc.count <= _budget(eng), sc.label_counts


@pytest.mark.tier1
def test_evaluate_payload_sync_budget(db):
    """Row-block caching must not add syncs: the payload plan (hit mask +
    block lengths) rides the per-fold ``replay-plan`` fetch — O(ops), not
    O(hits) — and the slab writes/splices are pure device ops.  Checked on
    a warm engine (second pass = replay-on-hit exercised end to end)."""
    q = path_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(
        q, td, order, db, capacity=1 << 9,
        cache=CacheConfig(policy="setassoc", slots=256, assoc=4,
                          cache_payloads=True, payload_rows=1 << 14))
    n1 = sum(b.shape[0] for b in eng.evaluate())  # cold: fills the slab
    with SyncCounter() as sc:
        n2 = sum(b.shape[0] for b in eng.evaluate())
    assert n1 == n2 == lftj_count(q, order, db)
    assert eng.stats["tier2_replay_hits"] > 0, "payload path not exercised"
    r = eng.last_executor.op_runs
    assert sc.count <= _budget(eng), sc.label_counts
    # payload fetches are batched per fold op, never per hit
    assert sc.label_counts["replay-plan"] <= r["fold"], sc.label_counts


@pytest.mark.tier1
def test_evaluate_stream_sync_budget(db):
    """Streaming emission must keep BLOCKING host syncs O(ops): result
    blocks leave as async fetches (``emit-stream`` issues, counted in
    ``async_count`` and labeled separately in ``label_counts``) — never
    as the one-shot ``emit-rows`` drain, and never as per-block blocking
    syncs.  Totals must still match the one-shot path exactly."""
    q = path_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(
        q, td, order, db, capacity=1 << 9,
        cache=CacheConfig(policy="setassoc", slots=256, assoc=4,
                          cache_payloads=True, payload_rows=1 << 14))
    n1 = sum(b.shape[0] for b in eng.evaluate())  # warm: fills the slab
    with SyncCounter() as sc:
        n2 = sum(b.shape[0] for b in eng.evaluate_stream())
    assert n1 == n2 == lftj_count(q, order, db)
    assert eng.stats["tier2_replay_hits"] > 0, "payload path not exercised"
    r = eng.last_executor.op_runs
    # blocking budget unchanged — streaming adds no blocking syncs at all
    assert sc.count <= _budget(eng), sc.label_counts
    assert sc.label_counts["emit-rows"] == 0, "one-shot drain in stream mode"
    # every emitted block left through the async queue, labeled as such;
    # interior-span streaming additionally issues replay plans async
    assert sc.label_counts["emit-stream"] == eng.last_executor.emitted_blocks > 0
    assert sc.async_count == (sc.label_counts["emit-stream"]
                              + sc.label_counts["replay-plan-async"])
    # payload fetches still batch per fold op, never per hit
    assert sc.label_counts["replay-plan"] <= r["fold"], sc.label_counts


def test_vanilla_lftj_sync_budget(db):
    q = path_query(3)
    order = sorted(q.variables)
    eng = JaxTrieJoin(q, order, db, capacity=1 << 12)
    with SyncCounter() as sc:
        eng.count()
    assert sc.count <= _budget(eng, stats_slack=2), sc.label_counts
