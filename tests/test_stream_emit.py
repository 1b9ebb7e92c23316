"""Streaming async EMIT + payload-capable static evaluation (DESIGN §2.8).

Four groups:

* the :class:`AsyncFetchQueue` contract — FIFO arrival order, the
  in-flight bound (back-pressure), drain completeness, and the
  SyncCounter accounting split (async issues never count as blocking
  syncs);
* ``evaluate_stream`` vs one-shot ``evaluate``: bit-identical rows in
  identical order, for the vanilla LFTJ engine, the cached engine under
  payload caching, and through the ``engine.evaluate_stream`` facade
  (whose ResultStream must reproduce the one-shot Result totals);
* trace-time ``execute_static`` evaluation: oracle parity, warm-pass
  payload replay (``tier2_replay_hits > 0``), count-table bypass
  (optionality), and honest overflow flagging at tiny capacity —
  including the splice path, which clamps silently and must be
  flagged by the executor;
* the measured-autotune JSON sidecar: save/load roundtrip, in-memory
  precedence, and the corrupt-file → cold-cache fallback.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (AsyncFetchQueue, CacheConfig, SyncCounter,
                        bowtie_query, choose_plan, clftj_count,
                        clftj_evaluate, cycle_query, engine, path_query)
from repro.core.cached_frontier import JaxCachedTrieJoin
from repro.core.db import graph_db
from repro.core.distributed import StaticCLFTJ
from repro.core.frontier import JaxTrieJoin


@pytest.fixture(scope="module")
def db():
    from repro.data.graphs import zipf_graph
    return graph_db(zipf_graph(16, 110, 1.1, seed=314))


PAY = CacheConfig(policy="setassoc", slots=256, assoc=4,
                  cache_payloads=True, payload_rows=1 << 13)


def _tuple_set(rows):
    return {tuple(map(int, r)) for r in np.asarray(rows).tolist()}


# ---------------------------------------------------------------------------
# AsyncFetchQueue
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_async_queue_fifo_bound_and_drain():
    q = AsyncFetchQueue(max_in_flight=3)
    got = []
    for i in range(10):
        got.extend(q.put(jnp.full((4,), i), f"blk{i}"))
        assert q.in_flight <= 3
    got.extend(q.drain())
    assert q.in_flight == 0 and q.issued == 10
    assert q.high_water <= 3
    # FIFO: host values arrive in exact issue order
    assert [int(x[0]) for x in got] == list(range(10))


@pytest.mark.tier1
def test_async_queue_poll_preserves_order():
    q = AsyncFetchQueue(max_in_flight=8)
    for i in range(5):
        assert q.put(jnp.full((2,), i), "b") == []
    out = list(q.poll()) + list(q.drain())
    assert [int(x[0]) for x in out] == list(range(5))


def test_async_queue_rejects_nonpositive_bound():
    with pytest.raises(ValueError):
        AsyncFetchQueue(max_in_flight=0)


@pytest.mark.tier1
def test_async_issues_counted_separately_from_blocking_syncs():
    from repro.core.hostsync import device_get, device_get_async
    with SyncCounter() as sc:
        h = device_get_async(jnp.arange(8), "async-lbl")
        device_get(jnp.arange(8), "blocking-lbl")
        np.testing.assert_array_equal(h.get(), np.arange(8))
    assert sc.count == 1 and sc.async_count == 1
    assert sc.label_counts == {"async-lbl": 1, "blocking-lbl": 1}
    # completion (h.get()) did not add any event
    assert sum(sc.label_counts.values()) == 2


# ---------------------------------------------------------------------------
# evaluate_stream vs one-shot evaluate
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_stream_matches_one_shot_identical_order_lftj(db):
    q = cycle_query(4)
    order = sorted(q.variables)
    one = list(JaxTrieJoin(q, order, db, capacity=1 << 8).evaluate())
    st = list(JaxTrieJoin(q, order, db, capacity=1 << 8).evaluate_stream())
    assert np.array_equal(np.concatenate(one), np.concatenate(st))


@pytest.mark.tier1
@pytest.mark.parametrize("cfg", [None, PAY], ids=["nocache", "payload"])
def test_stream_matches_one_shot_cached_engine(db, cfg):
    """Streaming only moves the output data plane: rows, order, count,
    and the tier-2 stats of a double pass must match the one-shot path
    (second pass exercises splice-on-hit through the stream)."""
    q = bowtie_query()
    td, order = choose_plan(q, db.stats())
    eng_one = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8,
                                cache=cfg)
    eng_st = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8,
                               cache=cfg)
    for run in (1, 2):
        one = list(eng_one.evaluate())
        st = list(eng_st.evaluate_stream())
        a = (np.concatenate(one) if one
             else np.zeros((0, len(order)), np.int32))
        b = (np.concatenate(st) if st
             else np.zeros((0, len(order)), np.int32))
        assert np.array_equal(a, b), f"run {run}"
    if cfg is not None:
        assert eng_st.stats["tier2_replay_hits"] > 0
        assert (eng_st.stats["tier2_replay_hits"]
                == eng_one.stats["tier2_replay_hits"])


@pytest.mark.tier1
def test_stream_respects_emit_in_flight_bound(db):
    """The executor's queue (exposed as ``last_executor.emit_queue``)
    must actually carry every block under the configured bound — a
    regression that ignores ``emit_in_flight`` or bypasses the queue
    fails here, not just in a perf trace."""
    q = path_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 6,
                            emit_in_flight=2)
    n = sum(b.shape[0] for b in eng.evaluate_stream())
    ex = eng.last_executor
    assert n == clftj_count(q, td, order, db)
    assert ex.emitted_blocks > 2, "workload too small to exercise the bound"
    q_ = ex.emit_queue
    assert q_.max_in_flight == 2
    assert q_.issued == ex.emitted_blocks
    assert 1 <= q_.high_water <= 2, q_.high_water
    assert q_.in_flight == 0  # fully drained


def test_facade_stream_result_totals(db):
    """engine.evaluate_stream: streamed rows == one-shot tuples, and the
    post-exhaustion Result carries identical count + tier-2 counters."""
    q = bowtie_query()
    res = engine.evaluate(q, db, algorithm="clftj", backend="jax",
                          capacity=1 << 8, cache=PAY)
    rs = engine.evaluate_stream(q, db, capacity=1 << 8, cache=PAY)
    assert rs.result is None  # not exhausted yet
    rows = [b for b in rs]
    got = np.concatenate(rows) if rows else np.zeros((0, 1))
    assert _tuple_set(got) == _tuple_set(res.tuples)
    assert rs.result is not None and rs.result.count == res.count
    assert rs.result.tuples is None
    assert rs.result.counters.keys() == res.counters.keys()
    assert rs.result.order == res.order


def test_facade_stream_rejects_host_backends(db):
    with pytest.raises(ValueError, match="JAX"):
        engine.evaluate_stream(bowtie_query(), db, backend="ref")
    with pytest.raises(ValueError, match="JAX"):
        engine.evaluate_stream(bowtie_query(), db, algorithm="ytd")


# ---------------------------------------------------------------------------
# execute_static evaluation (payload-capable)
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@pytest.mark.parametrize("qname,qf", [("bowtie", bowtie_query()),
                                      ("cycle5", cycle_query(5)),
                                      ("path4", path_query(4))],
                         ids=["bowtie", "cycle5", "path4"])
def test_static_evaluate_matches_oracle_cold_and_warm(db, qname, qf):
    td, order = choose_plan(qf, db.stats())
    want = _tuple_set(np.asarray(clftj_evaluate(qf, td, order, db),
                                 np.int64).reshape(-1, len(order)))
    eng = StaticCLFTJ(qf, td, order, db, capacity=1 << 13, cache=PAY)
    rows, stats, tables = eng.evaluate_static()
    assert not stats["overflow"], qname
    assert _tuple_set(rows) == want and rows.shape[0] == len(want), qname
    rows2, stats2, _ = eng.evaluate_static(tables)
    assert _tuple_set(rows2) == want and rows2.shape[0] == len(want), qname
    assert stats2["count"] == stats["count"] == len(want)


@pytest.mark.tier1
def test_static_evaluate_warm_pass_serves_replay_hits(db):
    """The acceptance-criterion path: on a recurring-bag query the warm
    static pass must report tier2_replay_hits > 0 — payload caching is
    genuinely on in trace-time evaluation, not silently bypassed."""
    q = bowtie_query()
    td, order = choose_plan(q, db.stats())
    eng = StaticCLFTJ(q, td, order, db, capacity=1 << 13, cache=PAY)
    _, stats, tables = eng.evaluate_static()
    assert stats["tier2_replay_hits"] == 0  # cold: nothing resident yet
    _, stats2, _ = eng.evaluate_static(tables)
    assert stats2["tier2_replay_hits"] > 0


@pytest.mark.tier1
def test_static_evaluate_bypasses_count_only_tables(db):
    """Optionality: a payloads-off cache config must leave evaluation
    untouched (count tables cannot replay tuples) while staying exact."""
    q = bowtie_query()
    td, order = choose_plan(q, db.stats())
    want = _tuple_set(np.asarray(clftj_evaluate(q, td, order, db),
                                 np.int64).reshape(-1, len(order)))
    cfg = CacheConfig(policy="setassoc", slots=256, assoc=4)  # no payloads
    eng = StaticCLFTJ(q, td, order, db, capacity=1 << 13, cache=cfg)
    tables = None
    for _ in range(2):
        rows, stats, tables = eng.evaluate_static(tables)
        assert _tuple_set(rows) == want
        assert stats["tier2_replay_hits"] == 0


@pytest.mark.tier1
def test_static_evaluate_flags_overflow_on_tiny_capacity(db):
    """No silent truncation: when the result cannot fit the fixed chunk,
    the overflow flag must be set — on the cold pass (replay overflow)
    AND the warm pass (splice overflow, which the jitted splice step
    clamps without telling)."""
    q = bowtie_query()
    td, order = choose_plan(q, db.stats())
    want_n = clftj_count(q, td, order, db)
    cap = 1 << 6
    assert want_n > cap, "fixture too small to force overflow"
    eng = StaticCLFTJ(q, td, order, db, capacity=cap, cache=PAY)
    _, stats, tables = eng.evaluate_static()
    assert stats["overflow"]
    _, stats2, _ = eng.evaluate_static(tables)
    assert stats2["overflow"]


@pytest.mark.tier1
def test_static_evaluate_dedup_off_conforms(db):
    """Tier-1 off: duplicate adhesion keys must still store exactly one
    block each (the in-trace first-occurrence collapse), with exact
    tuples both passes."""
    q = bowtie_query()
    td, order = choose_plan(q, db.stats())
    want = _tuple_set(np.asarray(clftj_evaluate(q, td, order, db),
                                 np.int64).reshape(-1, len(order)))
    eng = StaticCLFTJ(q, td, order, db, capacity=1 << 13, cache=PAY,
                      dedup=False)
    tables = None
    for _ in range(2):
        rows, stats, tables = eng.evaluate_static(tables)
        assert not stats["overflow"]
        assert _tuple_set(rows) == want and rows.shape[0] == len(want)


# ---------------------------------------------------------------------------
# measured-autotune sidecar persistence
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_registry():
    from repro.kernels import registry
    saved = registry.autotune_cache()
    registry.clear_autotune_cache()
    yield registry
    registry.clear_autotune_cache()
    registry._AUTOTUNE.update(saved)


def _spec(registry, cap=512):
    return registry.ExpandSpec(capacity=cap, n_vars=3, n_atoms=3,
                               n_others=1, dtype="int32", x64=True)


def _measured(registry, key, choice):
    """Record a decision as if a timing run produced it (only measured
    decisions persist — the heuristic defaults stay process-local)."""
    registry._AUTOTUNE[key] = choice
    registry._MEASURED.add(key)


@pytest.mark.tier1
def test_autotune_sidecar_roundtrip(fresh_registry, tmp_path):
    registry = fresh_registry
    path = str(tmp_path / "autotune.json")
    _measured(registry, (_spec(registry), "tpu"), "pallas")
    _measured(registry, (_spec(registry, cap=1024), "cpu"), "xla")
    assert registry.save_autotune_cache(path) == path
    registry.clear_autotune_cache()
    assert registry.autotune_cache() == {}
    assert registry.load_autotune_cache(path) == 2
    assert registry.autotune_cache()[(_spec(registry), "tpu")] == "pallas"
    assert registry.autotune_cache()[
        (_spec(registry, cap=1024), "cpu")] == "xla"


def test_autotune_sidecar_in_memory_wins(fresh_registry, tmp_path):
    registry = fresh_registry
    path = str(tmp_path / "autotune.json")
    key = (_spec(registry), "tpu")
    _measured(registry, key, "pallas")
    registry.save_autotune_cache(path)
    registry.clear_autotune_cache()
    _measured(registry, key, "xla")  # this process re-measured
    assert registry.load_autotune_cache(path) == 0
    assert registry.autotune_cache()[key] == "xla"


def test_autotune_sidecar_never_persists_heuristics_or_clobbers(
        fresh_registry, tmp_path):
    """Unmeasured (platform-default) decisions must not be written — a
    persisted guess would pre-empt a later measure=True run — and a save
    merges the on-disk entries (in-memory wins), so concurrent processes
    can never clobber each other's measurements."""
    registry = fresh_registry
    path = str(tmp_path / "autotune.json")
    key_a = (_spec(registry), "tpu")
    _measured(registry, key_a, "pallas")
    registry.save_autotune_cache(path)
    registry.clear_autotune_cache()
    # a heuristic-only cache: the save merges the file's measured entry
    # back in and re-writes it — the heuristic itself never lands
    heuristic_key = (_spec(registry, cap=128), "cpu")
    registry._AUTOTUNE[heuristic_key] = "xla"
    registry.save_autotune_cache(path)
    registry.clear_autotune_cache()
    assert registry.load_autotune_cache(path) == 1  # original entry intact
    assert key_a in registry.autotune_cache()
    assert heuristic_key not in registry.autotune_cache()
    # save with no path configured and nothing measured stays a no-op
    registry.clear_autotune_cache()
    assert registry.save_autotune_cache(str(tmp_path / "new.json")) is None
    # concurrent-writer simulation: B measures Y with A's entry on disk;
    # B's write-through must preserve A's measurement
    registry.clear_autotune_cache()
    key_b = (_spec(registry, cap=2048), "gpu")
    _measured(registry, key_b, "xla")
    registry.save_autotune_cache(path)
    registry.clear_autotune_cache()
    assert registry.load_autotune_cache(path) == 2
    assert registry.autotune_cache()[key_a] == "pallas"
    assert registry.autotune_cache()[key_b] == "xla"


@pytest.mark.tier1
def test_autotune_sidecar_corrupt_file_falls_back(fresh_registry, tmp_path):
    """A broken sidecar is a cold cache, never a crash: truncated JSON,
    wrong schema, and per-entry garbage all degrade gracefully."""
    registry = fresh_registry
    path = str(tmp_path / "autotune.json")
    with open(path, "w") as f:
        f.write('{"version": 1, "entries": [{"spec":')  # truncated
    with pytest.warns(UserWarning, match="autotune sidecar"):
        assert registry.load_autotune_cache(path) == 0
    with open(path, "w") as f:
        json.dump({"version": 1, "entries": "nope"}, f)
    with pytest.warns(UserWarning, match="autotune sidecar"):
        assert registry.load_autotune_cache(path) == 0
    # bad entries are skipped individually; good ones still load
    good = {"spec": {"capacity": 256, "n_vars": 2, "n_atoms": 2,
                     "n_others": 1, "dtype": "int32", "x64": False},
            "platform": "gpu", "choice": "pallas"}
    bad_choice = dict(good, choice="cuda")
    bad_spec = {"spec": {"capacity": 1}, "platform": "gpu",
                "choice": "xla"}
    with open(path, "w") as f:
        json.dump({"version": 1,
                   "entries": [bad_spec, bad_choice, good, 7]}, f)
    assert registry.load_autotune_cache(path) == 1
    key = (registry.ExpandSpec(capacity=256, n_vars=2, n_atoms=2,
                               n_others=1, dtype="int32", x64=False), "gpu")
    assert registry.autotune_cache()[key] == "pallas"
    # a missing file is silent (no warning, no entries)
    assert registry.load_autotune_cache(str(tmp_path / "absent.json")) == 0


def test_autotune_env_var_autoload_and_heuristic_hygiene(fresh_registry,
                                                         tmp_path,
                                                         monkeypatch):
    """$REPRO_AUTOTUNE_CACHE: select_expand consults the sidecar before
    deciding, and heuristic (unmeasured) resolutions never leak into it."""
    registry = fresh_registry
    path = str(tmp_path / "autotune.json")
    key_spec, platform = _spec(registry), "tpu"
    _measured(registry, (key_spec, platform), "pallas")
    registry.save_autotune_cache(path)
    registry.clear_autotune_cache()
    monkeypatch.setenv(registry.AUTOTUNE_CACHE_ENV, path)
    # loaded lazily at the first auto dispatch: no measurement happens
    # (builders=None would otherwise pick the platform default)
    got = registry.select_expand(key_spec, mode="auto", platform=platform,
                                 measure=False)
    assert got == "pallas"  # the persisted decision, not the cpu default
    # a heuristic decision for a new spec stays process-local: the
    # sidecar keeps exactly the one measured entry
    spec2 = registry.ExpandSpec(capacity=64, n_vars=2, n_atoms=2,
                                n_others=0, dtype="int32", x64=False)
    assert registry.select_expand(spec2, mode="auto", platform="cpu",
                                  measure=False) == "xla"
    registry.clear_autotune_cache()
    monkeypatch.delenv(registry.AUTOTUNE_CACHE_ENV)
    assert registry.load_autotune_cache(path) == 1


def test_async_queue_reset_and_per_label_accounting():
    """A drained-then-reused queue must report each session's issue
    counts alone: ``reset`` rezeroes issued/high_water/labels, refuses
    while fetches are in flight, and keeps working afterwards."""
    q = AsyncFetchQueue(max_in_flight=2)
    for i in range(5):
        q.put(jnp.arange(4, dtype=jnp.int32) + i, "sess-a")
    list(q.drain())
    assert q.issued == 5 and q.labels["sess-a"] == 5
    assert q.high_water == 2 and q.in_flight == 0
    q.reset()
    assert q.issued == 0 and q.high_water == 0 and not q.labels
    q.put(jnp.zeros((3,), jnp.int32), "sess-b")
    with pytest.raises(RuntimeError, match="in flight"):
        q.reset()
    list(q.drain())
    q.reset()
    assert q.labels == {} and q.issued == 0


def test_async_queue_double_buffer_recycles_host_arrays():
    """``double_buffer=True``: completions land in a bounded ring of
    preallocated host buffers per (shape, dtype) — correct values at
    receipt, with backing arrays recycled instead of freshly allocated
    per block."""
    bound = 2
    q = AsyncFetchQueue(max_in_flight=bound, double_buffer=True)
    vals, ids = [], []
    for i in range(7):
        for done in q.put(jnp.full((8,), i, jnp.int32), "x"):
            vals.append(int(done[0]))  # read at receipt: buffer is reused
            ids.append(id(done))
            assert (np.asarray(done) == done[0]).all()
    for done in q.drain():
        vals.append(int(done[0]))
        ids.append(id(done))
    assert vals == list(range(7)), "arrival order/values must be exact"
    assert len(set(ids)) <= max(2, bound), \
        "host buffers must be recycled, not freshly allocated per block"


def test_stream_queue_persists_across_sessions_with_per_pass_counts(db):
    """The engine keeps ONE emit queue across streaming passes (its
    double-buffered staging arrays are the point of reuse), while each
    pass's accounting reads per-session: issued/labels equal that pass's
    emitted blocks, not the running total."""
    q = path_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 9)
    n1 = sum(b.shape[0] for b in eng.evaluate_stream())
    q1 = eng.last_executor.emit_queue
    assert q1.issued == eng.last_executor.emitted_blocks > 0
    assert q1.labels["emit-stream"] == q1.issued
    n2 = sum(b.shape[0] for b in eng.evaluate_stream())
    q2 = eng.last_executor.emit_queue
    assert q2 is q1, "the queue must persist on the engine"
    assert n1 == n2
    assert q2.issued == eng.last_executor.emitted_blocks, \
        "per-session accounting must reset, not accumulate"
    assert q2.labels["emit-stream"] == q2.issued
