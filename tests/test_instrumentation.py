"""The program's own instrumentation, on the CPU: host spans in a profiler
trace (one ``clftj.sync.<label>`` span per counted sync, ``clftj.op.*``
around the schedule's ops), the EXPAND work counters against a host
enumeration, counters that ride fetches already made, and the XLA steps'
module names and named scopes."""
import dataclasses
import glob
import json
import os
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from repro.core import (CacheConfig, SyncCounter, bowtie_query, choose_plan,
                        cycle_query)
from repro.core.cached_frontier import JaxCachedTrieJoin
from repro.core.db import graph_db

ROOT = Path(__file__).resolve().parents[1]
BOWTIE_ORDER = ("x2", "x3", "x1", "x4", "x5")
# the syncs of the schedule's own ops in count mode: no counter's fetch
OP_LABELS = {"expand-plan", "expand-split", "expand-admit", "fold-admit",
             "emit-total"}


@pytest.fixture(scope="module")
def db():
    rng = np.random.default_rng(4242)
    return graph_db(rng.integers(0, 24, size=(160, 2)))


def _bowtie(db, capacity=1 << 7, **cache):
    q = bowtie_query()
    td, _ = choose_plan(q, db.stats())
    cfg = CacheConfig(policy="setassoc", slots=256, assoc=4, **cache)
    return JaxCachedTrieJoin(q, td, BOWTIE_ORDER, db, capacity=capacity,
                             cache=cfg)


def test_served_count_trace_has_op_spans_and_one_span_per_sync(db, tmp_path):
    import jax
    from jax.profiler import ProfileData

    from repro.configs import paper_clftj
    from repro.core import engine

    q = bowtie_query()
    td, _ = choose_plan(q, db.stats())
    cfg = dataclasses.replace(paper_clftj.TPU_SERVE,
                              frontier_capacity=1 << 7, cache_slots=256,
                              payload_rows=1 << 12)
    server = engine.serve(db, cfg)
    try:
        server.submit(q, "count", td, BOWTIE_ORDER).result(120)  # warm
        jax.profiler.start_trace(str(tmp_path))
        try:
            sess = server.submit(q, "count", td, BOWTIE_ORDER)
            sess.result(120)
        finally:
            jax.profiler.stop_trace()
    finally:
        server.close()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    # on the CPU the trace has no device plane for bench.trace.reduce:
    # count the host planes' events, as it collects its spans
    names = Counter(ev.name for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events)
    for op in ("expand", "enter", "fold", "emit"):
        assert names[f"clftj.op.{op}"] > 0, op
    assert names["clftj.serve.plan"] == names["clftj.serve.execute"] == 1
    assert names["clftj.admit"] > 0
    syncs = {n[len("clftj.sync."):]: k for n, k in names.items()
             if n.startswith("clftj.sync.")}
    assert syncs == dict(sess.sync.label_counts)
    assert sum(syncs.values()) == sess.sync.count


def _host_expand_work(eng):
    """Per EXPAND depth, by plain enumeration of the frontier's prefixes:
    (rows entering, (row, candidate) pairs of the guard, rows surviving)."""
    rows = [dict()]
    out = []
    for d, x in enumerate(eng.order):
        parts = eng.at_depth[d]
        g_ai, _ = parts[eng.guard[d]]
        n_in, n_pairs, nxt = len(rows), 0, []
        for r in rows:
            def values(ai):
                vs = eng.atom_vars[ai]
                rel = eng.atom_rows[ai]
                mask = np.ones(len(rel), bool)
                for c, v in enumerate(vs):
                    if v in r:
                        mask &= rel[:, c] == r[v]
                return set(rel[mask, vs.index(x)].tolist())
            cands = values(g_ai)
            n_pairs += len(cands)
            for ai, _ in parts:
                cands &= values(ai)
            nxt.extend(dict(r, **{x: v}) for v in sorted(cands))
        out.append((n_in, n_pairs, len(nxt)))
        rows = nxt
    return out


@pytest.mark.parametrize("capacity", [1 << 12, 1 << 5])
def test_expand_counters_match_a_host_enumeration(db, capacity):
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=capacity,
                            cache=CacheConfig(slots=0))
    assert not any(op.kind == "enter_child" for op in eng.schedule.ops)
    eng.count()
    want = np.sum(_host_expand_work(eng), axis=0)
    assert want[1] > capacity or capacity > 1 << 10  # small one splits
    got = (eng.stats["expand_rows_in"], eng.stats["expand_candidates"],
           eng.stats["expand_rows_out"])
    assert got == tuple(int(w) for w in want)


def test_count_mode_counters_ride_the_answer_fetch(db):
    eng = _bowtie(db)
    with SyncCounter() as sc:
        eng.count()
    labels = sc.label_counts
    assert "stats-t1" not in labels and "cache-stats" not in labels
    # every sync is one of the schedule's own: no counter adds one
    assert set(labels) <= OP_LABELS and labels["emit-total"] == 1
    assert sc.count == sum(labels.values())
    # the counters equal what their own fetches read
    ex = eng.last_executor
    fresh = eng.cache.stats()
    assert eng.stats["tier2_probes"] == fresh["probes"] > 0
    assert eng.stats["tier2_hits"] == fresh["hits"]
    assert eng.stats["tier2_inserts"] == fresh["inserts"]
    assert eng.stats["tier1_rows_collapsed"] == int(ex._t1_collapsed) > 0


def test_evaluate_counters_ride_the_row_fetch_and_stream_keeps_its_own(db):
    eng = _bowtie(db, cache_payloads=True, payload_rows=1 << 12)
    with SyncCounter() as one:
        n1 = sum(len(b) for b in eng.evaluate())
    s1 = dict(eng.stats)
    with SyncCounter() as st:
        n2 = sum(len(b) for b in eng.evaluate_stream())
    assert n1 == n2 > 0
    assert "stats-t1" not in one.label_counts
    assert "cache-stats" not in one.label_counts
    assert one.label_counts["emit-rows"] == 1
    # a stream has no final fetch to ride: it keeps the counters' own
    assert st.label_counts["stats-t1"] == 1
    assert st.label_counts["cache-stats"] == len(eng.cache.tables)
    assert s1["tier2_probes"] > 0


def test_xla_steps_lower_to_named_modules_with_scopes():
    import jax
    import jax.numpy as jnp

    from repro.core.frontier import Frontier
    from repro.kernels.emit import xla as emit_xla
    from repro.kernels.expand import xla as expand_xla
    from repro.kernels.fold import xla as fold_xla

    C, n, m = 16, 3, 3
    with jax.enable_x64(True):
        F = Frontier(assign=jnp.zeros((C, n), jnp.int32),
                     factor=jnp.ones((C,), jnp.int64),
                     valid=jnp.ones((C,), bool),
                     orig=jnp.arange(C, dtype=jnp.int32),
                     lo=jnp.zeros((C, m), jnp.int32),
                     hi=jnp.ones((C, m), jnp.int32))
        idx = jnp.arange(C, dtype=jnp.int32)
        slab = jnp.zeros((33, 2), jnp.int32)
        fold = fold_xla.build(d0=1, d1=2, with_replay=True, with_splice=True)
        low = fold.lower(F, F.valid, idx, F, F.valid, idx, idx, slab)
        emit = emit_xla.build().lower(F.assign, F.valid)
        col = jnp.arange(C, dtype=jnp.int32)
        rank = jnp.arange(C + 1, dtype=jnp.int32)
        expand = expand_xla.expand_step.lower(
            F, col, col, rank, (col,), d=0, g_ai=0, other_ais=(1,),
            n_rows_g=C, impl="bsearch")
    for lowered, module, scopes in (
            (low, "jit_fold_step", ("replay", "splice", "merge")),
            (emit, "jit_emit_step", ("pack",)),
            (expand, "jit_expand_step", ("layout", "verify", "compact"))):
        text = lowered.as_text(debug_info=True)
        assert f"module @{module} " in text
        for s in scopes:
            assert f"/{s}/" in text, (module, s)


# the mutual 2-hop sessions' sync labels (blocking and async alike), as
# the schedule made them before the FOLD counters: one root bag of three
# EXPANDs, one fold, sixteen 2^10-row blocks
_ROOT_BAG = {"expand-plan": 3, "expand-admit": 3, "fold-admit": 1}
STREAM_LABELS_COLD = dict(_ROOT_BAG, **{
    "replay-plan-async": 2, "emit-stream": 16, "cache-stats": 1,
    "stats-t1": 1})
STREAM_LABELS_WARM = dict(STREAM_LABELS_COLD, **{"replay-plan-async": 1})
COUNT_LABELS = dict(_ROOT_BAG, **{"emit-total": 1})
EVALUATE_LABELS = dict(_ROOT_BAG, **{"replay-plan": 1, "emit-rows": 1})


def _mutual_2hop_sessions(db_edges):
    """Serve the mutual 2-hop stream twice (cold, then warm), a count and
    an evaluation at TPU_SERVE's settings cut small; each session's rows,
    counters and sync labels."""
    from repro.configs import paper_clftj
    from repro.core import engine
    from repro.core.cq import CQ, Atom

    q = CQ(tuple(Atom("E", v) for v in (("x1", "x2"), ("x2", "x1"),
                                         ("x2", "x3"), ("x3", "x2"))))
    db = graph_db(db_edges)
    td, order = choose_plan(q, db.stats())
    cfg = dataclasses.replace(paper_clftj.TPU_SERVE,
                              frontier_capacity=1 << 10, cache_slots=512,
                              payload_rows=1 << 12)
    server = engine.serve(db, cfg)
    out = []
    try:
        for mode in ("stream", "stream", "count", "evaluate"):
            sess = server.submit(q, mode, td, order)
            blocks = list(sess.blocks()) if mode == "stream" else []
            res = sess.result(300)
            rows = (np.concatenate(blocks) if blocks else res.tuples)
            out.append((mode, rows, res, sess))
    finally:
        server.close()
    return q, out


def test_stream_fold_counters_replay_cold_and_splice_warm():
    """``fold_rows_replayed`` and ``fold_rows_spliced`` on a served mutual
    2-hop stream: the cold one replays every row from subtree exits (the
    root bag's rows fit one chunk, so no parent morsel probes after an
    insert), the warm one splices every row from tier-2 payloads.  The
    counters add no sync and no async issue: each mode's labels are the
    ones the schedule made before they existed."""
    from bench import graphs, reference

    config = json.loads((ROOT / "bench" / "configs" / "gap-kron.json")
                        .read_text())
    graph = dict(config, scale=6)
    nv = graphs.vertices(graph)
    edges = graphs.build(graph, 2 ** 31 + 11)
    assert len(edges) == 754 < 1 << 10
    q, runs = _mutual_2hop_sessions(edges)
    (_, cold_rows, cold, cold_s), (_, warm_rows, warm, warm_s), \
        (_, _, count, count_s), (_, ev_rows, ev, ev_s) = runs
    query = [[a.relation, *a.vars] for a in q.atoms]
    want = reference.packed(
        reference.join_rows(query, edges, nv, cold_s.order), nv)
    assert len(want) == 15932
    for rows in (cold_rows, warm_rows, ev_rows):
        assert reference.row_gap(rows, want, nv) == 0
    c, w = cold.counters, warm.counters
    assert c["fold_calls_xla"] > 1 and w["fold_calls_xla"] > 1
    assert (c["fold_rows_replayed"], c["fold_rows_spliced"]) == (15932, 0)
    assert (w["fold_rows_replayed"], w["fold_rows_spliced"]) == (0, 15932)
    assert c["tier2_replay_hits"] == 0 < w["tier2_replay_hits"]
    assert ev.counters["fold_rows_spliced"] == 15932
    assert count.count == 15932
    assert (count.counters["fold_rows_replayed"],
            count.counters["fold_rows_spliced"]) == (0, 0)
    # the labels each mode made before the counters existed
    assert dict(cold_s.sync.label_counts) == STREAM_LABELS_COLD
    assert dict(warm_s.sync.label_counts) == STREAM_LABELS_WARM
    assert dict(count_s.sync.label_counts) == COUNT_LABELS
    assert dict(ev_s.sync.label_counts) == EVALUATE_LABELS
    for s in (cold_s, warm_s, count_s, ev_s):
        assert s.sync.count + s.sync.async_count == sum(
            s.sync.label_counts.values())
