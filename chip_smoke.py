"""Bring-up check: the join engine's main path on a TPU.

    python chip_smoke.py              # one chip: three requests to a JoinServer
    python chip_smoke.py --chips 4    # four chips: the distributed count only

Both build a power-law directed graph shaped like SNAP ego-Twitter, the
paper's largest graph (§5.2.1): 81,306 vertices and 1,768,149 distinct
edges, Zipf-popular endpoints, made from ``--seed`` by
``repro.data.graphs.zipf_digraph`` and loaded with ``graph_db``.  Every
answer is checked against a ``scipy.sparse`` computation that shares no
code with the engine.

One chip (no arguments) opens ``engine.serve`` and asks it for
  1. the triangle count;
  2. the bowtie count, a query whose child bag recurs, so tier 2 must
     hit (``tier2_hits > 0``);
  3. a streamed evaluation of the mutual-follow 2-hop query with payload
     replay on, twice: every row must hold in the edge set, the rows must
     be distinct and as many as the host count, and the second stream (a
     plan-cache hit) must replay cached row blocks.

``--chips 4`` runs ``make_distributed_count`` of the mutual-follow pair
query over a 4-device mesh and compares it with the host count; no shard
may overflow its static capacity.

The script exits non-zero, without a result line, when the first device
is not a TPU, on any error and on any wrong answer.  On success the last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np
import scipy.sparse as sp

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# ego-Twitter (SNAP): |V| and |E|; Zipf exponent 0.45 puts the largest
# out- and in-degree near 1,900, between SNAP's 1,205 out and 3,383 in
N_VERTICES = 81_306
N_EDGES = 1_768_149
ZIPF_A = 0.45
DIST_CAPACITY = 1 << 20   # static per-shard frontier rows (--chips 4)
SERVE_QUERIES = ("triangle", "bowtie", "mutual_2hop")
BOWTIE_ORDER = ("x2", "x3", "x1", "x4", "x5")


def log(msg: str) -> None:
    print(msg, flush=True)


def build_graph(seed: int, nv: int = N_VERTICES,
                ne: int = N_EDGES) -> np.ndarray:
    from repro.data.graphs import zipf_digraph

    return zipf_digraph(nv, ne, ZIPF_A, seed=seed)


# -- queries and their host counts (scipy.sparse, independent of the engine)


def queries():
    from repro.core import bowtie_query, cycle_query, path_query
    from repro.core.cq import CQ, Atom

    mutual = (Atom("E", ("x1", "x2")), Atom("E", ("x2", "x1")))
    mutual_2hop = CQ(mutual + (Atom("E", ("x2", "x3")),
                               Atom("E", ("x3", "x2"))))
    return {"triangle": cycle_query(3), "bowtie": bowtie_query(),
            "mutual_2hop": mutual_2hop, "mutual": CQ(mutual)}


def host_counts(edges: np.ndarray, nv: int, names) -> dict:
    """Counts of the named queries of :func:`queries` by sparse algebra.

    ``triangle`` is E(x1,x2),E(x2,x3),E(x1,x3): t[c] = row c of
    (A·A)∘A summed, and the bowtie joins two such triangles at x1, so it
    is the sum of t².  ``mutual_2hop`` is the sum of squared mutual
    degrees (M = A∘Aᵀ) and ``mutual`` counts M's entries."""
    A = sp.csr_matrix((np.ones(len(edges), np.int64),
                       (edges[:, 0], edges[:, 1])), shape=(nv, nv))
    out = {}
    if {"triangle", "bowtie"} & set(names):
        t = np.zeros(nv, np.int64)
        for r0 in range(0, nv, 4096):  # row blocks bound A·A's fill-in
            blk = A[r0:r0 + 4096]
            t[r0:r0 + 4096] = np.asarray(
                (blk @ A).multiply(blk).sum(axis=1)).ravel()
        out["triangle"], out["bowtie"] = int(t.sum()), int((t * t).sum())
    M = A.multiply(A.T).tocsr()
    m = np.asarray(M.sum(axis=1)).ravel().astype(np.int64)
    out["mutual_2hop"], out["mutual"] = int((m * m).sum()), int(M.nnz)
    return {k: out[k] for k in names}


def check_rows(rows: np.ndarray, order, q, edges: np.ndarray,
               nv: int) -> None:
    """Every row satisfies every atom of ``q`` against the edge set, and
    no row repeats."""
    keys = np.sort(edges[:, 0] * nv + edges[:, 1])
    col = {v: i for i, v in enumerate(order)}
    r = rows.astype(np.int64)
    for atom in q.atoms:
        k = r[:, col[atom.vars[0]]] * nv + r[:, col[atom.vars[1]]]
        pos = np.clip(np.searchsorted(keys, k), 0, len(keys) - 1)
        bad = int((keys[pos] != k).sum())
        if bad:
            raise AssertionError(f"{bad} rows violate {atom}")
    packed = np.zeros(len(r), np.int64)
    for i in range(r.shape[1]):
        packed = packed * nv + r[:, i]
    if np.unique(packed).size != len(r):
        raise AssertionError("streamed rows repeat")


# -- reporting


def paths(res) -> str:
    c = res.counters
    return (f"expand_paths={res.expand_paths} fold_paths={res.fold_paths} "
            f"emit_calls_pallas={c.get('emit_calls_pallas', 0)} "
            f"emit_calls_xla={c.get('emit_calls_xla', 0)}")


def tier2(res) -> str:
    c = res.counters
    return " ".join(f"{k}={c.get(k, 0)}" for k in (
        "tier2_probes", "tier2_hits", "tier2_misses", "tier2_inserts",
        "tier2_replay_hits", "tier1_rows_collapsed"))


def timing(res) -> str:
    return (f"compile_s={res.compile_s!r} exec_s={res.exec_s!r} "
            f"wall_s={res.wall_s!r} plan_cache_hit={res.plan_cache_hit}")


def expect(name: str, got: int, want: int) -> None:
    if got != want:
        raise AssertionError(f"{name}: engine {got} != host {want}")


# -- phases


def serve_phase(edges: np.ndarray, nv: int, want: dict, config=None) -> None:
    """The one-chip path: three requests to a JoinServer."""
    from repro.core import choose_plan, engine
    from repro.core.db import graph_db

    q = queries()
    t0 = time.perf_counter()
    db = graph_db(edges)
    log(f"graph_db: |E|={db.size('E')} in {time.perf_counter() - t0!r}s")
    with engine.serve(db, config) as srv:
        for name in ("triangle", "bowtie"):
            td, order = choose_plan(q[name], db.stats())
            if name == "bowtie":
                # x1, the bags' shared vertex, after the root bag's other
                # vertices: its values then recur across frontier chunks
                # instead of arriving sorted, and tier 2 answers them
                order = BOWTIE_ORDER
            res = srv.count(q[name], td, order)
            log(f"{name} count={res.count} host={want[name]} "
                f"{timing(res)}")
            log(f"  {paths(res)}")
            log(f"  {tier2(res)}")
            expect(name, res.count, want[name])
        if res.counters["tier2_hits"] <= 0:
            raise AssertionError("bowtie: tier 2 never hit")
        for rnd in (1, 2):
            sess = srv.evaluate_stream(q["mutual_2hop"])
            blocks = list(sess.blocks())
            res = sess.result()
            rows = (np.concatenate(blocks) if blocks
                    else np.zeros((0, 3), np.int32))
            log(f"mutual_2hop stream {rnd}: rows={len(rows)} "
                f"blocks={len(blocks)} host={want['mutual_2hop']} "
                f"{timing(res)}")
            log(f"  {paths(res)}")
            log(f"  {tier2(res)}")
            expect("mutual_2hop", len(rows), want["mutual_2hop"])
            expect("mutual_2hop result", res.count, want["mutual_2hop"])
            check_rows(rows, sess.order, q["mutual_2hop"], edges, nv)
        if not res.plan_cache_hit or res.tier2_replay_hits <= 0:
            raise AssertionError("mutual_2hop: the warm stream did not "
                                 "replay cached row blocks")


def distributed_phase(edges: np.ndarray, nv: int, want: dict,
                      n_devices: int = 4,
                      capacity: int = DIST_CAPACITY) -> None:
    """The four-chip path: one distributed count over a device mesh."""
    import jax

    from repro.core import choose_plan
    from repro.core.db import graph_db
    from repro.core.distributed import make_distributed_count
    from repro.launch.mesh import make_local_mesh

    if len(jax.devices()) != n_devices:
        raise RuntimeError(f"needs {n_devices} devices, "
                           f"found {len(jax.devices())}")
    q = queries()["mutual"]
    db = graph_db(edges)
    td, order = choose_plan(q, db.stats())
    mesh = make_local_mesh()
    t0 = time.perf_counter()
    fn, eng = make_distributed_count(q, td, order, db, mesh,
                                     capacity=capacity,
                                     axes=("data", "model"))
    log(f"build_s={time.perf_counter() - t0!r} capacity={capacity} "
        f"expand_paths={eng.expand_paths} fold_paths={eng.fold_paths}")
    for i, dev in enumerate(mesh.devices.flat):
        log(f"  shard {i}: device id={dev.id} kind={dev.device_kind} "
            f"coords={getattr(dev, 'coords', None)}")
    with mesh:
        for call in ("first", "second"):
            t0 = time.perf_counter()
            total, overflow = fn()
            total = int(jax.block_until_ready(total))
            dt = time.perf_counter() - t0
            log(f"mutual distributed {call} call: count={total} "
                f"host={want['mutual']} overflow_shards={int(overflow)} "
                f"wall_s={dt!r} result_devices="
                f"{sorted(d.id for d in overflow.sharding.device_set)}")
    if int(overflow):
        raise AssertionError(f"{int(overflow)} shards overflowed")
    expect("mutual", total, want["mutual"])


def registry_report() -> None:
    from repro.kernels import registry

    for (spec, platform), why in registry.failures().items():
        log(f"fused kernel refused on {platform}: {spec}: {why[:600]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jax.monitoring

    events = {"cache_hits": 0, "cache_misses": 0}

    def on_event(name, **_kw):
        key = name.rsplit("/", 1)[-1]
        if key in events:
            events[key] += 1

    jax.monitoring.register_event_listener(on_event)
    devs = jax.devices()
    dev = devs[0]
    log(f"devices: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    log(f"compile cache: {cache_dir}")

    t0 = time.perf_counter()
    edges = build_graph(args.seed)
    want = host_counts(edges, N_VERTICES, SERVE_QUERIES if args.chips == 1
                       else ("mutual",))
    log(f"graph: |V|={N_VERTICES} |E|={len(edges)} seed={args.seed} "
        f"host reference {want} in {time.perf_counter() - t0!r}s")
    try:
        if args.chips == 4:
            distributed_phase(edges, N_VERTICES, want)
        else:
            serve_phase(edges, N_VERTICES, want)
    finally:
        registry_report()
        log(f"persistent compile cache: {events}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
