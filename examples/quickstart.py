"""Quickstart: plan and run a cached trie join (the paper's CLFTJ).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (CachePolicy, Counters, choose_plan, clftj_count,
                        cycle_query, path_query, graph_db, lftj_count, engine)
from repro.data.graphs import dataset
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    # a skewed graph (ego-Twitter-like) and the paper's flagship 5-cycle
    db = dataset("wiki-vote-like")
    q = path_query(4)
    print(f"query: {q}")

    # 1) plan: enumerate TDs (small adhesions first), pick one + a strongly
    #    compatible variable order
    td, order = choose_plan(q, db.stats())
    print(f"TD bags: {[sorted(b) for b in td.bags]}")
    print(f"adhesions: {[sorted(td.adhesion(v)) for v in range(td.num_nodes) if td.parent[v] >= 0]}")
    print(f"order: {order}")

    # 2) vanilla LFTJ (paper Fig 1) vs cached CLFTJ (paper Fig 2)
    c_l = Counters()
    n_l = lftj_count(q, order, db, c_l)
    c_c = Counters()
    n_c = clftj_count(q, td, order, db, CachePolicy(), c_c)
    assert n_l == n_c
    print(f"\n|q(D)| = {n_l}")
    print(f"LFTJ  memory accesses: {c_l.mem_accesses:>12,}")
    print(f"CLFTJ memory accesses: {c_c.mem_accesses:>12,} "
          f"({c_l.mem_accesses / max(c_c.mem_accesses, 1):.1f}x fewer; "
          f"{c_c.cache_hits} cache hits)")

    # 3) the TPU-native vectorized engine (same counts, one line)
    res = engine.count(q, db)
    assert res.count == n_l
    print(f"JAX engine count: {res.count}  ({res.wall_s:.2f}s, "
          f"tier-1 rows collapsed: {res.counters['tier1_rows_collapsed']:,})")


if __name__ == "__main__":
    main()
