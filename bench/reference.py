"""The plain reference: answers of a traffic mix's query on the host.

Shares no code with the engine.  Counts of the named queries come from
``scipy.sparse`` algebra (copied from the repository's ``chip_smoke.py``);
any query over one binary edge relation can also be answered by
:func:`join_rows`, a plain generic join over sorted adjacency lists, where
its intermediate results fit the host's memory.

A query is a list of atoms ``[relation, var, var]``; the relation is
always the configuration's edge relation ``E``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np
import scipy.sparse as sp

Atom = Sequence[str]


def _adjacency(edges: np.ndarray, nv: int) -> sp.csr_matrix:
    return sp.csr_matrix((np.ones(len(edges), np.int64),
                          (edges[:, 0], edges[:, 1])), shape=(nv, nv))


def _apex_triangles(A: sp.csr_matrix) -> np.ndarray:
    """t[c]: triangles E(c,x2),E(x2,x3),E(c,x3) at apex c, i.e. row c of
    (A·A)∘A summed, in row blocks that bound A·A's fill-in."""
    nv = A.shape[0]
    t = np.zeros(nv, np.int64)
    for r0 in range(0, nv, 4096):
        blk = A[r0:r0 + 4096]
        t[r0:r0 + 4096] = np.asarray(
            (blk @ A).multiply(blk).sum(axis=1)).ravel()
    return t


def triangle(edges: np.ndarray, nv: int) -> int:
    return int(_apex_triangles(_adjacency(edges, nv)).sum())


def bowtie(edges: np.ndarray, nv: int) -> int:
    """Two triangles joined at x1: the sum of t²."""
    t = _apex_triangles(_adjacency(edges, nv))
    return int((t * t).sum())


def _mutual_degrees(edges: np.ndarray, nv: int) -> np.ndarray:
    A = _adjacency(edges, nv)
    M = A.multiply(A.T).tocsr()
    return np.asarray(M.sum(axis=1)).ravel().astype(np.int64)


def mutual(edges: np.ndarray, nv: int) -> int:
    """Ordered mutual-follow pairs: E(x1,x2),E(x2,x1)."""
    return int(_mutual_degrees(edges, nv).sum())


def mutual_2hop(edges: np.ndarray, nv: int) -> int:
    """Mutual pairs joined at x2: the sum of squared mutual degrees."""
    m = _mutual_degrees(edges, nv)
    return int((m * m).sum())


COUNTS = {"triangle": triangle, "bowtie": bowtie, "mutual": mutual,
          "mutual_2hop": mutual_2hop}


# -- generic join ------------------------------------------------------


def _csr(src: np.ndarray, dst: np.ndarray, nv: int):
    order = np.lexsort((dst, src))
    ptr = np.zeros(nv + 1, np.int64)
    np.add.at(ptr, src + 1, 1)
    return np.cumsum(ptr), dst[order]


def join_rows(query: Sequence[Atom], edges: np.ndarray, nv: int,
              order: Sequence[str]) -> np.ndarray:
    """Every assignment of ``query`` over ``edges``, as int64 rows with
    columns in ``order``: bind one variable at a time, take candidates
    from the adjacency list of a bound neighbour, and keep the rows that
    satisfy every atom whose variables are all bound."""
    keys = np.unique(edges[:, 0] * nv + edges[:, 1])
    out_ptr, out_adj = _csr(edges[:, 0], edges[:, 1], nv)
    in_ptr, in_adj = _csr(edges[:, 1], edges[:, 0], nv)
    col: Dict[str, int] = {}
    rows = np.zeros((1, 0), np.int64)
    for v in order:
        src = None
        for _, a, b in query:
            if a in col and b == v:
                src = (col[a], out_ptr, out_adj)
                break
            if b in col and a == v:
                src = (col[b], in_ptr, in_adj)
                break
        if src is None:
            cand = np.arange(nv, dtype=np.int64)
            rep = np.repeat(np.arange(len(rows)), nv)
            new = np.tile(cand, len(rows))
        else:
            c, ptr, adj = src
            u = rows[:, c]
            deg = ptr[u + 1] - ptr[u]
            rep = np.repeat(np.arange(len(rows)), deg)
            start = np.repeat(ptr[u], deg)
            within = np.arange(len(rep)) - np.repeat(np.cumsum(deg) - deg,
                                                      deg)
            new = adj[start + within]
        rows = np.concatenate([rows[rep], new[:, None]], axis=1)
        col[v] = rows.shape[1] - 1
        for _, a, b in query:
            if v not in (a, b) or a not in col or b not in col:
                continue
            k = rows[:, col[a]] * nv + rows[:, col[b]]
            pos = np.clip(np.searchsorted(keys, k), 0, len(keys) - 1)
            rows = rows[keys[pos] == k]
    return rows


def packed(rows: np.ndarray, nv: int) -> np.ndarray:
    """Each row as one int64 (base ``nv`` digits), sorted."""
    if nv ** rows.shape[1] >= 2 ** 63:
        raise ValueError(f"{rows.shape[1]} columns of ids below {nv} do "
                         "not pack into int64")
    key = np.zeros(len(rows), np.int64)
    for i in range(rows.shape[1]):
        key = key * nv + rows[:, i].astype(np.int64)
    return np.sort(key)


def row_gap(got: np.ndarray, want_packed: np.ndarray, nv: int) -> int:
    """Rows missing from ``got`` plus rows in it that the reference lacks
    or that repeat: 0 exactly when ``got`` is the reference's row set."""
    g = packed(got, nv)
    common = np.intersect1d(g, want_packed, assume_unique=False)
    dup = len(g) - len(np.unique(g))
    return int(len(want_packed) - len(common) + len(np.unique(g))
               - len(common) + dup)


def answers(traffic: dict, edges: np.ndarray, nv: int,
            order: Sequence[str]) -> dict:
    """The reference answer of a traffic mix: ``count``, and for a
    streaming mix the sorted packed ``rows`` in the client's ``order``."""
    query: List[Atom] = traffic["query"]
    name = traffic["reference"]
    out: dict = {}
    if name == "join" or traffic["mode"] == "stream":
        rows = join_rows(query, edges, nv, order)
        out["count"] = len(rows)
        if traffic["mode"] == "stream":
            out["rows"] = packed(rows, nv)
    if name != "join":
        out["count"] = COUNTS[name](edges, nv)
    return out
