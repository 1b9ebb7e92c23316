"""Graphs of a configuration, made on the host.

``kronecker``: the Graph500 Kronecker generator, as the GAP Benchmark
Suite's ``kron`` graph uses it.  ``edge_factor * 2**scale`` edges are
drawn, each by choosing one of the four quadrants of the adjacency matrix
with the initiator's probabilities ``(A, B, C, 1 - A - B - C)`` at each of
``scale`` levels; self loops and repeats are then dropped, and the graph is
made undirected by storing each edge both ways.

The structure is drawn once, from the configuration's ``structure_seed``,
as GAP draws its ``kron`` graph from one fixed seed.  The run's seed
relabels the vertices with a random permutation (as Graph500 does) and
shuffles the edges.  So every seed gives the same work in another order:
the same numbers of edges and of distinct sources and targets, and so the
same shapes of the engine's trie columns, and no new compilation.
"""
from __future__ import annotations

import numpy as np


def kronecker_pairs(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int) -> np.ndarray:
    """``edge_factor * 2**scale`` directed (source, target) pairs drawn by
    the Graph500 rule with initiator ``(a, b, c, 1 - a - b - c)``, before
    self loops and repeats are dropped."""
    rng = np.random.default_rng(seed)
    m = edge_factor << scale
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    e = np.zeros((m, 2), np.int64)
    for level in range(scale):
        src_bit = rng.random(m) > ab
        dst_bit = rng.random(m) > np.where(src_bit, c_norm, a_norm)
        e[:, 0] |= src_bit.astype(np.int64) << level
        e[:, 1] |= dst_bit.astype(np.int64) << level
    return e


def undirected(pairs: np.ndarray, nv: int) -> np.ndarray:
    """Each distinct edge of ``pairs`` without self loops, both ways,
    sorted."""
    e = pairs[pairs[:, 0] != pairs[:, 1]]
    key = np.unique(np.concatenate([e[:, 0] * nv + e[:, 1],
                                    e[:, 1] * nv + e[:, 0]]))
    return np.stack([key // nv, key % nv], axis=1)


def relabel(edges: np.ndarray, nv: int, seed: int) -> np.ndarray:
    """``edges`` with vertex ids permuted and rows shuffled by ``seed``."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(nv)
    return perm[edges][rng.permutation(len(edges))]


def vertices(config: dict) -> int:
    return 1 << int(config["scale"])


def build(config: dict, seed: int) -> np.ndarray:
    """The edge list a configuration describes, labelled by ``seed``."""
    if config["generator"] != "kronecker":
        raise ValueError(f"unknown graph generator {config['generator']!r}")
    nv = vertices(config)
    pairs = kronecker_pairs(int(config["scale"]), int(config["edge_factor"]),
                            float(config["A"]), float(config["B"]),
                            float(config["C"]), int(config["structure_seed"]))
    return relabel(undirected(pairs, nv), nv, seed)
