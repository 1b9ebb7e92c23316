"""The benchmark's data and plain reference on the host: the graphs it
makes from a seed, its counts and rows against the repository's brute-force
oracle, the row comparison, and the control that must come out not
correct."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import control, graphs, reference
from bench.run import make_query
from repro.core.bruteforce import brute_force_evaluate
from repro.core.db import graph_db

ROOT = Path(__file__).resolve().parents[2]
TRAFFIC = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
TINY = {"generator": "kronecker", "scale": 4, "edge_factor": 4,
        "A": 0.57, "B": 0.19, "C": 0.19, "structure_seed": 5}


def traffic(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def config(name="gap-kron"):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_graph_is_undirected_without_loops_or_repeats(seed):
    g = dict(TINY, scale=8, edge_factor=16)
    e = graphs.build(g, seed)
    nv = graphs.vertices(g)
    assert e.min() >= 0 and e.max() < nv
    assert (e[:, 0] != e[:, 1]).all()
    key = e[:, 0] * nv + e[:, 1]
    assert np.unique(key).size == len(e)
    assert np.array_equal(np.sort(key), np.sort(e[:, 1] * nv + e[:, 0]))
    assert np.array_equal(e, graphs.build(g, seed))


def test_seeds_relabel_one_structure_so_shapes_stay():
    """Every seed gives the same degree sequence and edge count, under
    other vertex ids and another edge order."""
    g = dict(TINY, scale=8, edge_factor=16)
    nv = graphs.vertices(g)
    a, b = graphs.build(g, 1), graphs.build(g, 2**31 + 9)
    assert not np.array_equal(a, b)
    assert len(a) == len(b)
    deg = [np.sort(np.bincount(e[:, 0], minlength=nv)) for e in (a, b)]
    assert np.array_equal(*deg)
    assert len(np.unique(a[:, 0])) == len(np.unique(b[:, 0]))
    assert (reference.triangle(a, nv) == reference.triangle(b, nv))


def test_kronecker_pairs_follow_the_initiator():
    """At one level the quadrants are drawn with the initiator's
    probabilities (A top-left, B top-right, C bottom-left, D the rest)."""
    e = graphs.kronecker_pairs(1, 1 << 16, 0.57, 0.19, 0.19, 3)
    share = np.bincount(e[:, 0] * 2 + e[:, 1], minlength=4) / len(e)
    assert np.allclose(share, [0.57, 0.19, 0.19, 0.05], atol=0.01)


def test_the_configuration_keeps_its_source_but_for_the_scale():
    """GAP's kron: Graph500 initiator, degree 16, scale 27 cut to fit."""
    c = config()
    assert c["reduced"] == ["scale"] and c["scale"] < 27
    assert (c["edge_factor"], c["A"], c["B"], c["C"], c["undirected"]) == (
        16, 0.57, 0.19, 0.19, True)
    e = graphs.build(c, 1)
    assert len(e) == 20948 and len(np.unique(e[:, 0])) == 894


@pytest.mark.parametrize("name", TRAFFIC)
@pytest.mark.parametrize("seed", [1, 2])
def test_reference_agrees_with_brute_force(name, seed):
    t = traffic(name)
    edges = graphs.build(TINY, seed)
    nv = graphs.vertices(TINY)
    q = make_query(t)
    want = brute_force_evaluate(q, graph_db(edges))
    order = tuple(q.variables)
    got = reference.answers(t, edges, nv, order)
    assert got["count"] == len(want)
    rows = reference.join_rows(t["query"], edges, nv, order)
    assert {tuple(int(x) for x in r) for r in rows} == want
    assert len(rows) == len(want)
    if t["mode"] == "stream":
        assert np.array_equal(got["rows"], reference.packed(rows, nv))


def test_row_gap_counts_missing_extra_and_repeated_rows():
    nv = 10
    rows = np.array([[1, 2], [2, 3], [3, 4]])
    want = reference.packed(rows, nv)
    assert reference.row_gap(rows, want, nv) == 0
    assert reference.row_gap(rows[::-1], want, nv) == 0
    assert reference.row_gap(rows[:2], want, nv) == 1
    assert reference.row_gap(np.vstack([rows, [[5, 6]]]), want, nv) == 1
    assert reference.row_gap(np.vstack([rows, rows[:1]]), want, nv) == 1
    assert reference.row_gap(np.array([[1, 2], [2, 3], [3, 5]]), want,
                             nv) == 2


def test_packing_refuses_rows_that_overflow_int64():
    with pytest.raises(ValueError):
        reference.packed(np.zeros((1, 6), np.int64), 1 << 11)


@pytest.mark.parametrize("name", TRAFFIC)
def test_control_comes_out_not_correct(name):
    """The control goes through the harness's own comparison, which must
    find it not correct on every seed."""
    cell = {"name": name, "config": "gap-kron", "traffic": name,
            "chips": 1}
    for seed in (3, 4, 5):
        correct, failed, checks = control.checks(cell, seed, ROOT,
                                                 {"scale": 9})
        assert correct is False and failed == 2, checks
        assert any(c["value"] > c["limit"] for c in checks.values())


def test_control_narrows_to_the_widest_type_the_ids_do_not_fit():
    assert control.narrow_bits(1 << 17) == 16
    assert control.narrow_bits(1 << 10) == 8
    with pytest.raises(ValueError):
        control.narrow_bits(100)
