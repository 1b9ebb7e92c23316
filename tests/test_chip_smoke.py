"""``chip_smoke.py`` at a toy size on the CPU: its host reference agrees
with the repo's reference engines, its phases pass against the engine,
its row check rejects bad rows, and it reports nothing without a TPU."""
import dataclasses

import numpy as np
import pytest

import chip_smoke
from repro.configs.paper_clftj import TPU_SERVE
from repro.core import engine
from repro.core.db import graph_db
from repro.launch import compile_cache

NV, NE = 300, 2500
SMALL_SERVE = dataclasses.replace(TPU_SERVE, frontier_capacity=1 << 8,
                                  cache_slots=512, payload_rows=1 << 13)


@pytest.fixture(scope="module")
def graph():
    edges = chip_smoke.build_graph(1, NV, NE)
    names = chip_smoke.SERVE_QUERIES + ("mutual",)
    return edges, chip_smoke.host_counts(edges, NV, names)


def test_graph_has_exactly_the_distinct_edges_asked_for(graph):
    edges, _ = graph
    assert edges.shape == (NE, 2)
    assert (edges[:, 0] != edges[:, 1]).all()
    assert np.unique(edges[:, 0] * NV + edges[:, 1]).size == NE
    assert edges.max() < NV


@pytest.mark.parametrize("name", chip_smoke.SERVE_QUERIES + ("mutual",))
def test_host_counts_match_reference_engine(graph, name):
    edges, want = graph
    q = chip_smoke.queries()[name]
    got = engine.count(q, graph_db(edges), algorithm="lftj",
                       backend="ref").count
    assert want[name] == got


def test_serve_phase_passes_on_cpu(graph):
    edges, want = graph
    chip_smoke.serve_phase(edges, NV, want, SMALL_SERVE)


def test_check_rows_rejects_bad_rows(graph):
    edges, _ = graph
    q = chip_smoke.queries()["mutual"]
    m = set(map(tuple, edges.tolist()))
    good = np.asarray([e for e in edges.tolist() if (e[1], e[0]) in m])
    chip_smoke.check_rows(good, ("x1", "x2"), q, edges, NV)
    with pytest.raises(AssertionError, match="repeat"):
        chip_smoke.check_rows(np.concatenate([good, good[:1]]),
                              ("x1", "x2"), q, edges, NV)
    bad = good.copy()
    bad[0, 1] = bad[0, 0]  # a self loop: never an edge of the graph
    with pytest.raises(AssertionError, match="violate"):
        chip_smoke.check_rows(bad, ("x1", "x2"), q, edges, NV)


def test_main_refuses_without_a_tpu(monkeypatch, tmp_path, capsys):
    # with the variable set, the helper leaves JAX's cache config alone
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
