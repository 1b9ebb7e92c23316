"""The readers of the stream cell's per-layer metrics (``fold_roofline``,
``emit_roofline``, ``fold_fill``, ``emit_wait_ms``) and the bytes behind
its rooflines (``bench/roofline_fold_emit.py``): on a trace written by hand
(``data/stream.pbtxt``), whose numbers are read off its events; on the older
hand traces and on count requests, where there is nothing to read; and at
the cell's own capacity and widths, where the bytes counted for a launch
are no more than the XLA steps' own."""
import importlib.util
import json
from pathlib import Path

import pytest

from bench import roofline_fold_emit, trace
from bench.run import Request, RunView

DATA = Path(__file__).resolve().parent / "data"
ROOT = DATA.parents[2]
US = 1e-6
PEAK = 819e9
READERS = ("fold_roofline", "emit_roofline", "fold_fill", "emit_wait_ms")


def _summary(name):
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_text_proto(
        (DATA / name).read_text()))


@pytest.fixture(scope="module")
def stream():
    return _summary("stream.pbtxt")


def _read(name, view):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def _view(summary, requests, n_atoms=4):
    return RunView(cell={}, config={}, traffic={}, requests=requests,
                   trace=summary, peaks={"hbm_bytes_per_s": PEAK},
                   memory_peak_bytes=None, capacity=64, n_vars=3,
                   n_atoms=n_atoms)


def _stream_request(replayed, spliced, folds):
    return Request(0.0, 1.0, replayed + spliced, counters={
        "fold_rows_replayed": replayed, "fold_rows_spliced": spliced,
        "fold_calls_xla": folds, "fold_calls_pallas": 0,
        "emit_calls_xla": folds})


def _count_request():
    return Request(0.0, 1.0, 5, counters={
        "fold_rows_replayed": 0, "fold_rows_spliced": 0,
        "fold_calls_xla": 0, "fold_calls_pallas": 0,
        "expand_calls_xla": 3, "expand_candidates": 100})


def test_bytes_are_the_chunks_each_launch_reads_and_writes():
    # a row of 3 variables and 4 atoms: 12 + 8 + 1 + 4 + 32 = 57 bytes
    assert roofline_fold_emit.fold_bytes(64, 3, 4, 1) == 64 * (2 * 57 + 4)
    assert roofline_fold_emit.fold_bytes(64, 3, 4, 2) == 64 * (2 * 57 + 8)
    assert roofline_fold_emit.emit_bytes(64, 3) == 64 * 13 + 64 * 12 + 4


def test_stream_trace_modules(stream):
    assert stream.window_s == pytest.approx(19 * US)
    assert stream.module_s() == pytest.approx(
        {"jit_expand_step": 1 * US, "jit_fold_step": 7 * US,
         "jit_emit_step": 3.5 * US})
    assert stream.module_n() == {"jit_expand_step": 1, "jit_fold_step": 3,
                                 "jit_emit_step": 3}


def test_fold_and_emit_rooflines_on_the_hand_trace(stream):
    view = _view(stream, [_stream_request(0, 100, 2)] * 2)
    want_fold = 100 * 3 * 64 * (2 * 57 + 4) / PEAK / (7 * US)
    want_emit = 100 * 3 * (64 * 25 + 4) / PEAK / (3.5 * US)
    assert _read("fold_roofline", view) == pytest.approx(want_fold)
    assert _read("emit_roofline", view) == pytest.approx(want_emit)


def test_emit_wait_is_the_union_of_emit_waits_per_request(stream):
    two = _view(stream, [_stream_request(0, 100, 2)] * 2)
    assert _read("emit_wait_ms", two) == pytest.approx(1.9 * US * 1e3 / 2)
    four = _view(stream, [_stream_request(0, 100, 2)] * 4)
    assert _read("emit_wait_ms", four) == pytest.approx(1.9 * US * 1e3 / 4)


def test_fold_fill_is_rows_written_over_launched_slots():
    view = _view(None, [_stream_request(100, 0, 2),
                        _stream_request(0, 90, 2)])
    assert _read("fold_fill", view) == pytest.approx(100 * 190 / (4 * 64))
    one = _view(None, [_stream_request(30, 34, 1)])
    assert _read("fold_fill", one) == pytest.approx(100.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_the_older_hand_trace_or_a_count(name):
    view = _view(_summary("hand.pbtxt"), [_count_request()] * 3, n_atoms=3)
    assert _read(name, view) is None


def test_readers_on_the_scopes_trace_read_only_its_fold_launch():
    view = _view(_summary("scopes.pbtxt"), [_count_request()], n_atoms=3)
    assert _read("emit_roofline", view) is None   # no jit_emit_step module
    assert _read("emit_wait_ms", view) is None
    assert _read("fold_fill", view) is None
    # one jit_fold_step launch of 2 us
    assert _read("fold_roofline", view) == pytest.approx(
        100 * 64 * (2 * 49 + 4) / PEAK / (2 * US))


@pytest.mark.parametrize("name", ("fold_roofline", "emit_roofline",
                                  "emit_wait_ms"))
def test_trace_readers_read_nothing_untraced(name):
    assert _read(name, _view(None, [_stream_request(0, 100, 2)])) is None


@pytest.mark.parametrize("variant", ("replay", "splice", "merged", "emit"))
def test_a_launch_counts_no_more_bytes_than_the_xla_step_moves(variant):
    """At the cell's capacity and widths, the bytes counted for a launch are
    at most the bytes XLA's cost analysis gives the step: a share above
    100% would need the chip to move the step's own bytes faster than its
    peak."""
    import jax
    import jax.numpy as jnp

    from repro.configs import paper_clftj
    from repro.core.frontier import Frontier
    from repro.kernels.emit import xla as emit_xla
    from repro.kernels.fold import xla as fold_xla

    traffic = json.loads(
        (ROOT / "bench" / "traffic" / "mutual2hop-stream.json").read_text())
    C = paper_clftj.TPU_SERVE.frontier_capacity
    n = len({v for _, a, b in traffic["query"] for v in (a, b)})
    m = len(traffic["query"])
    S = jax.ShapeDtypeStruct
    with jax.enable_x64(True):
        F = Frontier(assign=S((C, n), jnp.int32), factor=S((C,), jnp.int64),
                     valid=S((C,), bool), orig=S((C,), jnp.int32),
                     lo=S((C, m), jnp.int32), hi=S((C, m), jnp.int32))
        mask, idx = S((C,), bool), S((C,), jnp.int32)
        slab = S((paper_clftj.TPU_SERVE.payload_rows + 1, 1), jnp.int32)
        d = n - 1   # the child bag binds the last variable alone (x3)
        if variant == "emit":
            fn, args = emit_xla.build(), (F.assign, mask)
            counted = roofline_fold_emit.emit_bytes(C, n)
        else:
            replay, splice = variant != "splice", variant != "replay"
            fn = fold_xla.build(d0=d, d1=d, with_replay=replay,
                                with_splice=splice)
            args = (((F, mask, idx, F) if replay else (F,))
                    + ((mask, idx, idx, slab) if splice else ()))
            counted = roofline_fold_emit.fold_bytes(C, n, m, 1)
        cost = fn.lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert C == 1 << 16 and (n, m) == (3, 4)
    assert 0 < counted <= cost["bytes accessed"]


def test_the_stream_configuration_is_gap_kron_served_whole():
    """The stream's deployment: GAP's kron graph, cut as ``gap-kron`` is,
    so a request's answer is the graph's 2,035,770 2-walks, delivered each
    row once, in blocks."""
    from bench import graphs, reference

    configs = {n: json.loads((ROOT / "bench" / "configs" / f"{n}.json")
                             .read_text())
               for n in ("gap-kron", "gap-kron-stream")}
    kron, stream = configs["gap-kron"], configs["gap-kron-stream"]
    graph = ("scale", "edge_factor", "A", "B", "C", "undirected",
             "generator", "structure_seed", "engine")
    assert {k: stream[k] for k in graph} == {k: kron[k] for k in graph}
    assert stream["reduced"] == ["scale"] and stream["source"] != kron["source"]
    assert "exactly once" in stream["guarantees"]
    edges = graphs.build(stream, 2**31 + 3)
    assert reference.mutual_2hop(edges, graphs.vertices(stream)) == 2035770
