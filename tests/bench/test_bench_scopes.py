"""Device time per named scope (``bench/scopes.py``) and the readers of
the program's own spans and counters: on a trace written by hand
(``data/scopes.pbtxt``), whose numbers are read off its events, and on
the older hand trace, which carries no scope and no program span (the
parent program's case)."""
import importlib.util
from pathlib import Path

import pytest

from bench import scopes, trace
from bench.run import Request, RunView

DATA = Path(__file__).resolve().parent / "data"
US = 1e-6


def _load(name):
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(
        (DATA / name).read_text())
    pd = ProfileData.from_serialized_xspace(data)
    summary = trace.reduce(pd)
    ops = scopes.op_scopes(data)
    return summary, ops, scopes.scope_s(pd, ops, summary.window)


@pytest.fixture(scope="module")
def hand_scopes():
    return _load("scopes.pbtxt")


def _read(name, view):
    path = DATA.parents[2] / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def _view(summary, requests):
    return RunView(cell={}, config={}, traffic={}, requests=requests,
                   trace=summary, peaks={"hbm_bytes_per_s": 819e9},
                   memory_peak_bytes=None, capacity=64, n_vars=3, n_atoms=3)


def test_scope_key_takes_the_function_and_its_first_scope():
    assert scopes.scope_key("jit(expand_step)/verify/while/body/lt") == (
        "expand_step", "verify")
    assert scopes.scope_key("jit(fold_step)/add") == ("fold_step", "")
    assert scopes.scope_key("jit(f)/while/body/add") == ("f", "while")
    assert scopes.scope_key("loop fusion") is None


def test_innermost_credits_each_instant_once():
    got = scopes.innermost([(0, 10, "a"), (2, 4, "b"), (3, 5, "c"),
                            (12, 13, "d")])
    assert got == [(0, 2, "a"), (2, 3, "b"), (3, 5, "c"), (5, 10, "a"),
                   (12, 13, "d")]


def test_op_scopes_read_event_and_metadata_stats(hand_scopes):
    assert hand_scopes[1] == {"/device:TPU:0": [
        ("expand_step", "layout"), ("expand_step", "verify"),
        ("expand_step", "verify"), ("expand_step", "compact"),
        ("expand_step", "compact"), ("fold_step", "replay"),
        ("fold_step", ""), None, ("emit_step", "pack")]}


def test_nested_ops_give_the_exact_scope_seconds(hand_scopes):
    summary, _, got = hand_scopes
    assert got == pytest.approx({
        ("expand_step", "layout"): 1 * US,
        ("expand_step", "verify"): 5 * US,
        ("expand_step", "compact"): 2 * US,
        ("fold_step", "replay"): 1 * US,
        ("fold_step", ""): 1 * US,
        ("emit_step", "pack"): 1 * US})
    # verify's device time per jit_expand_step launch: 5 us over one
    assert got[("expand_step", "verify")] / summary.module_n()[
        "jit_expand_step"] == pytest.approx(5 * US)


def test_scope_seconds_stay_within_module_seconds(hand_scopes):
    summary, _, got = hand_scopes
    mods = summary.module_s()
    for fn in ("expand_step", "fold_step"):
        inside = sum(v for (f, _), v in got.items() if f == fn)
        assert inside <= mods[f"jit_{fn}"] * (1 + 1e-12)
    assert summary.module_n() == {"jit_expand_step": 1, "jit_fold_step": 1}


def test_readers_give_the_hand_computed_values(hand_scopes):
    reqs = [Request(0, 1, 0, counters={"expand_candidates": 100,
                                       "expand_rows_in": 20,
                                       "expand_rows_out": 30,
                                       "expand_calls_xla": 2}),
            Request(1, 2, 0, counters={"expand_candidates": 150,
                                       "expand_rows_in": 30,
                                       "expand_rows_out": 20,
                                       "expand_calls_xla": 2,
                                       "expand_calls_pallas": 1})]
    view = _view(hand_scopes[0], reqs)
    assert _read("expand_fill", view) == pytest.approx(
        100 * 250 / (5 * 64))
    assert _read("expand_pass_rate", view) == pytest.approx(100 * 50 / 250)
    assert _read("expand_fanout", view) == pytest.approx(250 / 50)
    # device 0 idles over [0, 1], [9, 10], [12, 12.5] and [13, 19]; the op
    # spans cover [0.5, 12.2] and [14, 16]: 0.5 + 1 + 0.2 + 2 = 3.7 us
    assert _read("interp_idle_ms", view) == pytest.approx(3.7e-3 / 2)
    # per request (clftj.serve.execute): [0.2, 10] idles 0.5 + 1 us inside
    # op spans, [10, 18] 0.2 + 2 us, [18, 19.5] none: median 1.5 us
    assert _read("interp_idle_ms_median", view) == pytest.approx(1.5e-3)


def test_readers_find_nothing_without_the_program_s_instrumentation():
    summary, ops, got = _load("hand.pbtxt")
    assert got == {}
    view = _view(summary, [Request(0, 1, 7,
                                   counters={"expand_calls_xla": 3})])
    for name in ("expand_fill", "expand_pass_rate", "expand_fanout",
                 "interp_idle_ms", "interp_idle_ms_median"):
        assert _read(name, view) is None, name
