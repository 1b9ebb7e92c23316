"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (``data/v5e_tiny.xplane.pb``: two jitted functions
called three times in a ``window`` span), reduced to the numbers read by
hand from its events."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlapping_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == [
        (0, 4), (5, 9)]
    assert trace.length(trace.union([(0, 2), (1, 3)])) == 3


def test_clip_and_gaps_within_a_window():
    busy = trace.union(trace.clip([(-5, 2), (4, 6), (9, 20)], 0, 10))
    assert busy == [(0, 2), (4, 6), (9, 10)]
    assert trace.gaps(busy, 0, 10) == [(2, 4), (6, 9)]
    assert trace.gaps([], 0, 10) == [(0, 10)]


def test_module_name_drops_the_program_id():
    assert trace.module_name("jit_expand_step(1234)") == "jit_expand_step"
    assert trace.module_name("jit_fn") == "jit_fn"


def test_roofline_share_is_bytes_at_peak_over_time():
    assert trace.share(819e9, 2.0, 819e9) == pytest.approx(50.0)
    assert trace.share(0, 1.0, 819e9) is None
    assert trace.share(1, 0.0, 819e9) is None


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData

    text = (DATA / "hand.pbtxt").read_text()
    return trace.reduce(ProfileData.from_text_proto(text))


def test_hand_trace_window_busy_and_modules(hand):
    us = 1e-6
    assert hand.window_s == pytest.approx(10 * us)
    d0, d1 = hand.devices
    assert d0.name == "/device:TPU:0" and d1.name == "/device:TPU:1"
    assert d0.busy_s == pytest.approx(5.5 * us)
    assert d1.busy_s == pytest.approx(1.0 * us)
    assert hand.busy_s() == pytest.approx(3.25 * us)
    assert hand.module_s() == pytest.approx(
        {"jit_expand_step": 5 * us, "jit_fn": 1 * us})
    assert hand.module_n() == {"jit_expand_step": 2, "jit_fn": 1}


def test_hand_trace_idle_gaps_are_named_by_the_covering_host_span(hand):
    got = hand.idle_gaps()
    assert [g[0] for g in got] == ["wait_result", "wait_result",
                                   "PjRtExecute", "wait_result"]
    assert [g[1] for g in got] == pytest.approx([1.5e-6, 1e-6, 1e-6, 1e-6])


def test_hand_trace_metrics(hand):
    import importlib.util

    from bench import roofline
    from bench.run import RunView

    def read(name, view):
        path = DATA.parents[2] / "bench" / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(view)

    view = RunView(cell={}, config={}, traffic={}, requests=[], trace=hand,
                   peaks={"hbm_bytes_per_s": 819e9}, memory_peak_bytes=None,
                   capacity=1 << 16, n_vars=3, n_atoms=3)
    assert read("device_idle_share", view) == pytest.approx(67.5)
    assert read("shard_busy_skew", view) == pytest.approx(5.5 / 3.25)
    want = 100 * 2 * roofline.expand_bytes(1 << 16, 3, 3) / 819e9 / 5e-6
    assert roofline.expand_bytes(1 << 16, 3, 3) == 2 * 65536 * 49
    assert read("expand_roofline", view) == pytest.approx(want)
    assert read("device_peak_mb", view) is None
