"""One run of each one-chip cell end to end on the CPU at a tiny size,
with the look for a chip skipped: the answers come out correct, and with
the timed path broken underneath, ``correct`` comes out false.  Without a
TPU the command itself exits non-zero and prints no result."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# every one-chip mix, whether or not BENCHMARK.json lists its cell yet
MIXES = {m["name"]: m for m in (
    {"name": "gap-kron.bowtie-count", "config": "gap-kron",
     "traffic": "bowtie-count", "chips": 1},
    {"name": "gap-kron.triangle-count", "config": "gap-kron",
     "traffic": "triangle-count", "chips": 1},
    {"name": "gap-kron.mutual2hop-stream", "config": "gap-kron",
     "traffic": "mutual2hop-stream", "chips": 1})}
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
TINY_GRAPH = {"scale": 7}
TINY_NV = 1 << 7
TINY_ENGINE = {"frontier_capacity": 1 << 8, "cache_slots": 512,
               "payload_rows": 1 << 13}


def tiny_run(workload, seed=2**31 + 11, seconds=1.0):
    return R.run(MIXES[workload], seed, seconds, False, require_chip=False,
                 graph_overrides=TINY_GRAPH, engine_overrides=TINY_ENGINE,
                 t_start=time.perf_counter())


@pytest.mark.parametrize("workload", sorted(MIXES))
def test_tiny_run_is_correct_and_reports_its_metrics(workload):
    # a window of several requests even when a loaded host slows each one
    out = tiny_run(workload, seconds=4.0)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert out["window_compiles"] == 0
    want = {m["name"] for m in R.metric_names(BENCH, MIXES[workload],
                                              per_layer=False)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 == c["limit"] for c in out["checks"].values())


def _count_plus_one(monkeypatch):
    from repro.core.cached_frontier import JaxCachedTrieJoin

    orig = JaxCachedTrieJoin.count
    monkeypatch.setattr(JaxCachedTrieJoin, "count",
                        lambda self: orig(self) + 1)


def _patch_stream(monkeypatch, change):
    from repro.core.cached_frontier import JaxCachedTrieJoin

    orig = JaxCachedTrieJoin.evaluate_stream

    def stream(self):
        for block in orig(self):
            yield change(np.array(block))

    monkeypatch.setattr(JaxCachedTrieJoin, "evaluate_stream", stream)


def _alter_a_row(block):
    if len(block):
        block[0, 0] = (block[0, 0] + 1) % TINY_NV
    return block


def _drop_half(block):
    return block[:len(block) // 2]


@pytest.mark.parametrize("workload,fault", [
    ("gap-kron.bowtie-count", "answer"),
    ("gap-kron.triangle-count", "answer"),
    ("gap-kron.mutual2hop-stream", "answer"),
    ("gap-kron.mutual2hop-stream", "half"),
])
def test_a_broken_timed_path_comes_out_not_correct(workload, fault,
                                                   monkeypatch):
    if workload.endswith("stream"):
        _patch_stream(monkeypatch,
                      _alter_a_row if fault == "answer" else _drop_half)
    else:
        _count_plus_one(monkeypatch)
    out = tiny_run(workload)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_command_refuses_the_cpu_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", ONE_CHIP[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_device_kind_missing_from_peaks_is_refused():
    peaks = R.load_peaks(ROOT)
    assert R.peak_row("TPU v5 lite", peaks)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(R.NoChip):
        R.peak_row("TPU v4", peaks)
