"""Fused FOLD/EMIT kernel microbench + end-to-end dispatch check (§2.10).

Three sections:

* ``foldk/micro`` — one realistic merged FOLD step (replay + splice +
  merge) per chunk-size point: per-call wall time and the **device-op
  count** (``kernels.registry.device_op_count``) for the fused Pallas
  kernel vs the XLA op chain.  The acceptance bound lives here: fused
  must lower to ≤2 device ops per FOLD.  On CPU the fused kernel runs
  through the Pallas interpreter (recorded as ``interpret: true``) — its
  wall time is a conformance-vehicle number, not a perf claim; the op
  count is the figure that transfers to TPU/GPU.
* ``emitk/micro`` — the EMIT pack under the same protocol.
* ``foldk/e2e`` — end-to-end evaluate on the recurring bowtie query with
  ``fold_kernel``/``emit_kernel`` ``"auto"`` vs ``"xla"`` forced: the
  dispatch layer must cost nothing on CPU (auto resolves to the XLA
  chain, pinned via the path counters), plus one forced-``pallas`` run
  to keep the interpret-mode cost honest, and a streaming record pinning
  the interior-span async-issue surplus over a tail-only twin.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (CacheConfig, SyncCounter, bowtie_query, choose_plan,
                        engine)
from repro.core.cached_frontier import JaxCachedTrieJoin
from repro.core.db import graph_db
from repro.core.frontier import Frontier
from repro.kernels import registry
from repro.kernels.emit import fused as efused, xla as exla
from repro.kernels.fold import fused as ffused, xla as fxla

from .common import emit

CAPS = (1 << 10, 1 << 12, 1 << 14)
N, M = 5, 3
D0, D1 = 1, 3


def _fold_inputs(C: int, seed: int = 31):
    """A realistic merged-FOLD input set at capacity ``C``: quarter-full
    parent chunk, compacted exits sorted by representative (the executor
    invariant), an eighth of the parents carrying tier-2 payload hits."""
    rng = np.random.default_rng(seed)
    n_par, n_ex, n_reps = C // 4, C // 3, max(8, C // 16)
    slab_rows = 2 * C

    def chunk(k, orig):
        return Frontier(
            jnp.asarray(rng.integers(0, 99, size=(C, N)).astype(np.int32)),
            jnp.asarray(rng.integers(1, 5, size=(C,)).astype(np.int64)),
            jnp.asarray(np.arange(C) < k),
            jnp.asarray(orig, dtype=jnp.int32),
            jnp.asarray(rng.integers(0, 9, size=(C, M)).astype(np.int32)),
            jnp.asarray(rng.integers(9, 19, size=(C, M)).astype(np.int32)))

    P = chunk(n_par, np.sort(rng.integers(0, C, size=(C,))).astype(np.int32))
    eorig = np.full((C,), n_reps - 1, np.int32)
    eorig[:n_ex] = np.sort(rng.integers(0, n_reps, size=(n_ex,)))
    E = chunk(n_ex, eorig)
    active = (np.arange(C) < n_par) & (rng.random(C) < 0.5)
    rep_of_row = rng.integers(0, n_reps, size=(C,)).astype(np.int32)
    hit = (np.arange(C) < n_par) & (np.arange(C) % 8 == 0)
    plen = rng.integers(1, 4, size=(C,)).astype(np.int32)
    poff = rng.integers(0, slab_rows - 4, size=(C,)).astype(np.int32)
    slab = rng.integers(0, 99,
                        size=(slab_rows + 1, D1 - D0 + 1)).astype(np.int32)
    return (P, jnp.asarray(active), jnp.asarray(rep_of_row), E,
            jnp.asarray(hit), jnp.asarray(poff), jnp.asarray(plen),
            jnp.asarray(slab))


def _time_call(fn, args, reps=5):
    jax.block_until_ready(fn(*args))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def micro_sweep() -> None:
    interpret = jax.default_backend() not in ("tpu", "gpu")
    with jax.enable_x64(True):
        for cap in CAPS:
            args = _fold_inputs(cap)
            fns = {"xla": fxla.build(d0=D0, d1=D1, with_replay=True,
                                     with_splice=True),
                   "pallas": ffused.build(d0=D0, d1=D1, with_replay=True,
                                          with_splice=True)}
            stats = np.asarray(fns["xla"](*args)[1])
            ops = {}
            for impl, fn in fns.items():
                ops[impl] = registry.device_op_count(fn, *args)
                dt = _time_call(fn, args)
                emit(f"foldk/micro/cap{cap}/{impl}", dt * 1e6,
                     f"device_ops={ops[impl]};pairs={int(stats[0])};"
                     f"spliced={int(stats[1])};"
                     f"interpret={interpret if impl == 'pallas' else False}",
                     record={"kind": "fold-kernel", "cap": cap,
                             "impl": impl, "seconds": dt,
                             "device_ops": ops[impl],
                             "replay_pairs": int(stats[0]),
                             "splice_rows": int(stats[1]),
                             "interpret": (interpret if impl == "pallas"
                                           else False)})
            assert ops["pallas"] <= 2, \
                f"fused FOLD lowered to {ops['pallas']} device ops"
            # EMIT pack on the fold output's chunk shape
            P = args[0]
            efns = {"xla": exla.build(), "pallas": efused.build()}
            for impl, fn in efns.items():
                n_ops = registry.device_op_count(fn, P.assign, P.valid)
                dt = _time_call(fn, (P.assign, P.valid))
                emit(f"emitk/micro/cap{cap}/{impl}", dt * 1e6,
                     f"device_ops={n_ops};"
                     f"interpret={interpret if impl == 'pallas' else False}",
                     record={"kind": "emit-kernel", "cap": cap,
                             "impl": impl, "seconds": dt,
                             "device_ops": n_ops,
                             "interpret": (interpret if impl == "pallas"
                                           else False)})
                if impl == "pallas":
                    assert n_ops <= 2, \
                        f"fused EMIT lowered to {n_ops} device ops"


def e2e_recurring() -> None:
    """Recurring bowtie end-to-end: auto vs forced-xla must match on CPU
    (dispatch picks xla), so the FOLD/EMIT subsystem costs nothing until
    an accelerator is present."""
    from repro.data.graphs import barabasi_albert

    pay = CacheConfig(policy="setassoc", slots=1 << 14, assoc=8,
                      cache_payloads=True, payload_rows=1 << 17)
    q = bowtie_query()
    db = graph_db(barabasi_albert(600, 5, seed=9))
    reps = 5

    def ev(mode):
        return engine.evaluate(q, db, algorithm="clftj", backend="jax",
                               capacity=1 << 11, cache=pay,
                               fold_kernel=mode, emit_kernel=mode)

    pairs = [(ev("xla"), ev("auto")) for _ in range(reps)]
    best_x = min(pairs, key=lambda p: p[0].wall_s)[0]
    best_a = min(pairs, key=lambda p: p[1].wall_s)[1]
    for tag, res in (("xla", best_x), ("auto", best_a)):
        s = res.counters or {}
        emit(f"foldk/e2e/bowtie/eval-{tag}", res.exec_s * 1e6,
             f"count={res.count};exec_s={res.exec_s:.4f};"
             f"fold_paths={res.fold_paths};"
             f"replay_hits={res.tier2_replay_hits}",
             record={"kind": "engine", "result": res.count,
                     "seconds": res.wall_s, "plan_s": res.plan_s,
                     "compile_s": res.compile_s, "exec_s": res.exec_s,
                     "reps": reps, "algorithm": res.algorithm,
                     "backend": res.backend, **s})
    assert best_a.count == best_x.count
    ratios = sorted(a.exec_s / max(x.exec_s, 1e-9) for x, a in pairs)
    ratio = ratios[len(ratios) // 2]
    same = best_a.fold_paths == best_x.fold_paths
    delta_s = best_a.exec_s - best_x.exec_s
    emit("foldk/e2e/bowtie/eval-auto-vs-xla", abs(delta_s) * 1e6,
         f"auto_s={best_a.exec_s:.4f};xla_s={best_x.exec_s:.4f};"
         f"delta_s={delta_s:+.4f};"
         f"median_pair_ratio={ratio:.3f};identical_dispatch={same}",
         record={"kind": "fold-e2e-delta", "query": "bowtie",
                 "auto_s": best_a.exec_s, "xla_s": best_x.exec_s,
                 "ratio": ratio, "identical_dispatch": same,
                 "pair_ratios": [round(r, 3) for r in ratios]})
    # interpret-mode honesty record (compile/exec split unmeasurable on
    # the interpreter: report wall − plan)
    res_p = min((ev("pallas") for _ in range(2)), key=lambda r: r.wall_s)
    exec_p = max(0.0, res_p.wall_s - res_p.plan_s)
    emit("foldk/e2e/bowtie/eval-pallas-interpret", exec_p * 1e6,
         f"count={res_p.count};fold_paths={res_p.fold_paths};"
         f"exec_includes_compile=True",
         record={"kind": "engine", "result": res_p.count,
                 "seconds": res_p.wall_s, "plan_s": res_p.plan_s,
                 "exec_s": exec_p, "exec_includes_compile": True,
                 "algorithm": res_p.algorithm, "backend": res_p.backend,
                 **(res_p.counters or {})})
    assert res_p.count == best_x.count


def stream_interior_delta() -> None:
    """The §2.10 streaming acceptance figure as a bench record: interior-
    span streaming issues strictly more async fetches (per-morsel replay
    plans) than a tail-only twin on the recurring bowtie query."""
    from repro.data.graphs import barabasi_albert

    q = bowtie_query()
    db = graph_db(barabasi_albert(400, 4, seed=11))
    cfg = CacheConfig(policy="setassoc", slots=1 << 12, assoc=8,
                      cache_payloads=True, payload_rows=1 << 15)
    td, order = choose_plan(q, db.stats())
    counts = {}
    for tag, interior in (("interior", True), ("tail-only", False)):
        eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 10,
                                cache=cfg, stream_interior=interior)
        sum(b.shape[0] for b in eng.evaluate_stream())  # warm
        with SyncCounter() as sc:
            t0 = time.perf_counter()
            n = sum(b.shape[0] for b in eng.evaluate_stream())
            dt = time.perf_counter() - t0
        counts[tag] = sc.async_count
        emit(f"foldk/stream/bowtie/{tag}", dt * 1e6,
             f"rows={n};async_issues={sc.async_count};"
             f"blocking_syncs={sc.count};"
             f"replay_plans_async={sc.label_counts['replay-plan-async']}",
             record={"kind": "stream-interior", "mode": tag, "rows": n,
                     "seconds": dt, "async_issues": sc.async_count,
                     "blocking_syncs": sc.count,
                     "replay_plans_async":
                         sc.label_counts["replay-plan-async"]})
    assert counts["interior"] > counts["tail-only"], \
        "interior-span streaming must issue extra async replay plans"


def main() -> None:
    micro_sweep()
    e2e_recurring()
    stream_interior_delta()


if __name__ == "__main__":
    main()
