"""Fused-EXPAND kernel microbench + end-to-end dispatch check (§2.7).

Two sections:

* ``expandk/micro`` — one realistic EXPAND step per chunk-size point:
  per-call wall time and the **device-op count** (non-metadata jaxpr
  primitives, ``kernels.registry.device_op_count``) for the fused Pallas
  kernel vs the XLA op chain.  The acceptance bound lives here: fused
  must lower to ≤2 device ops per EXPAND.  On CPU the fused kernel runs
  through the Pallas interpreter (recorded as ``interpret: true``) — its
  wall time is a conformance-vehicle number, not a perf claim; the op
  count is the figure that transfers to TPU/GPU.
* ``expandk/e2e`` — end-to-end count + evaluate on the recurring-bag
  queries (bowtie on a Barabási–Albert graph; the 4-zigzag on the small
  Zipf-skewed DB) with ``expand_kernel="auto"`` vs ``"xla"`` forced: the
  dispatch layer must cost nothing (on CPU auto resolves to the XLA
  chain, so the pair must match — "no end-to-end regression"), plus one
  small forced-``pallas`` bowtie run to keep the interpret-mode cost
  honest in the record.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import CacheConfig, bowtie_query, choose_plan, cycle_query, engine
from repro.core.cached_frontier import JaxCachedTrieJoin
from repro.core.db import graph_db
from repro.kernels import registry
from repro.kernels.expand import fused as fused_mod, xla as xla_mod

from .common import emit

CAPS = (1 << 10, 1 << 12, 1 << 14)


def _zipf_db(nv=40, ne=400, a=1.1, seed=31):
    from repro.data.graphs import zipf_graph
    return graph_db(zipf_graph(nv, ne, a, seed=seed))


def _time_call(fn, F, reps=5):
    import jax
    jax.block_until_ready(fn(F))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(F))
        best = min(best, time.perf_counter() - t0)
    return best


def micro_sweep() -> None:
    """One depth-1 EXPAND (two membership atoms on the 4-cycle) on a
    frontier produced by a real depth-0 expansion, per chunk size."""
    import jax
    db = _zipf_db()
    q = cycle_query(4)
    td, order = choose_plan(q, db.stats())
    interpret = jax.default_backend() not in ("tpu", "gpu")
    with jax.enable_x64(True):
        for cap in CAPS:
            eng = JaxCachedTrieJoin(q, td, order, db, capacity=cap)
            a0 = eng.expand_kernel_args(0)
            a1 = eng.expand_kernel_args(1)
            F = xla_mod.build(impl="bsearch", **a0)(eng.initial_frontier())[0]
            n_valid = int(np.asarray(F.valid).sum())
            fns = {"xla": xla_mod.build(impl="bsearch", **a1),
                   "pallas": fused_mod.build(**a1)}
            ops = {}
            for impl, fn in fns.items():
                ops[impl] = registry.device_op_count(fn, F)
                dt = _time_call(fn, F)
                emit(f"expandk/micro/cap{cap}/{impl}", dt * 1e6,
                     f"device_ops={ops[impl]};valid_rows={n_valid};"
                     f"interpret={interpret if impl == 'pallas' else False}",
                     record={"kind": "expand-kernel", "cap": cap,
                             "impl": impl, "seconds": dt,
                             "device_ops": ops[impl],
                             "valid_rows": n_valid,
                             "interpret": (interpret if impl == "pallas"
                                           else False)})
            assert ops["pallas"] <= 2, \
                f"fused EXPAND lowered to {ops['pallas']} device ops"


def _best_engine_run(name: str, mk, reps: int = 5,
                     exec_unreliable: bool = False) -> dict:
    """Best-of-``reps`` engine facade run (fresh engine per rep; jit
    caches warm after the first, so the min isolates host-loop noise —
    single-shot exec_s jitter on these queries is ±50%, far larger than
    any real auto-vs-xla delta).  ``exec_unreliable`` marks configs whose
    compile/exec split cannot be trusted — interpret-mode Pallas emits
    compile events *during* execution, so the listener drains exec_s —
    and reports wall − plan (compile + exec) instead, flagged."""
    results = [mk() for _ in range(reps)]
    res = min(results, key=lambda r: r.wall_s)
    s = res.counters or {}
    exec_s, clamped = res.exec_s, False
    if exec_unreliable or exec_s == 0.0:
        exec_s, clamped = max(0.0, res.wall_s - res.plan_s), True
    emit(name, exec_s * 1e6,
         f"count={res.count};exec_s={exec_s:.4f};"
         f"paths={res.expand_paths};replay_hits={res.tier2_replay_hits}",
         record={"kind": "engine", "result": res.count,
                 "seconds": res.wall_s, "plan_s": res.plan_s,
                 "compile_s": res.compile_s, "exec_s": exec_s,
                 "exec_includes_compile": clamped,
                 "reps": reps, "algorithm": res.algorithm,
                 "backend": res.backend, **s})
    return {"exec_s": exec_s, "paths": res.expand_paths}


def e2e_recurring() -> None:
    """End-to-end recurring-bag queries: auto vs forced-xla must match
    (CPU dispatch picks xla), so the kernel subsystem costs nothing
    until an accelerator is present."""
    from repro.data.graphs import barabasi_albert
    from .bench_td_skew import TDS, zigzag_cycle
    from .bench_eval_queries import small_skewed_db

    pay = CacheConfig(policy="setassoc", slots=1 << 14, assoc=8,
                      cache_payloads=True, payload_rows=1 << 17)
    qb = bowtie_query()
    dbb = graph_db(barabasi_albert(600, 5, seed=9))
    q4 = zigzag_cycle(4)
    td4 = TDS[4]["TD1-person"]
    cases = [("bowtie", qb, dbb, None, None),
             ("4-zigzag", q4, small_skewed_db(), td4,
              td4.strongly_compatible_order())]

    def runners(q, db, td, order, kind):
        def count(mode):
            return engine.count(q, db, td=td, order=order,
                                capacity=1 << 11, expand_kernel=mode)

        def ev(mode):
            return engine.evaluate(q, db, algorithm="clftj", backend="jax",
                                   td=td, order=order, capacity=1 << 11,
                                   cache=pay, expand_kernel=mode)

        return count if kind == "count" else ev

    reps = 5
    for name, q, db, td, order in cases:
        for kind in ("count", "eval"):
            mk = runners(q, db, td, order, kind)
            # interleave the two modes so each rep's pair shares the
            # host's momentary load — this box drifts far more than any
            # real auto-vs-xla delta (on CPU both resolve to the same
            # fn, which identical_dispatch pins via the path counters)
            pairs = [(mk("xla"), mk("auto")) for _ in range(reps)]
            best_x = min(pairs, key=lambda p: p[0].wall_s)[0]
            best_a = min(pairs, key=lambda p: p[1].wall_s)[1]
            for tag, res in (("xla", best_x), ("auto", best_a)):
                s = res.counters or {}
                emit(f"expandk/e2e/{name}/{kind}-{tag}",
                     res.exec_s * 1e6,
                     f"count={res.count};exec_s={res.exec_s:.4f};"
                     f"paths={res.expand_paths};"
                     f"replay_hits={res.tier2_replay_hits}",
                     record={"kind": "engine", "result": res.count,
                             "seconds": res.wall_s, "plan_s": res.plan_s,
                             "compile_s": res.compile_s,
                             "exec_s": res.exec_s, "reps": reps,
                             "algorithm": res.algorithm,
                             "backend": res.backend, **s})
            ratios = sorted(a.exec_s / max(x.exec_s, 1e-9)
                            for x, a in pairs)
            ratio = ratios[len(ratios) // 2]  # median of paired ratios
            same = best_a.expand_paths == best_x.expand_paths
            auto_s, xla_s = best_a.exec_s, best_x.exec_s
            emit(f"expandk/e2e/{name}/{kind}-auto-vs-xla",
                 (auto_s - xla_s) * 1e6,
                 f"auto_s={auto_s:.4f};xla_s={xla_s:.4f};"
                 f"median_pair_ratio={ratio:.3f};"
                 f"identical_dispatch={same}",
                 record={"kind": "expand-e2e-delta", "query": name,
                         "mode": kind, "auto_s": auto_s, "xla_s": xla_s,
                         "ratio": ratio, "identical_dispatch": same,
                         "pair_ratios": [round(r, 3) for r in ratios]})
    # interpret-mode honesty record: one small forced-pallas end-to-end.
    # Per-call the fused step beats the XLA chain even on CPU (the
    # interpreter traces to one jitted fusion and skips the argsort
    # compaction — see expandk/micro), but its compile cost is much
    # higher and its compile/exec split unmeasurable, which is why CPU
    # "auto" stays on xla; the time reported here is wall − plan.
    _best_engine_run(
        "expandk/e2e/bowtie/count-pallas-interpret",
        lambda: engine.count(qb, dbb, capacity=1 << 11,
                             expand_kernel="pallas"),
        exec_unreliable=True)


def main() -> None:
    micro_sweep()
    e2e_recurring()


if __name__ == "__main__":
    main()
