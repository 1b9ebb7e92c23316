"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Module map:
  bench_count_queries   — Fig 5 (+§1 memory-access analysis)
  bench_path_scaling    — Fig 6
  bench_cycle_scaling   — Fig 7
  bench_eval_queries    — Figs 8/9 (+ JAX CLFTJ materialization)
  bench_cache_size      — Fig 10
  bench_cache_structure — Figs 11/12
  bench_td_skew         — Figs 13/14
  bench_engine_backends — beyond-paper: vectorized engine + tier ablation
  bench_expand_kernel   — fused-EXPAND kernel: device-op counts + e2e deltas
  bench_fold_kernel     — fused FOLD/EMIT kernels: device-op counts, e2e
                          deltas, interior-span streaming async surplus
  bench_serve           — query-serving latency: cold vs plan-cache-warm
                          vs snapshot-loaded persistent-warm (DESIGN §2.9)
  bench_lm_step         — LM substrate wall-clock micro-bench

``--json [PATH]`` additionally writes every emitted row as structured
records (count + evaluate wall-times with the plan/compile/exec split,
tier-2 hit rates) to ``BENCH_<date>.json`` — the perf trajectory file.
"""
import argparse
import datetime
import json
import platform
import sys

MODULES = [
    "bench_count_queries", "bench_path_scaling", "bench_cycle_scaling",
    "bench_eval_queries", "bench_cache_size", "bench_cache_structure",
    "bench_td_skew", "bench_engine_backends", "bench_expand_kernel",
    "bench_fold_kernel", "bench_stream_emit", "bench_serve",
    "bench_lm_step",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes to run")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write structured records to PATH "
                         "(default BENCH_<date>.json)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mods = MODULES if not args.only else [
        m for m in MODULES if any(s in m for s in args.only.split(","))]
    print("name,us_per_call,derived")
    errors = []
    for m in mods:
        print(f"# --- {m} ---", flush=True)
        mod = __import__(f"benchmarks.{m}", fromlist=["main"])
        try:
            mod.main()
        except Exception as e:     # keep the harness running
            errors.append({"module": m, "error": str(e)})
            print(f"{m},0,ERROR:{e}", flush=True)
    if args.json is not None:
        from . import common
        import jax
        date = datetime.date.today().isoformat()
        path = args.json or f"BENCH_{date}.json"
        payload = {
            "date": date,
            "modules": mods,
            "platform": platform.platform(),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "errors": errors,
            "rows": common.RECORDS,
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(common.RECORDS)} records -> {path}", flush=True)


if __name__ == "__main__":
    main()
