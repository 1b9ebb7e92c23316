"""Distributed CLFTJ across devices: shard_map over top-level candidate
runs, private per-shard caches, a single count psum (DESIGN.md §3).

    PYTHONPATH=src python examples/distributed_join.py --devices 8
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--devices", type=int, default=8)
ap.add_argument("--dataset", default="gnutella-like")  # balanced degrees; on skewed
# graphs equal-run sharding can overflow the hub shard (see EXPERIMENTS §Perf)
args = ap.parse_args()
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           f" --xla_force_host_platform_device_count={args.devices}")

import jax                              # noqa: E402 (after XLA_FLAGS)
import time                             # noqa: E402
from repro.core import choose_plan, cycle_query, lftj_count  # noqa: E402
from repro.core.distributed import make_distributed_count    # noqa: E402
from repro.data.graphs import dataset   # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def main() -> None:
    enable_compile_cache()
    db = dataset(args.dataset)
    q = cycle_query(4)
    td, order = choose_plan(q, db.stats())
    mesh = jax.make_mesh((args.devices, 1), ("data", "model"))
    fn, eng = make_distributed_count(q, td, order, db, mesh,
                                     capacity=1 << 17,
                                     axes=("data", "model"))
    with mesh:
        t0 = time.perf_counter()
        total, overflow = fn()
        total.block_until_ready()
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        total, overflow = fn()
        total.block_until_ready()
        dt2 = time.perf_counter() - t0
    print(f"devices={args.devices}  count={int(total)}  "
          f"overflow_shards={int(overflow)}")
    if int(overflow):
        raise SystemExit("static capacity overflow — rerun with a larger "
                         "capacity (the host-driven engine splits morsels "
                         "automatically; the SPMD pipeline flags instead)")
    print(f"first call (incl. compile): {dt:.2f}s; steady-state: {dt2:.3f}s")
    want = lftj_count(q, order, db)
    assert int(total) == want, (int(total), want)
    print(f"matches host reference ({want})")


if __name__ == "__main__":
    main()
