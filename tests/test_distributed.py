"""Multi-device tests (subprocess: XLA host-device flags must be set before
jax initializes, and the main pytest process must keep 1 device)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"  # the parent may hold the chip
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_join_count_parity():
    out = _run("""
import numpy as np, jax
from repro.core import cycle_query, choose_plan, lftj_count
from repro.core.distributed import make_distributed_count
from repro.core.db import graph_db
rng = np.random.default_rng(5)
db = graph_db(rng.integers(0, 60, size=(400, 2)))
q = cycle_query(4)
td, order = choose_plan(q, db.stats())
mesh = jax.make_mesh((4, 2), ("data", "model"))
fn, eng = make_distributed_count(q, td, order, db, mesh,
                                 capacity=1 << 12, axes=("data", "model"))
with mesh:
    total, ov = fn()
print(int(total), int(ov), lftj_count(q, order, db))
""")
    total, ov, want = map(int, out.split())
    assert total == want and ov == 0


def test_distributed_evaluate_payload_parity_and_warm_replay():
    """Payload-capable distributed evaluation (DESIGN.md §2.8): per-shard
    slab arenas, shard-local splice, host-side merge.  The merged tuple
    set must equal the host oracle's on both passes, and the second pass
    (tables round-tripped) must serve tier-2 replay hits — the
    acceptance-criterion recurring-bag query."""
    out = _run("""
import numpy as np, jax
from repro.core import CacheConfig, bowtie_query, choose_plan, clftj_evaluate
from repro.core.distributed import make_distributed_evaluate
from repro.core.db import graph_db
from repro.data.graphs import zipf_graph
db = graph_db(zipf_graph(14, 80, 1.1, seed=7))
q = bowtie_query()
td, order = choose_plan(q, db.stats())
want = {tuple(map(int, t)) for t in
        np.asarray(clftj_evaluate(q, td, order, db),
                   np.int64).reshape(-1, len(order))}
mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = CacheConfig(policy="setassoc", slots=256, assoc=4,
                  cache_payloads=True, payload_rows=1 << 12)
fn0, eng0 = make_distributed_evaluate(q, td, order, db, mesh,
                                      capacity=1 << 12)
assert eng0.cache_config.cache_payloads, "default must be replay-capable"
fn, eng = make_distributed_evaluate(q, td, order, db, mesh,
                                    capacity=1 << 12,
                                    axes=("data", "model"), cache=cfg)
rows1, s1, tables = fn()
rows2, s2, _ = fn(tables)
got1 = {tuple(map(int, r)) for r in rows1.tolist()}
got2 = {tuple(map(int, r)) for r in rows2.tolist()}
print(int(got1 == want and rows1.shape[0] == len(got1)),
      int(got2 == want and rows2.shape[0] == len(got2)),
      s1["overflow"] + s2["overflow"],
      s1["tier2_replay_hits"], s2["tier2_replay_hits"],
      int(s1["count"] == s2["count"] == len(want)))
""")
    ok1, ok2, ov, hits1, hits2, counts_ok = map(int, out.split())
    assert ok1 and ok2 and counts_ok and ov == 0
    assert hits1 == 0 and hits2 > 0, (hits1, hits2)


def test_sharded_train_step_runs_on_mesh():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.models import Model
from repro.train.train_step import TrainConfig, init_train_state, make_train_step, state_shardings
from repro.sharding import rules as shr
from repro.launch.mesh import make_local_mesh
cfg = get_arch('minitron-8b-smoke')
model = Model(cfg)
mesh = make_local_mesh(model_parallel=4)
with mesh:
    state = init_train_state(model, jax.random.PRNGKey(0))
    shards = state_shardings(model, mesh)
    state = jax.device_put(state, shards)
    step = jax.jit(make_train_step(model, TrainConfig(microbatches=2), mesh))
    batch = {"tokens": jnp.ones((8, 16), jnp.int32),
             "targets": jnp.ones((8, 16), jnp.int32)}
    batch = jax.device_put(batch, jax.tree.map(
        lambda _: shr.batch_sharding(mesh, 8), batch))
    state, metrics = step(state, batch)
    print(float(metrics["loss"]))
""")
    assert float(out.strip()) > 0


@pytest.mark.slow
def test_dryrun_cell_production_mesh():
    """One full dry-run cell on the 512-device production mesh + probe."""
    out = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.dryrun import lower_cell
rec = lower_cell("whisper-tiny", "train_4k", multi_pod=False)
print(rec["status"], rec["n_devices"],
      rec["roofline"]["useful_flop_ratio"] > 0.005)
""", devices=512)
    status, ndev, ratio_ok = out.split()
    assert status == "ok" and int(ndev) == 256 and ratio_ok == "True"


def test_elastic_restore_different_mesh(tmp_path):
    out = _run(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.models import Model
from repro.checkpoint.ckpt import CheckpointManager
from repro.runtime.elastic import restore_for_mesh
from repro.train.train_step import init_train_state, state_shardings
cfg = get_arch('qwen2.5-3b-smoke')
model = Model(cfg)
# save under a 2x4 mesh
mesh1 = jax.make_mesh((2, 4), ("data", "model"))
with mesh1:
    state = jax.device_put(init_train_state(model, jax.random.PRNGKey(0)),
                           state_shardings(model, mesh1))
mgr = CheckpointManager(r'{tmp_path}', keep=1, async_save=False)
mgr.save(3, state)
# restore under a 8x1 mesh (elastic re-scale)
mesh2 = jax.make_mesh((8, 1), ("data", "model"))
with mesh2:
    step, restored, _ = restore_for_mesh(mgr, model, mesh2)
a = np.asarray(jax.tree.leaves(state["params"])[0])
b = np.asarray(jax.tree.leaves(restored["params"])[0])
print(step, np.allclose(a, b))
""")
    step, ok = out.split()
    assert int(step) == 3 and ok == "True"
