"""The four-chip cell end to end on four virtual CPU devices at a tiny
size: correct as it stands, and not correct with the exchange between the
chips (the count's psum) left out.  Runs in a subprocess, because the
device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = {"name": "gap-kron.mutual-count-4chip", "config": "gap-kron",
        "traffic": "mutual-count-4chip", "chips": 4}

SCRIPT = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
if {drop_psum}:
    jax.lax.psum = lambda x, axes: x
from bench import run as R
out = R.run({cell!r}, 2**31 + 17, 1.0, False, require_chip=False,
            graph_overrides={{"scale": 7}},
            engine_overrides={{"capacity": 1 << 14}},
            t_start=time.perf_counter())
print(json.dumps(out))
"""


@pytest.mark.parametrize("drop_psum", [False, True])
def test_mesh_cell_on_four_virtual_devices(drop_psum):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                         drop_psum=drop_psum, cell=CELL)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert set(out["checks"]) == {"count_gap", "overflow_shards"}
    assert out["correct"] is (not drop_psum), out["checks"]
