"""Shared fixtures.  NOTE: no XLA device-count flags here — smoke tests and
benches must see the single real device; multi-device tests spawn
subprocesses that set XLA_FLAGS themselves (see test_distributed.py)."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="session")
def small_graphs():
    """A few deterministic small graph databases."""
    from repro.core.db import graph_db
    rng = np.random.default_rng(0)
    out = []
    for i, (ne, nv) in enumerate([(30, 8), (60, 10), (120, 14)]):
        out.append(graph_db(rng.integers(0, nv, size=(ne, 2))))
    return out
