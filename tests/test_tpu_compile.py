"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The main path's XLA steps (EXPAND, merged FOLD, EMIT) must compile at
the default ``frontier_capacity = 1 << 16`` with x64 on, over trie
columns the size of ``chip_smoke.py``'s graph (1,768,149 edges), and a
fused kernel that the TPU compiler refuses must make a forced
``"pallas"`` raise instead of falling back.  Prefix sums are inverted by
counting, not by a binary search (a ``while`` loop of full-width
gathers on the TPU).  The topology is described
inside a fixture, so only the worker that runs this file loads the TPU
compiler.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import schedule
from repro.core.frontier import Frontier
from repro.kernels import registry
from repro.kernels.emit import FusedEmitConfig, xla as emit_xla
from repro.kernels.expand import xla as expand_xla
from repro.kernels.fold import xla as fold_xla

C = 1 << 16                 # JoinEngineConfig.frontier_capacity
N_VARS, N_ATOMS = 3, 3      # the triangle query
N_EDGES = 1_768_149         # chip_smoke.py's graph
PAYLOAD_ROWS = 1 << 17      # TPU_SERVE's slab arena


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(autouse=True)
def _x64():
    with jax.enable_x64(True):
        yield


def _arr(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _frontier(sharding):
    a = lambda shape, dtype: _arr(sharding, shape, dtype)
    return Frontier(assign=a((C, N_VARS), jnp.int32),
                    factor=a((C,), jnp.int64), valid=a((C,), jnp.bool_),
                    orig=a((C,), jnp.int32),
                    lo=a((C, N_ATOMS), jnp.int32),
                    hi=a((C, N_ATOMS), jnp.int32))


def _has_no_sort(compiled):
    return "sort(" not in compiled.as_text()


def test_xla_expand_compiles(one_chip):
    col = _arr(one_chip, (N_EDGES,), jnp.int32)
    compiled = expand_xla.expand_step.lower(
        _frontier(one_chip), col, col, (col,), d=2, g_ai=1, other_ais=(2,),
        n_rows_g=N_EDGES, impl="bsearch").compile()
    assert _has_no_sort(compiled)


def _n_loops(compiled):
    return compiled.as_text().count(" while(")


@pytest.mark.parametrize("step", ["compact", "merge_chunks", "emit_pack",
                                  "expand_1_other", "expand_2_others"])
def test_prefix_sum_inversions_compile_without_loops(one_chip, step):
    """Compaction, chunk merging and the EMIT pack hold no loop; EXPAND
    holds only its searches by value: the guard's run starts (two) and
    two membership searches per other atom."""
    F = _frontier(one_chip)
    col = _arr(one_chip, (N_EDGES,), jnp.int32)
    if step == "compact":
        lowered, loops = expand_xla.compact.lower(F), 0
    elif step == "merge_chunks":
        lowered, loops = schedule._merge_chunks.lower(F, F), 0
    elif step == "emit_pack":
        lowered, loops = jax.jit(emit_xla.build()).lower(
            _arr(one_chip, (C, N_VARS), jnp.int32),
            _arr(one_chip, (C,), jnp.bool_)), 0
    else:
        others = (1, 2) if step == "expand_2_others" else (2,)
        lowered = expand_xla.expand_step.lower(
            F, col, col, (col,) * len(others), d=2, g_ai=0,
            other_ais=others, n_rows_g=N_EDGES, impl="bsearch")
        loops = 2 + 2 * len(others)
    assert _n_loops(lowered.compile()) <= loops


def test_xla_merged_fold_compiles(one_chip):
    i32 = lambda: _arr(one_chip, (C,), jnp.int32)
    mask = lambda: _arr(one_chip, (C,), jnp.bool_)
    F = _frontier(one_chip)
    fn = fold_xla.build(d0=1, d1=2, with_replay=True, with_splice=True)
    compiled = jax.jit(fn).lower(
        F, mask(), i32(), F, mask(), i32(), i32(),
        _arr(one_chip, (PAYLOAD_ROWS + 1, 2), jnp.int32)).compile()
    assert _has_no_sort(compiled)


def test_xla_emit_compiles(one_chip):
    compiled = jax.jit(emit_xla.build()).lower(
        _arr(one_chip, (C, N_VARS), jnp.int32),
        _arr(one_chip, (C,), jnp.bool_)).compile()
    assert _has_no_sort(compiled)


def test_forced_pallas_refused_by_compiler_raises(one_chip, monkeypatch):
    """The fused EMIT uses an in-kernel cumsum, which the TPU lowering
    refuses; a forced "pallas" must surface that, not fall back."""
    registry.clear_autotune_cache()
    (device,) = one_chip.device_set
    monkeypatch.setattr(registry, "_compile_target", lambda: device)
    spec = registry.EmitSpec(capacity=C, n_vars=N_VARS, dtype="int32",
                             x64=True)
    try:
        with pytest.raises(RuntimeError, match="refused by the compiler"
                                               ".*cumsum"):
            registry.emit_fn(spec, mode="pallas",
                             config=FusedEmitConfig(interpret=False))
        assert any("cumsum" in why for (s, platform), why
                   in registry.failures().items()
                   if s == spec and platform == "tpu")
    finally:
        registry.clear_autotune_cache()
