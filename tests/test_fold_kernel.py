"""Fused FOLD/EMIT kernel subsystems: parity, dispatch, and autotune.

The fused Pallas kernels (interpret mode on CPU — the `pallas` marker
names this tier; see scripts/verify.sh) must be bit-exact with the XLA
op chains on every FOLD arity (replay-only, splice-only, merged) and on
the EMIT pack: same ``stats`` triple, same compacted valid prefix
(assign/factor/orig/lo/hi — resp. packed rows).  Both device paths are
additionally validated against the plain-numpy oracles
``kernels/fold/ref.py`` and ``kernels/emit/ref.py``.  Invalid tail rows
are garbage in both paths and not part of the contract.

The fused FOLD requires the exit chunk valid-prefix compacted with
nondecreasing ``orig`` (the executor's sorted-exits invariant); the
synthetic chunks here honor it so all three implementations enumerate
(parent, exit) pairs identically.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import choose_plan, cycle_query, engine
from repro.core.cached_frontier import JaxCachedTrieJoin
from repro.core.frontier import Frontier
from repro.kernels import registry
from repro.kernels.emit import emit_ref
from repro.kernels.emit import fused as efused, xla as exla
from repro.kernels.fold import FusedFoldConfig, fold_ref
from repro.kernels.fold import fused as ffused, xla as fxla


# ---------------------------------------------------------------------------
# Synthetic chunk factory (honors the sorted-exits invariant)
# ---------------------------------------------------------------------------

C, N, M = 128, 5, 3
D0, D1 = 1, 3  # fold bracket: width 3


def _frontier(rng, k, orig):
    """A valid-prefix compacted chunk with ``k`` valid rows."""
    assign = rng.integers(0, 40, size=(C, N)).astype(np.int32)
    factor = rng.integers(1, 5, size=(C,)).astype(np.int64)
    valid = np.arange(C) < k
    lo = rng.integers(0, 9, size=(C, M)).astype(np.int32)
    hi = lo + rng.integers(0, 4, size=(C, M)).astype(np.int32)
    return Frontier(jnp.asarray(assign), jnp.asarray(factor),
                    jnp.asarray(valid), jnp.asarray(orig, dtype=jnp.int32),
                    jnp.asarray(lo), jnp.asarray(hi))


def _fold_inputs(seed=0, n_parents=24, n_exits=40, n_reps=8, slab_rows=64,
                 fanout_hi=4, hit_every=3):
    """(P, active, rep_of_row, E, hit, poff, plen, slab) with bounded
    pair totals: needed <= n_parents * n_exits but chosen to fit C."""
    rng = np.random.default_rng(seed)
    P = _frontier(rng, n_parents,
                  np.sort(rng.integers(0, C, size=(C,))).astype(np.int32))
    active = (np.arange(C) < n_parents) & (rng.random(C) < 0.8)
    rep_of_row = rng.integers(0, n_reps, size=(C,)).astype(np.int32)
    # exits: compacted, orig nondecreasing (the invariant)
    eorig = np.full((C,), n_reps - 1, np.int32)
    eorig[:n_exits] = np.sort(rng.integers(0, n_reps, size=(n_exits,)))
    E = _frontier(rng, n_exits, eorig)
    hit = (np.arange(C) < n_parents) & (np.arange(C) % hit_every == 0)
    plen = rng.integers(1, fanout_hi, size=(C,)).astype(np.int32)
    poff = rng.integers(0, slab_rows - fanout_hi,
                        size=(C,)).astype(np.int32)
    slab = rng.integers(0, 40,
                        size=(slab_rows + 1, D1 - D0 + 1)).astype(np.int32)
    return (P, jnp.asarray(active), jnp.asarray(rep_of_row), E,
            jnp.asarray(hit), jnp.asarray(poff), jnp.asarray(plen),
            jnp.asarray(slab))


def _host(F):
    return Frontier(*(np.asarray(x) for x in F))


def _assert_fold_parity(ra, rb, msg=""):
    (Fa, sa), (Fb, sb) = ra, rb
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb),
                                  err_msg=f"{msg}: stats")
    va, vb = np.asarray(Fa.valid), np.asarray(Fb.valid)
    ka, kb = int(va.sum()), int(vb.sum())
    assert ka == kb, f"{msg}: {ka} != {kb} valid rows"
    assert va[:ka].all() and vb[:kb].all(), f"{msg}: not compacted"
    for f in ("assign", "factor", "orig", "lo", "hi"):
        np.testing.assert_array_equal(
            np.asarray(getattr(Fa, f))[:ka], np.asarray(getattr(Fb, f))[:kb],
            err_msg=f"{msg}: {f}")


def _assert_fold_oracle(result, P, active, rep_of_row, E, hit, poff, plen,
                        slab, *, with_replay, with_splice, msg=""):
    F, stats = result
    ref = fold_ref(
        _host(P),
        np.asarray(active) if with_replay else None,
        np.asarray(rep_of_row) if with_replay else None,
        _host(E) if with_replay else None,
        np.asarray(hit) if with_splice else None,
        np.asarray(poff) if with_splice else None,
        np.asarray(plen) if with_splice else None,
        np.asarray(slab) if with_splice else None,
        d0=D0, d1=D1)
    r_assign, r_factor, r_orig, r_lo, r_hi, r_stats = ref
    np.testing.assert_array_equal(np.asarray(stats), r_stats,
                                  err_msg=f"{msg}: stats vs oracle")
    k = r_assign.shape[0]
    v = np.asarray(F.valid)
    assert int(v.sum()) == k, f"{msg}: {int(v.sum())} != oracle {k}"
    got = (np.asarray(F.assign)[:k], np.asarray(F.factor)[:k],
           np.asarray(F.orig)[:k], np.asarray(F.lo)[:k],
           np.asarray(F.hi)[:k])
    for name, g, r in zip(("assign", "factor", "orig", "lo", "hi"),
                          got, (r_assign, r_factor, r_orig, r_lo, r_hi)):
        np.testing.assert_array_equal(g, r, err_msg=f"{msg}: {name}")


ARITIES = [("replay", True, False), ("splice", False, True),
           ("merged", True, True)]


def _fold_args(inputs, with_replay, with_splice):
    P, active, rep_of_row, E, hit, poff, plen, slab = inputs
    args = [P]
    if with_replay:
        args += [active, rep_of_row, E]
    if with_splice:
        args += [hit, poff, plen, slab]
    return tuple(args)


# ---------------------------------------------------------------------------
# Bit-exact parity: fused vs XLA vs oracle, all three arities
# ---------------------------------------------------------------------------

@pytest.mark.pallas
@pytest.mark.tier1
@pytest.mark.parametrize("name,wr,ws", ARITIES)
@pytest.mark.parametrize("seed", [0, 7])
def test_fold_fused_matches_xla_and_oracle(name, wr, ws, seed):
    inputs = _fold_inputs(seed=seed)
    with jax.enable_x64(True):
        fx = fxla.build(d0=D0, d1=D1, with_replay=wr, with_splice=ws)
        fp = ffused.build(d0=D0, d1=D1, with_replay=wr, with_splice=ws)
        args = _fold_args(inputs, wr, ws)
        rx, rp = fx(*args), fp(*args)
        _assert_fold_parity(rx, rp, msg=f"{name} seed={seed}")
        _assert_fold_oracle(rp, *inputs, with_replay=wr, with_splice=ws,
                            msg=f"{name} seed={seed}")


@pytest.mark.pallas
@pytest.mark.parametrize("name,wr,ws", ARITIES)
def test_empty_fold(name, wr, ws):
    """No active parents, no valid exits, no hits: zero stats and an
    all-invalid output from both paths (the edge the executor reaches
    when a morsel's subtree joins to nothing)."""
    P, active, rep_of_row, E, hit, poff, plen, slab = _fold_inputs(seed=3)
    inputs = (P, jnp.zeros_like(active),
              rep_of_row, E._replace(valid=jnp.zeros_like(E.valid)),
              jnp.zeros_like(hit), poff, plen, slab)
    with jax.enable_x64(True):
        fx = fxla.build(d0=D0, d1=D1, with_replay=wr, with_splice=ws)
        fp = ffused.build(d0=D0, d1=D1, with_replay=wr, with_splice=ws)
        args = _fold_args(inputs, wr, ws)
        for r in (fx(*args), fp(*args)):
            F, stats = r
            np.testing.assert_array_equal(np.asarray(stats), [0, 0, 0])
            assert not np.asarray(F.valid).any()


@pytest.mark.pallas
def test_fold_overflow_flag():
    """When the pair total exceeds capacity both paths truncate the
    output to C rows and report the uncapped totals in ``stats`` — the
    triple the static executor overflow-checks (the host executor never
    launches an overflowing fold: it morsel-splits first)."""
    # every parent active, every exit under one representative: needed =
    # n_parents * n_exits >> C
    rng = np.random.default_rng(11)
    P = _frontier(rng, C, np.arange(C, dtype=np.int32))
    active = jnp.ones((C,), bool)
    rep_of_row = jnp.zeros((C,), jnp.int32)
    E = _frontier(rng, C, np.zeros((C,), np.int32))
    with jax.enable_x64(True):
        fx = fxla.build(d0=D0, d1=D1, with_replay=True, with_splice=False)
        fp = ffused.build(d0=D0, d1=D1, with_replay=True, with_splice=False)
        rx = fx(P, active, rep_of_row, E)
        rp = fp(P, active, rep_of_row, E)
        _assert_fold_parity(rx, rp, msg="overflow")
        stats = np.asarray(rx[1])
        assert stats[0] == C * C, "needed must be the uncapped pair total"
        assert stats[2] == C
        assert int(np.asarray(rx[0].valid).sum()) == C


@pytest.mark.pallas
@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_fold_parity_x64_on_and_off(x64):
    """One built fn serves both precisions (dtypes derived at trace
    time); values agree between the paths either way."""
    class _null:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    with (jax.enable_x64(True) if x64 else _null()):
        inputs = _fold_inputs(seed=5)
        fx = fxla.build(d0=D0, d1=D1, with_replay=True, with_splice=True)
        fp = ffused.build(d0=D0, d1=D1, with_replay=True, with_splice=True)
        args = _fold_args(inputs, True, True)
        _assert_fold_parity(fx(*args), fp(*args), msg=f"x64={x64}")


@pytest.mark.pallas
@pytest.mark.parametrize("block_q", [16, 7, 1024])
def test_fold_block_q_configs(block_q):
    """Odd and oversized block sizes still produce bit-exact output."""
    cfg = FusedFoldConfig(block_q=block_q)
    inputs = _fold_inputs(seed=13)
    with jax.enable_x64(True):
        fx = fxla.build(d0=D0, d1=D1, with_replay=True, with_splice=True)
        fp = ffused.build(d0=D0, d1=D1, with_replay=True, with_splice=True,
                          config=cfg)
        args = _fold_args(inputs, True, True)
        _assert_fold_parity(fx(*args), fp(*args), msg=f"bq={block_q}")


# ---------------------------------------------------------------------------
# EMIT pack parity
# ---------------------------------------------------------------------------

@pytest.mark.pallas
@pytest.mark.tier1
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_emit_fused_matches_xla_and_oracle(density):
    rng = np.random.default_rng(17)
    assign = rng.integers(0, 99, size=(C, N)).astype(np.int32)
    valid = rng.random(C) < density
    if density == 1.0:
        valid[:] = True
    want = emit_ref(assign, valid)
    with jax.enable_x64(True):
        ex_, ep = exla.build(), efused.build()
        for name, fn in (("xla", ex_), ("pallas", ep)):
            packed, k = fn(jnp.asarray(assign), jnp.asarray(valid))
            assert int(k) == want.shape[0], name
            np.testing.assert_array_equal(
                np.asarray(packed)[:int(k)], want, err_msg=name)


# ---------------------------------------------------------------------------
# Dispatch + autotune
# ---------------------------------------------------------------------------

def _fspec(**over):
    kw = dict(capacity=C, n_vars=N, n_atoms=M, width=D1 - D0 + 1,
              with_replay=True, with_splice=True, dtype="int32", x64=True)
    kw.update(over)
    return registry.FoldSpec(**kw)


def _espec(**over):
    kw = dict(capacity=C, n_vars=N, dtype="int32", x64=True)
    kw.update(over)
    return registry.EmitSpec(**kw)


def test_auto_dispatch_picks_xla_on_cpu():
    registry.clear_autotune_cache()
    assert registry.select_fold(_fspec(), mode="auto",
                                platform="cpu") == "xla"
    assert registry.select_emit(_espec(), mode="auto",
                                platform="cpu") == "xla"
    # on an accelerator the same specs resolve to the fused kernels
    assert registry.select_fold(_fspec(), mode="auto", platform="tpu",
                                measure=False) == "pallas"
    assert registry.select_emit(_espec(), mode="auto", platform="tpu",
                                measure=False) == "pallas"
    with pytest.raises(ValueError, match="fold_kernel must be one of"):
        registry.select_fold(_fspec(), mode="nope")
    with pytest.raises(ValueError, match="emit_kernel must be one of"):
        registry.select_emit(_espec(), mode="nope")
    registry.clear_autotune_cache()


def test_engine_knob_validation():
    db = _db()
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    with pytest.raises(ValueError, match="fold_kernel"):
        JaxCachedTrieJoin(q, td, order, db, fold_kernel="nope")
    with pytest.raises(ValueError, match="emit_kernel"):
        JaxCachedTrieJoin(q, td, order, db, emit_kernel="nope")


def test_fold_pallas_build_failure_falls_back_to_xla(monkeypatch):
    """A fused FOLD/EMIT the compiler refuses: ``"auto"`` falls back to
    the XLA chain at build time, recorded in failures(); a forced
    ``"pallas"`` raises with the compiler's message instead."""
    def broken_build(**kw):
        def fn(*a):
            raise RuntimeError("mosaic lowering exploded")
        return fn

    registry.clear_autotune_cache()
    monkeypatch.setattr(ffused, "build", broken_build)
    monkeypatch.setattr(efused, "build", broken_build)
    with jax.enable_x64(True):
        with pytest.warns(UserWarning, match="falling back to the XLA path"):
            fn, chosen = registry.fold_fn(_fspec(), mode="auto",
                                          measure=True, d0=D0, d1=D1)
        assert chosen == "xla"
        with pytest.warns(UserWarning, match="falling back to the XLA path"):
            efn, echosen = registry.emit_fn(_espec(), mode="auto",
                                            measure=True)
        assert echosen == "xla"
        assert registry.failures(), "failures must be recorded"
        # the fallbacks actually run
        inputs = _fold_inputs(seed=19)
        F, stats = fn(*_fold_args(inputs, True, True))
        assert int(np.asarray(stats)[2]) == int(np.asarray(F.valid).sum())
        packed, k = efn(inputs[0].assign, inputs[0].valid)
        assert int(k) == int(np.asarray(inputs[0].valid).sum())
        with pytest.raises(RuntimeError, match="mosaic lowering exploded"):
            registry.fold_fn(_fspec(), mode="pallas", d0=D0, d1=D1)
        with pytest.raises(RuntimeError, match="mosaic lowering exploded"):
            registry.emit_fn(_espec(), mode="pallas")
    registry.clear_autotune_cache()


def test_fold_autotune_measured_caches_choice():
    registry.clear_autotune_cache()
    spec = _fspec()
    builders = {
        "xla": lambda: fxla.build(d0=D0, d1=D1, with_replay=True,
                                  with_splice=True),
        "pallas": lambda: ffused.build(d0=D0, d1=D1, with_replay=True,
                                       with_splice=True),
    }
    with jax.enable_x64(True):
        choice = registry.select_fold(spec, mode="auto", measure=True,
                                      builders=builders)
    assert choice in ("pallas", "xla")
    key = (spec, jax.default_backend())
    assert registry.autotune_cache()[key] == choice
    # second call must not re-measure: poison the builders
    boom = {"xla": None, "pallas": None}
    assert registry.select_fold(spec, mode="auto", measure=True,
                                builders=boom) == choice
    registry.clear_autotune_cache()


def test_autotune_entries_roundtrip_with_op_field():
    """FOLD/EMIT records carry an ``"op"`` discriminator in the sidecar
    schema; EXPAND records keep the historical op-less shape, and all
    three merge back into an empty cache."""
    registry.clear_autotune_cache()
    with jax.enable_x64(True):
        registry.select_fold(_fspec(), mode="auto", measure=True, builders={
            "xla": lambda: fxla.build(d0=D0, d1=D1, with_replay=True,
                                      with_splice=True),
            "pallas": lambda: ffused.build(d0=D0, d1=D1, with_replay=True,
                                           with_splice=True)})
        registry.select_emit(_espec(), mode="auto", measure=True, builders={
            "xla": lambda: exla.build(),
            "pallas": lambda: efused.build()})
    entries = registry.autotune_entries()
    ops = sorted(e.get("op", "expand") for e in entries)
    assert ops == ["emit", "fold"]
    registry.clear_autotune_cache()
    assert registry.merge_autotune_entries(entries) == 2
    assert registry.autotune_entries() == entries
    registry.clear_autotune_cache()


def test_expand_only_sidecar_migrates_cleanly():
    """A pre-FOLD/EMIT sidecar (EXPAND records, no ``"op"`` key) loads
    without KeyError and its decisions apply — no cold-start regression
    from the schema extension."""
    registry.clear_autotune_cache()
    spec = registry.ExpandSpec(capacity=C, n_vars=N, n_atoms=M, n_others=1,
                               dtype="int32", x64=True)
    old = [{"spec": dict(spec.__dict__), "platform": "cpu",
            "choice": "xla"}]
    assert registry.merge_autotune_entries(old) == 1
    assert registry.select_expand(spec, mode="auto", platform="cpu") == "xla"
    # the migrated record round-trips byte-identically (still op-less)
    assert old[0] in registry.autotune_entries()
    registry.clear_autotune_cache()


# ---------------------------------------------------------------------------
# Device-op bound (the acceptance figure)
# ---------------------------------------------------------------------------

@pytest.mark.pallas
@pytest.mark.tier1
@pytest.mark.parametrize("name,wr,ws", ARITIES)
def test_fold_fused_is_at_most_two_device_ops(name, wr, ws):
    """The acceptance bound: every fused FOLD arity lowers to ≤2
    non-metadata device ops (the pallas_call + the int64 stats cast is
    metadata); the XLA chain is an order of magnitude more."""
    inputs = _fold_inputs(seed=23)
    with jax.enable_x64(True):
        fx = fxla.build(d0=D0, d1=D1, with_replay=wr, with_splice=ws)
        fp = ffused.build(d0=D0, d1=D1, with_replay=wr, with_splice=ws)
        args = _fold_args(inputs, wr, ws)
        n_fused = registry.device_op_count(fp, *args)
        n_xla = registry.device_op_count(fx, *args)
        assert n_fused <= 2, f"{name}: fused lowers to {n_fused} ops"
        assert n_xla > n_fused, f"{name}: xla {n_xla} vs fused {n_fused}"


@pytest.mark.pallas
@pytest.mark.tier1
def test_emit_fused_is_at_most_two_device_ops():
    rng = np.random.default_rng(29)
    assign = jnp.asarray(rng.integers(0, 9, size=(C, N)).astype(np.int32))
    valid = jnp.asarray(rng.random(C) < 0.5)
    with jax.enable_x64(True):
        n_fused = registry.device_op_count(efused.build(), assign, valid)
        n_xla = registry.device_op_count(exla.build(), assign, valid)
        assert n_fused <= 2, f"fused EMIT lowers to {n_fused} ops"
        assert n_xla > n_fused, f"xla {n_xla} vs fused {n_fused}"


# ---------------------------------------------------------------------------
# Facade stats
# ---------------------------------------------------------------------------

def _db(seed=5, nv=10, ne=70):
    from repro.core.db import graph_db
    rng = np.random.default_rng(seed)
    return graph_db(rng.integers(0, nv, size=(ne, 2)))


@pytest.mark.pallas
def test_result_records_which_fold_path_ran():
    """``Result.fold_paths`` mirrors ``expand_paths``: forcing the knob
    each way lands every FOLD launch on that path, with identical
    results."""
    db = _db(seed=17)
    q = cycle_query(4)
    counts = {}
    for fk in ("xla", "pallas"):
        res = engine.evaluate(q, db, backend="jax", capacity=1 << 8,
                              fold_kernel=fk, emit_kernel=fk)
        counts[fk] = res.count
        paths = res.fold_paths
        if paths:  # count-mode folds don't launch; evaluate-mode must
            assert paths.get("pallas" if fk == "xla" else "xla", 0) == 0
        ep = {k[len("emit_calls_"):]: v for k, v in res.counters.items()
              if k.startswith("emit_calls_")}
        assert ep[fk] > 0
        assert ep["pallas" if fk == "xla" else "xla"] == 0
    assert counts["xla"] == counts["pallas"]
