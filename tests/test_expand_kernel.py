"""Fused-EXPAND kernel subsystem: parity, dispatch, and autotune.

The fused Pallas kernel (interpret mode on CPU — the `pallas` marker
names this tier; see scripts/verify.sh) must be bit-exact with the XLA
op chain on every EXPAND: same ``needed`` total, same compacted valid
prefix (assign/factor/orig/lo/hi).  Both are additionally validated
against the plain-numpy oracle ``kernels/expand/ref.py``.  Invalid tail
rows are garbage in both paths and not part of the contract (every
downstream consumer gates on ``valid``)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import choose_plan, cycle_query, star_query, engine
from repro.core.cached_frontier import JaxCachedTrieJoin
from repro.core.db import Database, graph_db
from repro.kernels import registry
from repro.kernels.expand import FusedExpandConfig, expand_ref
from repro.kernels.expand import fused as fused_mod, xla as xla_mod


def _db(seed=5, nv=10, ne=70):
    rng = np.random.default_rng(seed)
    return graph_db(rng.integers(0, nv, size=(ne, 2)))


def _fused_args(a):
    """The fused kernel keeps its own guard search: no run-rank table."""
    return {k: v for k, v in a.items() if k != "g_rank"}


def _build_pair(eng, d, config=None):
    a = eng.expand_kernel_args(d)
    fx = xla_mod.build(impl="bsearch", **a)
    fp = fused_mod.build(config=config, **_fused_args(a))
    return fx, fp, a


def _assert_parity(Fa, na, Fb, nb, msg=""):
    va, vb = np.asarray(Fa.valid), np.asarray(Fb.valid)
    ka, kb = int(va.sum()), int(vb.sum())
    assert ka == kb, f"{msg}: {ka} != {kb} valid rows"
    assert va[:ka].all() and vb[:kb].all(), f"{msg}: not compacted"
    for f in ("assign", "factor", "orig", "lo", "hi"):
        np.testing.assert_array_equal(
            np.asarray(getattr(Fa, f))[:ka], np.asarray(getattr(Fb, f))[:kb],
            err_msg=f"{msg}: {f}")
    assert int(na) == int(nb), f"{msg}: needed {int(na)} != {int(nb)}"


def _assert_oracle(F, a, Fo, no):
    """Compare a device result against the numpy oracle's row list.

    Only meaningful when ``needed <= C``: past capacity the device paths
    truncate the slot enumeration (the executor morsel-splits before
    ever running an overflowing chunk), while the oracle enumerates
    everything.  Returns whether the comparison ran."""
    if int(no) > F.assign.shape[0]:
        return False
    host = {k: np.asarray(v) for k, v in F._asdict().items()}
    rows, needed = expand_ref(
        host, np.asarray(a["g_col"]), np.asarray(a["g_rs"]),
        [np.asarray(c) for c in a["other_cols"]],
        d=a["d"], g_ai=a["g_ai"], other_ais=a["other_ais"],
        n_rows_g=a["n_rows_g"])
    k = rows["assign"].shape[0]
    vo = np.asarray(Fo.valid)
    assert int(vo.sum()) == k
    for f in ("assign", "factor", "orig", "lo", "hi"):
        np.testing.assert_array_equal(np.asarray(getattr(Fo, f))[:k],
                                      rows[f], err_msg=f)
    assert int(no) == needed
    return True


# ---------------------------------------------------------------------------
# Bit-exact parity, level by level on real engines
# ---------------------------------------------------------------------------

@pytest.mark.pallas
@pytest.mark.tier1
@pytest.mark.parametrize("qname,q", [("5-cycle", cycle_query(5)),
                                     ("star-3", star_query(3))])
def test_fused_matches_xla_and_oracle_level_by_level(qname, q):
    """Walk every depth: the fused kernel, the XLA chain, and the numpy
    oracle agree on the compacted valid prefix and ``needed``; the next
    level continues from the XLA result so all depths see realistic
    frontiers (duplicate keys included — the db has a small domain)."""
    db = _db(seed=11, nv=8, ne=90)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 10)
    with jax.enable_x64(True):
        F = eng.initial_frontier()
        oracle_checked = 0
        for d in range(eng.n):
            fx, fp, a = _build_pair(eng, d)
            Fx, nx = fx(F)
            Fp, npd = fp(F)
            _assert_parity(Fx, nx, Fp, npd, msg=f"{qname} d={d}")
            oracle_checked += bool(_assert_oracle(F, a, Fp, npd))
            F = Fx
        assert oracle_checked >= 2, "oracle must cover some depths"


@pytest.mark.pallas
def test_empty_frontier():
    db = _db()
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 7)
    with jax.enable_x64(True):
        F = eng.initial_frontier()
        F = F._replace(valid=jnp.zeros_like(F.valid))
        fx, fp, _ = _build_pair(eng, 0)
        Fx, nx = fx(F)
        Fp, npd = fp(F)
        assert int(nx) == 0 and int(npd) == 0
        assert not np.asarray(Fx.valid).any()
        assert not np.asarray(Fp.valid).any()


@pytest.mark.pallas
def test_single_atom_guard_depth():
    """A depth where only the guard atom participates (no membership
    searches at all): star-query leaf variables."""
    db = _db(seed=2)
    q = star_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 10)
    solo = [d for d in range(eng.n) if len(eng.at_depth[d]) == 1]
    assert solo, "star query must have single-atom depths"
    with jax.enable_x64(True):
        F = eng.initial_frontier()
        for d in range(eng.n):
            fx, fp, a = _build_pair(eng, d)
            if d in solo:
                assert a["other_ais"] == ()
                Fx, nx = fx(F)
                Fp, npd = fp(F)
                _assert_parity(Fx, nx, Fp, npd, msg=f"solo d={d}")
            F = fx(F)[0]


@pytest.mark.pallas
def test_duplicate_keys_heavy():
    """A two-value domain: every guard run is long and every membership
    window has duplicates — the stable-compaction order must still be
    identical."""
    rng = np.random.default_rng(0)
    db = graph_db(rng.integers(0, 2, size=(40, 2)))
    q = cycle_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8)
    with jax.enable_x64(True):
        F = eng.initial_frontier()
        for d in range(eng.n):
            fx, fp, a = _build_pair(eng, d)
            Fx, nx = fx(F)
            Fp, npd = fp(F)
            _assert_parity(Fx, nx, Fp, npd, msg=f"dup d={d}")
            _assert_oracle(F, a, Fp, npd)
            F = Fx


@pytest.mark.pallas
@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_parity_x64_on_and_off(x64):
    """The fused kernel derives every ref/out dtype from the chunk at
    trace time, so one built fn serves both precisions."""
    db = _db(seed=9)
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8)
    ctx = jax.enable_x64(True) if x64 else _null()
    with ctx:
        F = eng.initial_frontier()
        want_factor = jnp.int64 if x64 else jnp.int32
        assert F.factor.dtype == want_factor
        for d in range(eng.n):
            fx, fp, _ = _build_pair(eng, d)
            Fx, nx = fx(F)
            Fp, npd = fp(F)
            assert Fp.factor.dtype == want_factor
            _assert_parity(Fx, nx, Fp, npd, msg=f"x64={x64} d={d}")
            F = Fx


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


@pytest.mark.pallas
@pytest.mark.parametrize("cap,block_q", [(96, 64), (128, 7), (64, 1024)])
def test_block_q_config_snaps_to_divisor(cap, block_q):
    """block_q is snapped to a divisor of the capacity (gcd), so odd
    capacities and oversized blocks both work."""
    cfg = FusedExpandConfig(block_q=block_q)
    bq = cfg.resolve_block_q(cap)
    assert cap % bq == 0 and bq <= min(block_q, cap) or bq == cap
    db = _db(seed=4)
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=cap)
    with jax.enable_x64(True):
        F = eng.initial_frontier()
        for d in range(eng.n):
            fx, fp, _ = _build_pair(eng, d, config=cfg)
            Fx, nx = fx(F)
            Fp, npd = fp(F)
            _assert_parity(Fx, nx, Fp, npd, msg=f"cap={cap} bq={block_q}")
            F = Fx


# ---------------------------------------------------------------------------
# Prefix-sum inversion by counting (XLA chain)
# ---------------------------------------------------------------------------

def _inversion_counts(case, C, rng):
    """Non-negative per-row counts; ``valid`` is ``counts > 0``."""
    if case == "all_invalid":
        return np.zeros(C, np.int32)
    if case == "all_valid":
        return np.ones(C, np.int32)
    if case in ("single_first", "single_last"):
        c = np.zeros(C, np.int32)
        c[0 if case == "single_first" else -1] = 1
        return c
    if case.startswith("density_"):
        valid = rng.random(C) < float(case.split("_")[1])
        return np.where(valid, rng.integers(1, 4, C), 0).astype(np.int32)
    if case == "counts_with_zeros":
        return rng.integers(0, 3, C).astype(np.int32)
    if case == "offsets_reach_C":
        c = rng.integers(0, 5, C).astype(np.int32)
        c[C // 2] = C        # some offsets land on C exactly, most past it
        return c
    raise ValueError(case)


@pytest.mark.parametrize("C", [8, 64, 1024])
@pytest.mark.parametrize("case", [
    "all_invalid", "all_valid", "single_first", "single_last",
    "density_0.05", "density_0.5", "density_0.95", "counts_with_zeros",
    "offsets_reach_C"])
def test_counting_inversion_matches_searchsorted(case, C):
    """``count_le``, ``valid_first`` (every slot, past ``k`` too, and a
    shorter output) and EXPAND's slot-to-row map equal their
    ``jnp.searchsorted`` forms."""
    counts = _inversion_counts(case, C, np.random.default_rng(C))
    with jax.enable_x64(True):
        valid = jnp.asarray(counts > 0)
        csum = jnp.cumsum(valid.astype(jnp.int32))
        want_perm = jnp.clip(jnp.searchsorted(
            csum, jnp.arange(1, C + 1, dtype=jnp.int32), side="left"),
            0, C - 1)
        for out in (C, C // 2):
            got = xla_mod.count_le(csum, out)
            want = jnp.searchsorted(csum, jnp.arange(out, dtype=jnp.int32),
                                    side="right")
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            perm, k = xla_mod.valid_first(valid, out)
            assert perm.dtype == jnp.int32 and perm.shape == (out,)
            np.testing.assert_array_equal(np.asarray(perm),
                                          np.asarray(want_perm[:out]))
            assert int(k) == int(np.count_nonzero(counts))
        c = jnp.asarray(counts)
        offsets = jnp.cumsum(c) - c
        slot = jnp.arange(C, dtype=jnp.int32)
        want_src = jnp.clip(jnp.searchsorted(offsets, slot, side="right") - 1,
                            0, C - 1)
        got_src = jnp.clip(xla_mod.count_le(offsets, C) - 1, 0, C - 1)
        np.testing.assert_array_equal(np.asarray(got_src),
                                      np.asarray(want_src))


# ---------------------------------------------------------------------------
# Guard run-rank table (XLA chain)
# ---------------------------------------------------------------------------

_RANK_RELATIONS = {
    "empty": np.zeros((0, 2), np.int64),
    "one_row": np.asarray([[3, 5]], np.int64),
    "single_run": np.asarray([[4, 0], [4, 1], [4, 7], [4, 9]], np.int64),
}


def _rank_tables(case):
    """Every trie level the case builds: a conformance-zoo query's tries
    over each zoo database, or one hand-made relation's levels."""
    from test_conformance import ZOO, SEED, zipf_graph_db
    from repro.core.frontier import JaxTrieJoin, _build_levels
    if case in _RANK_RELATIONS:
        return _build_levels(_RANK_RELATIONS[case])
    q = dict(ZOO)[case]
    out = []
    for db in (zipf_graph_db(12, 60, 1.1, seed=SEED + 1),
               zipf_graph_db(18, 90, 0.9, seed=SEED + 2)):
        _, order = choose_plan(q, db.stats())
        eng = JaxTrieJoin(q, order, db, capacity=1 << 6)
        out += [lvl for atom in eng.levels for lvl in atom]
    return out


@pytest.mark.parametrize("case", ["4-clique", "5-cycle", "bowtie",
                                  "rand-acyclic-5", "rand-acyclic-6",
                                  "rand-acyclic-7", *_RANK_RELATIONS])
def test_run_rank_table_matches_searchsorted(case):
    """``AtomLevel.rank[p]`` is ``searchsorted(runstarts, p, "left")`` for
    every row position ``p`` in ``0..N``, on device and host alike."""
    levels = _rank_tables(case)
    assert levels
    for lvl in levels:
        n = lvl.col_np.shape[0]
        want = np.searchsorted(lvl.runstarts_np, np.arange(n + 1),
                               side="left")
        assert lvl.rank_np.dtype == np.int32 and lvl.rank_np.shape == (n + 1,)
        np.testing.assert_array_equal(lvl.rank_np, want)
        np.testing.assert_array_equal(np.asarray(lvl.rank), want)
        assert lvl.rank.dtype == jnp.int32


@pytest.mark.parametrize("qname,q,cap", [("triangle", cycle_query(3), 4),
                                         ("4-cycle", cycle_query(4), 8),
                                         ("star-3", star_query(3), 4)])
def test_expand_matches_oracle_on_split_pieces(qname, q, cap):
    """Every depth, every morsel piece ``split_chunk_host`` makes: the XLA
    chain equals the numpy oracle bit for bit on guard windows that end
    at the trie's last row (``N``) and on windows cut mid-row at a run
    start, where the run-rank table replaces the search."""
    from test_conformance import SEED, zipf_graph_db
    from repro.core.frontier import JaxTrieJoin
    db = zipf_graph_db(12, 60, 1.1, seed=SEED + 1)
    _, order = choose_plan(q, db.stats())
    eng = JaxTrieJoin(q, order, db, capacity=cap)
    ends_at_n = split_rows = pieces = 0
    with jax.enable_x64(True):
        chunks = [eng.initial_frontier()]
        for d in range(eng.n):
            a = eng.expand_kernel_args(d)
            fn = xla_mod.build(impl="bsearch", **a)
            g_ai, rs, n_rows_g = eng.expand_plan(d)
            nxt = []
            for F in chunks:
                host = {k: np.asarray(v) for k, v in F._asdict().items()}
                r0 = np.searchsorted(rs, host["lo"][:, g_ai], side="left")
                r1 = np.searchsorted(rs, host["hi"][:, g_ai], side="left")
                counts = np.where(host["valid"], r1 - r0, 0)
                # a row of more than ``cap`` runs is cut at run starts
                split_rows += int((counts > cap).sum())
                for P in eng.split_chunk_host(host, d, counts):
                    hi = np.asarray(P.hi)[np.asarray(P.valid), g_ai]
                    ends_at_n += int((hi == n_rows_g).any())
                    Fo, no = fn(P)
                    assert _assert_oracle(P, a, Fo, no), (qname, d)
                    pieces += 1
                    if int(np.asarray(Fo.valid).sum()):
                        nxt.append(Fo)
            chunks = nxt[:8]
    assert pieces > eng.n and ends_at_n and split_rows, (
        pieces, ends_at_n, split_rows)


# ---------------------------------------------------------------------------
# Dispatch + autotune
# ---------------------------------------------------------------------------

def _spec(eng, d, **over):
    kw = dict(capacity=eng.capacity, n_vars=eng.n, n_atoms=eng.m,
              n_others=len(eng.expand_kernel_args(d)["other_ais"]),
              dtype="int32", x64=True)
    kw.update(over)
    return registry.ExpandSpec(**kw)


def test_auto_dispatch_picks_xla_on_cpu():
    db = _db()
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8)
    assert eng.expand_impl(0) == "xla"
    assert registry.select_expand(_spec(eng, 0), mode="auto",
                                  platform="cpu") == "xla"
    # on an accelerator the same spec resolves to the fused kernel
    assert registry.select_expand(_spec(eng, 0, capacity=1 << 9),
                                  mode="auto", platform="tpu",
                                  measure=False) == "pallas"
    with pytest.raises(ValueError):
        registry.select_expand(_spec(eng, 0), mode="nope")
    with pytest.raises(ValueError):
        JaxCachedTrieJoin(q, td, order, db, expand_kernel="nope")


def test_degenerate_spec_takes_xla_even_when_pallas_forced():
    """An empty relation makes the expansion statically empty — never
    worth a kernel launch; the registry routes it to the XLA chain."""
    db = Database({"E": np.zeros((0, 2), np.int64),
                   "R": np.asarray([[0, 1], [1, 2]], np.int64)})
    from repro.core import Atom, CQ
    q = CQ((Atom("E", ("x1", "x2")), Atom("R", ("x1", "x2"))))
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 6,
                            expand_kernel="pallas")
    assert eng.count() == 0
    assert all(v == "xla" for v in eng.expand_paths.values())


def test_pallas_build_failure_falls_back_to_xla(monkeypatch):
    """A fused EXPAND the compiler refuses: ``"auto"`` falls back to the
    XLA chain at build time, with a warning and the compiler's message in
    failures(); a forced ``"pallas"`` raises with that message instead of
    falling back."""
    from repro.kernels.expand import fused as fused_real

    def broken_build(**kw):
        def fn(F):
            raise RuntimeError("mosaic lowering exploded")
        return fn

    registry.clear_autotune_cache()
    monkeypatch.setattr(fused_real, "build", broken_build)
    db = _db(seed=29)
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 7)
    a = eng.expand_kernel_args(0)
    with jax.enable_x64(True):
        with pytest.warns(UserWarning, match="falling back to the XLA path"):
            fn, chosen = registry.expand_fn(_spec(eng, 0), mode="auto",
                                            measure=True, sizes=eng.sizes,
                                            **a)
        assert chosen == "xla"
        F = eng.initial_frontier()
        _assert_parity(*fn(F), *xla_mod.build(impl="bsearch", **a)(F),
                       msg="fallback")
    assert any("mosaic lowering exploded" in why
               for why in registry.failures().values())
    forced = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 7,
                               expand_kernel="pallas")
    with pytest.raises(RuntimeError, match="refused by the compiler"
                                           ".*mosaic lowering exploded"):
        forced.count()
    registry.clear_autotune_cache()


def test_autotune_measured_caches_choice():
    registry.clear_autotune_cache()
    db = _db(seed=13)
    q = cycle_query(3)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8)
    a = eng.expand_kernel_args(0)
    spec = _spec(eng, 0)
    builders = {
        "xla": lambda: xla_mod.build(impl="bsearch", **a),
        "pallas": lambda: fused_mod.build(**_fused_args(a)),
    }
    with jax.enable_x64(True):
        choice = registry.select_expand(spec, mode="auto", measure=True,
                                        builders=builders, sizes=eng.sizes)
    assert choice in ("pallas", "xla")
    key = (spec, jax.default_backend())
    assert registry.autotune_cache()[key] == choice
    # second call must not re-measure: poison the builders
    boom = {"xla": None, "pallas": None}
    assert registry.select_expand(spec, mode="auto", measure=True,
                                  builders=boom) == choice
    registry.clear_autotune_cache()


@pytest.mark.pallas
@pytest.mark.tier1
def test_fused_is_at_most_two_device_ops():
    """The acceptance bound: the fused path lowers to ≤2 non-metadata
    device ops per EXPAND (the pallas_call + the ``needed`` extraction);
    the XLA chain is an order of magnitude more."""
    db = _db(seed=21)
    q = cycle_query(4)
    td, order = choose_plan(q, db.stats())
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=1 << 8)
    with jax.enable_x64(True):
        F = eng.initial_frontier()
        for d in range(eng.n):
            fx, fp, _ = _build_pair(eng, d)
            n_fused = registry.device_op_count(fp, F)
            n_xla = registry.device_op_count(fx, F)
            assert n_fused <= 2, f"d={d}: fused lowers to {n_fused} ops"
            assert n_xla > n_fused, f"d={d}: xla {n_xla} vs {n_fused}"


# ---------------------------------------------------------------------------
# Facade stats
# ---------------------------------------------------------------------------

@pytest.mark.pallas
def test_result_records_which_path_ran():
    db = _db(seed=17)
    q = cycle_query(4)
    for ek in ("xla", "pallas"):
        res = engine.count(q, db, capacity=1 << 8, expand_kernel=ek)
        paths = res.expand_paths
        assert paths[ek] > 0
        assert paths["pallas" if ek == "xla" else "xla"] == 0
        res_l = engine.count(q, db, algorithm="lftj", capacity=1 << 8,
                             expand_kernel=ek)
        assert res_l.expand_paths[ek] > 0
        assert res_l.count == res.count
