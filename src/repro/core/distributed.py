"""Distributed CLFTJ: a fully-jittable static pipeline + mesh execution.

The host-driven engine (``cached_frontier``) splits morsels adaptively; for
SPMD execution we instead fix the chunk capacity, interpret the lowered op
schedule at trace time (``schedule.execute_static``), and flag overflow
instead of splitting.  The result is one pure function
(frontier₀, cache tables) → (count, overflow, tables) that
``shard_map``s across the mesh: each shard owns a contiguous slice of the
top-level variable's candidate runs (the natural LFTJ work partition — see
DESIGN.md §3), keeps a private cache (caching is an optimization, never a
correctness requirement, so no coherence traffic), and the only collective
is the final count psum.

Evaluation (DESIGN.md §2.8) runs the same pure schedule in materialization
mode with **payload-capable** tier-2 tables: each shard keeps a private
slab arena (the §2.6 row-block region, bump pointer threaded as a traced
scalar), splices its own payload hits shard-locally, and returns its
result chunk; the host merges the per-shard ``(assign, valid)`` blocks —
no result collective.  Tables round-trip through
:func:`make_distributed_evaluate`'s returned callable, so a second pass
over the same (or an overlapping) workload serves tier-2 replay hits.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .cache import CacheConfig
from .cached_frontier import JaxCachedTrieJoin, _resolve_cache_config
from .cq import CQ
from .db import Database
from .frontier import Frontier
from .hostsync import device_get
from .schedule import EXPAND, FOLD_CHILD, execute_static
from .td import TreeDecomposition


class StaticCLFTJ(JaxCachedTrieJoin):
    """Jittable fixed-capacity CLFTJ (no host-side morsel splitting).

    Tier-2 tables are (S, W) arrays per the configured :class:`CacheConfig`
    policy; each shard keeps a private table (no coherence traffic) and the
    LRU tick is a static counter baked in by the unrolled op schedule —
    the *same* lowered schedule the host executor interprets, run through
    ``schedule.execute_static`` instead of a third recursion copy."""

    # -----------------------------------------------------------------
    def make_tables(self, mode: str = "count") -> Dict[int, tuple]:
        """Fresh functional tier-2 tables for every probed TD node: the
        count-only 5-tuple, or — ``mode="evaluate"`` with
        ``cache_payloads`` — the 9-tuple with the §2.6 payload region
        (metadata planes, slab arena sized to the node's subtree width,
        traced bump pointer)."""
        cfg = self.cache_config
        if cfg.initial_slots() <= 0:
            return {}
        w = cfg.ways
        s = max(1, cfg.initial_slots() // w)
        tables: Dict[int, tuple] = {}
        for op in self.schedule.ops:
            if op.kind != FOLD_CHILD or not op.probe or op.node in tables:
                continue
            base = (jnp.zeros((s, w), jnp.int64),
                    jnp.zeros((s, w), jnp.int64),
                    jnp.zeros((s, w), bool),
                    jnp.zeros((s, w), jnp.int32),
                    jnp.zeros((s, w), jnp.int64))
            if mode == "evaluate" and cfg.cache_payloads:
                width = op.sub_last - op.sub_first + 1
                tables[op.node] = base + (
                    jnp.zeros((s, w), jnp.int32),
                    jnp.full((s, w), -1, jnp.int32),
                    jnp.zeros((int(cfg.payload_rows) + 1, width),
                              jnp.int32),
                    jnp.zeros((), jnp.int32))
            else:
                tables[op.node] = base
        return tables

    def resolve_kernels(self, mode: str = "count") -> None:
        """Resolve every registry kernel :func:`execute_static` traces in
        ``mode``, before any trace: selection may compile and time
        kernels, which it cannot do inside a ``shard_map`` trace.  Folds
        are resolved whether or not their exits end up sorted (an unsorted
        fold takes the XLA chain without asking the registry)."""
        cfg = self.cache_config
        payloads = cfg.initial_slots() > 0 and cfg.cache_payloads
        with jax.enable_x64(True):
            for op in self.schedule.ops:
                if op.kind == EXPAND:
                    self._expand_fn(op.d)
                elif op.kind == FOLD_CHILD and mode == "evaluate":
                    self._fold_fn(op.sub_first, op.sub_last, True,
                                  op.probe and payloads)
            if mode == "evaluate":
                self._emit_fn()

    def count_fn(self):
        """Returns a pure fn(frontier0) -> (count, overflow)."""
        cfg = self.cache_config

        def fn(F0: Frontier):
            total, ov, _ = execute_static(self.schedule, self, F0,
                                          self.make_tables("count"), cfg)
            return total, ov

        return fn

    def evaluate_fn(self):
        """Returns a pure fn(frontier0, tables) -> (assign, valid, count,
        overflow, replay_hits, tables) — the payload-capable trace-time
        evaluation of the lowered schedule (DESIGN.md §2.8)."""
        cfg = self.cache_config

        def fn(F0: Frontier, tables: Dict[int, tuple]):
            return execute_static(self.schedule, self, F0, tables, cfg,
                                  mode="evaluate")

        return fn

    def evaluate_static(self, tables: Optional[Dict[int, tuple]] = None):
        """Single-device trace-time evaluation with tier-2 payloads.

        Returns ``(rows, stats, tables)`` — rows the materialized (N, n)
        int32 result, ``stats`` with ``count``/``overflow``/
        ``tier2_replay_hits``, and the updated functional tables to pass
        back in for a warm pass (recurring adhesion keys then splice from
        the slab instead of re-expanding)."""
        with jax.enable_x64(True):
            if tables is None:
                tables = self.make_tables("evaluate")
            F0 = self.initial_frontier()
            assign, valid, total, ov, hits, tables = self.evaluate_fn()(
                F0, tables)
            a, v, t, o, h = device_get((assign, valid, total, ov, hits),
                                       "static-eval")
        rows = np.asarray(a)[np.asarray(v)]
        stats = {"count": int(t), "overflow": bool(o),
                 "tier2_replay_hits": int(h)}
        return rows, stats, tables


class _GuardPartition:
    """The top-level work partition shared by every distributed entry
    point: shard i of D takes guard runs [i·R/D, (i+1)·R/D) — the lo/hi
    math must stay byte-identical between count and evaluate, or the two
    would shard different row ranges."""

    def __init__(self, eng: StaticCLFTJ, mesh: Mesh,
                 axes: Tuple[str, ...]):
        self.eng = eng
        self.mesh = mesh
        g_ai, g_lvl = eng.at_depth[0][eng.guard[0]]
        self.g_ai = g_ai
        self.rs = eng.levels[g_ai][g_lvl].runstarts
        self.nruns = self.rs.shape[0]
        self.n_rows_g = eng.sizes[g_ai]
        self.all_axes = tuple(a for a in axes if a in mesh.axis_names)
        self.d_total = int(np.prod([mesh.shape[a] for a in self.all_axes]))

    def shard_frontier(self) -> Frontier:
        """This shard's initial frontier (call inside the shard body)."""
        idx = jnp.zeros((), jnp.int32)
        mult = 1
        for a in reversed(self.all_axes):
            idx = idx + jax.lax.axis_index(a) * mult
            mult *= self.mesh.shape[a]
        r0 = (idx * self.nruns) // self.d_total
        r1 = ((idx + 1) * self.nruns) // self.d_total
        lo0 = jnp.where(r0 < self.nruns,
                        self.rs[jnp.clip(r0, 0, self.nruns - 1)],
                        self.n_rows_g).astype(jnp.int32)
        hi0 = jnp.where(r1 < self.nruns,
                        self.rs[jnp.clip(r1, 0, self.nruns - 1)],
                        self.n_rows_g).astype(jnp.int32)
        F0 = self.eng.initial_frontier()
        return F0._replace(lo=F0.lo.at[0, self.g_ai].set(lo0),
                           hi=F0.hi.at[0, self.g_ai].set(hi0))


def make_distributed_count(q: CQ, td: TreeDecomposition,
                           order: Sequence[str], db: Database, mesh: Mesh,
                           capacity: int = 1 << 14,
                           axes: Tuple[str, ...] = ("data",),
                           cache: Optional[CacheConfig] = None,
                           expand_kernel: str = "auto",
                           fold_kernel: str = "auto",
                           emit_kernel: str = "auto"):
    """Build (jitted_fn, engine).  ``jitted_fn()`` -> (count, overflow).

    Work partition: shard i of D takes top-level guard runs
    [i·R/D, (i+1)·R/D); relations are replicated (closure constants); the
    final count is a psum over the mesh axes — the single collective.
    ``expand_kernel``/``fold_kernel``/``emit_kernel`` are resolved per
    spec before the ``shard_map`` trace (the registry choices are baked
    into the unrolled schedule, identically per shard).
    """
    cache = _resolve_cache_config(cache, None, default_slots=1 << 15)
    eng = StaticCLFTJ(q, td, order, db, capacity=capacity, cache=cache,
                      expand_kernel=expand_kernel, fold_kernel=fold_kernel,
                      emit_kernel=emit_kernel)
    part = _GuardPartition(eng, mesh, axes)
    eng.resolve_kernels("count")
    count_fn = eng.count_fn()

    def per_shard():
        with jax.enable_x64(True):
            total, ov = count_fn(part.shard_frontier())
            total = jax.lax.psum(total, part.all_axes)
            ov = jax.lax.psum(ov.astype(jnp.int32), part.all_axes)
            return total, ov

    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=(),
                       out_specs=(P(), P()), check_vma=False)
    return _X64Jit(fn), eng


def make_distributed_evaluate(q: CQ, td: TreeDecomposition,
                              order: Sequence[str], db: Database, mesh: Mesh,
                              capacity: int = 1 << 14,
                              axes: Tuple[str, ...] = ("data",),
                              cache: Optional[CacheConfig] = None,
                              expand_kernel: str = "auto",
                              fold_kernel: str = "auto",
                              emit_kernel: str = "auto"):
    """Build (eval_fn, engine) for payload-capable distributed evaluation.

    ``eval_fn(tables=None)`` runs one materialization pass over the mesh
    and returns ``(rows, stats, tables)``: each shard evaluates its guard-
    run slice through the static schedule with a *private* payload-capable
    tier-2 table + slab arena (shard-local splice, no coherence traffic),
    the host concatenates the per-shard ``(assign, valid)`` result chunks
    (the host-side merge — there is no result collective; count/overflow/
    replay-hit scalars are the only psums).  Tables are stacked on a
    leading shard axis and round-trip: pass the returned ``tables`` back
    in and recurring adhesion keys are served by slab splice
    (``stats["tier2_replay_hits"] > 0``) instead of re-expansion.
    Replay requires ``cache_payloads=True`` — the default here (unlike
    the count factory): an explicit payloads-off config still evaluates
    exactly, but its tables are count-only and every probe misses.
    """
    if cache is None:
        cache = CacheConfig(policy="direct", slots=1 << 15,
                            cache_payloads=True)
    cache = _resolve_cache_config(cache, None, default_slots=1 << 15)
    eng = StaticCLFTJ(q, td, order, db, capacity=capacity, cache=cache,
                      expand_kernel=expand_kernel, fold_kernel=fold_kernel,
                      emit_kernel=emit_kernel)
    part = _GuardPartition(eng, mesh, axes)
    d_total = part.d_total
    eng.resolve_kernels("evaluate")
    eval_fn = eng.evaluate_fn()
    spec = P(part.all_axes)
    with jax.enable_x64(True):
        template = eng.make_tables("evaluate")
    table_specs = jax.tree.map(lambda _: spec, template)

    def init_tables():
        with jax.enable_x64(True):
            # stack the spec template itself — building a second full
            # table set (slab arenas included) just to throw it away
            # would double the allocation per factory call
            return jax.tree.map(
                lambda x: jnp.repeat(x[None], d_total, axis=0), template)

    def per_shard(tables):
        with jax.enable_x64(True):
            local = jax.tree.map(lambda x: x[0], tables)
            assign, valid, total, ov, hits, local = eval_fn(
                part.shard_frontier(), local)
            total = jax.lax.psum(total, part.all_axes)
            ov = jax.lax.psum(ov.astype(jnp.int32), part.all_axes)
            hits = jax.lax.psum(hits, part.all_axes)
            return (assign[None], valid[None], total, ov, hits,
                    jax.tree.map(lambda x: x[None], local))

    fn = _X64Jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(table_specs,),
        out_specs=(spec, spec, P(), P(), P(), table_specs),
        check_vma=False))

    def run(tables: Optional[Dict[int, tuple]] = None):
        if tables is None:
            tables = init_tables()
        with mesh:
            assign, valid, total, ov, hits, tables = fn(tables)
        a, v, t, o, h = device_get((assign, valid, total, ov, hits),
                                   "dist-eval-rows")
        a, v = np.asarray(a), np.asarray(v)
        rows = np.concatenate([a[i][v[i]] for i in range(a.shape[0])],
                              axis=0) if a.shape[0] else \
            np.zeros((0, len(eng.order)), np.int32)
        # "overflow" is a bool on every evaluation surface
        # (evaluate_static included); the shard count rides separately
        stats = {"count": int(t), "overflow": bool(o),
                 "overflow_shards": int(o), "tier2_replay_hits": int(h)}
        return rows, stats, tables

    return run, eng


class _X64Jit:
    """jit wrapper that traces/lowers under enable_x64.

    The shard body builds int64 counts/keys, so the x64 scope must cover
    tracing *and* lowering; entering it only inside the traced function
    leaves lowering (triggered by the first call or ``.lower()`` outside
    any scope) with mixed 32/64-bit IR that fails stablehlo verification.
    """

    def __init__(self, fn):
        self._jit = jax.jit(fn)

    def __call__(self, *args, **kwargs):
        with jax.enable_x64(True):
            return self._jit(*args, **kwargs)

    def lower(self, *args, **kwargs):
        with jax.enable_x64(True):
            return self._jit.lower(*args, **kwargs)
