"""Vectorized CLFTJ in JAX — adhesion-keyed memoization for the frontier join.

TPU-native realization of the paper's Figure 2 (see DESIGN.md §2):

* **Tier 1 — intra-chunk dedup.**  On entering TD node ``c`` the frontier rows
  sharing an adhesion key μ|α are collapsed to unique representatives; the
  subtree is expanded once per distinct key and the resulting per-rep counts
  are scattered back as factor multipliers.  This is the paper's reuse
  executed as sort/segment data-parallel work, with zero persistent memory.

* **Tier 2 — persistent bounded cache.**  A pluggable device table per TD
  node (``core/cache.py``) — the paper's *dynamic cache size* knob (Fig 10)
  plus its admission/eviction flexibility (§3.4): direct-mapped,
  set-associative-LRU, or cost-aware, with an optional sizing controller
  that grows/shrinks tables between subtree launches under a slot budget.
  Caching is optional so correctness is unaffected.  Per the paper's own
  implementation, only adhesions of dimension <= 2 are cached (the packed
  int64 key limit).

Both tiers preserve LFTJ's guarantees: they only ever *skip recomputation of
subtrees whose count is already known*, exactly like the paper's cache[α, μ|α].

Control flow lives in ``core/schedule.py`` (DESIGN.md §2.5): the TD + order
are lowered once into a linear op schedule and this class only supplies the
data plane — the :class:`~.schedule.ScheduleExecutor` interprets the ops,
with both memoization tiers as executor capabilities.  ``evaluate()`` runs
the same schedule in materialization mode: tier-1 representatives are
replayed as row blocks through ``orig`` (the paper §3.4's factorized
intermediates), so the JAX engine now answers full-evaluation workloads —
and with ``CacheConfig(cache_payloads=True)`` tier 2 serves evaluation as
well, replaying cached factorized row blocks from the per-node slab arena
on every recurring adhesion key (DESIGN.md §2.6).
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .cache import CacheConfig, CacheManager
from .cq import CQ
from .clftj_ref import Plan
from .db import Database
from .frontier import Frontier, JaxTrieJoin, MAX_KEY_BITS
from .schedule import ScheduleExecutor, lower
from .td import TreeDecomposition

__all__ = ["JaxCachedTrieJoin", "jax_clftj_count", "jax_clftj_evaluate",
           "MAX_KEY_BITS"]

def _resolve_cache_config(cache: Optional[CacheConfig],
                          cached_nodes: Optional[frozenset],
                          default_slots: int) -> CacheConfig:
    """Default the tier-2 config and merge an explicit node filter.  (The
    legacy ``cache_slots`` int and its one-release DeprecationWarning
    shim were removed after the promised window — pass
    ``cache=CacheConfig(...)``.)"""
    if cache is None:
        cache = CacheConfig(policy="direct", slots=default_slots,
                            enabled_nodes=cached_nodes)
    elif cached_nodes is not None and cache.enabled_nodes is None:
        from dataclasses import replace as _replace
        cache = _replace(cache, enabled_nodes=cached_nodes)
    return cache


class JaxCachedTrieJoin(JaxTrieJoin):
    """CLFTJ over the frontier engine.

    Tier 2 is configured by ``cache`` (a :class:`CacheConfig`;
    ``slots=0`` disables tier 2).  ``dedup=False`` disables tier 1 (then
    it degenerates to vanilla LFTJ with per-subtree counting).
    ``expand_kernel`` selects the EXPAND kernel path
    (``"auto"|"pallas"|"xla"`` — kernels/registry.py)."""

    def __init__(self, q: CQ, td: TreeDecomposition, order: Sequence[str],
                 db: Database, capacity: int = 1 << 17, dedup: bool = True,
                 impl: str = "bsearch",
                 cached_nodes: Optional[frozenset] = None,
                 cache: Optional[CacheConfig] = None,
                 expand_kernel: str = "auto", emit_in_flight: int = 8,
                 fold_kernel: str = "auto", emit_kernel: str = "auto",
                 stream_interior: bool = True):
        super().__init__(q, order, db, capacity=capacity, impl=impl,
                         expand_kernel=expand_kernel,
                         emit_in_flight=emit_in_flight,
                         fold_kernel=fold_kernel, emit_kernel=emit_kernel,
                         stream_interior=stream_interior)
        self.plan = Plan.build(td, order)
        self.td = td
        cache = _resolve_cache_config(cache, cached_nodes,
                                      default_slots=1 << 16)
        self.dedup = dedup
        maxval = max((int(r.max()) if r.size else 0) for r in self.atom_rows)
        # keys that don't pack into int64 fields would alias distinct
        # adhesion assignments — both tiers must stay off (tier-1 dedup on
        # corrupted keys could merge rows that are not duplicates)
        self._keys_packable = maxval < (1 << MAX_KEY_BITS)
        self.cache_config = cache
        self.cache = CacheManager(cache)
        self.cache.expected_tables = sum(
            1 for v in range(td.num_nodes)
            if td.parent[v] >= 0 and self._node_cacheable(v))
        # the tentpole: TD + order lowered ONCE into the shared op schedule
        self.schedule = lower(self.n, plan=self.plan,
                              cacheable=self._node_cacheable,
                              dedup=self.dedup)
        self.stats = {"tier1_rows_collapsed": 0, "tier2_hits": 0,
                      "tier2_misses": 0, "tier2_probes": 0,
                      "tier2_inserts": 0, "tier2_evictions": 0,
                      "tier2_resizes": 0, "tier2_slots": 0,
                      "tier2_replay_hits": 0, "tier2_payload_flushes": 0,
                      "tier2_payload_skips": 0, "tier2_payload_throttled": 0,
                      "tier2_slab_rows": 0, "subtree_launches": 0,
                      "expand_rows_in": 0, "expand_candidates": 0,
                      "expand_rows_out": 0,
                      "fold_rows_replayed": 0, "fold_rows_spliced": 0,
                      "expand_calls_pallas": 0, "expand_calls_xla": 0,
                      "fold_calls_pallas": 0, "fold_calls_xla": 0,
                      "emit_calls_pallas": 0, "emit_calls_xla": 0}

    # -----------------------------------------------------------------
    def _node_cacheable(self, v: int) -> bool:
        """Can node v's adhesion be keyed at all (tier 1 *or* tier 2)?
        Independent of the slot count: ``slots=0`` disables only
        tier 2, never tier-1 dedup."""
        if not self._keys_packable:
            return False
        en = self.cache_config.enabled_nodes
        if en is not None and v not in en:
            return False
        return len(self.plan.adhesion_idx[v]) <= 2

    def _finalize(self, ex: ScheduleExecutor) -> None:
        agg = self.cache.stats(ex.cache_accumulators())
        self.stats["tier2_hits"] = agg["hits"]
        self.stats["tier2_misses"] = agg["misses"]
        self.stats["tier2_probes"] = agg["probes"]
        self.stats["tier2_inserts"] = agg["inserts"]
        self.stats["tier2_evictions"] = agg["evictions"]
        self.stats["tier2_resizes"] = agg["resizes"]
        self.stats["tier2_slots"] = agg["slots"]
        self.stats["tier2_replay_hits"] = agg.get("payload_hits", 0)
        self.stats["tier2_payload_flushes"] = agg.get("payload_flushes", 0)
        self.stats["tier2_payload_skips"] = agg.get("payload_skips", 0)
        self.stats["tier2_payload_throttled"] = agg.get(
            "payload_throttled", 0)
        self.stats["tier2_slab_rows"] = agg.get("slab_rows", 0)
        self.stats["tier1_rows_collapsed"] += ex.t1_rows_collapsed()
        self.stats["subtree_launches"] += ex.subtree_launches
        for k in ("expand_rows_in", "expand_candidates", "expand_rows_out",
                  "fold_rows_replayed", "fold_rows_spliced"):
            self.stats[k] += getattr(ex, k)
        for path, runs in ex.expand_path_runs.items():
            self.stats[f"expand_calls_{path}"] = (
                self.stats.get(f"expand_calls_{path}", 0) + runs)
        for path, runs in ex.fold_path_runs.items():
            self.stats[f"fold_calls_{path}"] = (
                self.stats.get(f"fold_calls_{path}", 0) + runs)
        for path, runs in ex.emit_path_runs.items():
            self.stats[f"emit_calls_{path}"] = (
                self.stats.get(f"emit_calls_{path}", 0) + runs)

    # -----------------------------------------------------------------
    def count(self) -> int:
        with jax.enable_x64(True):
            ex = ScheduleExecutor(self, mode="count")
            self.last_executor = ex  # op_runs / sync diagnostics
            total = ex.count()
            self._finalize(ex)
            return total

    def evaluate(self) -> Iterator[np.ndarray]:
        """Yields (k, n) int32 blocks of result assignments (order cols).

        Materialization mode of the same schedule: tier-1 representatives
        are replayed back through ``orig`` at every FOLD.  With
        ``cache=CacheConfig(cache_payloads=True)`` tier 2 participates
        too: recurring adhesion keys replay their cached factorized row
        blocks instead of re-expanding the bag (paper §3.4's evaluation
        discussion; ``stats["tier2_replay_hits"]`` counts the parent rows
        whose bag was served by splice — each such hit expands to its
        block's ``pay_len`` result rows).
        Count-only tables cannot replay tuples and are bypassed
        (optionality — the cache is never required for correctness)."""
        with jax.enable_x64(True):
            ex = ScheduleExecutor(self, mode="evaluate")
            self.last_executor = ex
            yield from ex.evaluate()
            self._finalize(ex)

    def evaluate_stream(self) -> Iterator[np.ndarray]:
        """Streaming evaluation (DESIGN.md §2.8): identical blocks, in the
        same order, as :meth:`evaluate`, but each block's device→host copy
        is issued asynchronously as it is produced — bounded by
        ``emit_in_flight`` — so copies overlap the next morsel's EXPAND
        instead of draining at pass end.  All tier-2 behavior (payload
        probe/splice/store) is unchanged: streaming only moves the output
        data plane."""
        with jax.enable_x64(True):
            ex = ScheduleExecutor(self, mode="evaluate")
            self.last_executor = ex
            try:
                yield from ex.evaluate_stream()
            finally:
                # a stream abandoned early (break / close) must still
                # fold whatever the executor did complete into stats —
                # stale previous-pass counters would read as current
                self._finalize(ex)


def jax_clftj_count(q: CQ, td: TreeDecomposition, order: Sequence[str],
                    db: Database, capacity: int = 1 << 17,
                    dedup: bool = True, impl: str = "bsearch",
                    cache: Optional[CacheConfig] = None,
                    expand_kernel: str = "auto",
                    fold_kernel: str = "auto",
                    emit_kernel: str = "auto") -> int:
    return JaxCachedTrieJoin(q, td, order, db, capacity=capacity,
                             dedup=dedup, impl=impl, cache=cache,
                             expand_kernel=expand_kernel,
                             fold_kernel=fold_kernel,
                             emit_kernel=emit_kernel).count()


def jax_clftj_evaluate(q: CQ, td: TreeDecomposition, order: Sequence[str],
                       db: Database, capacity: int = 1 << 17,
                       dedup: bool = True, impl: str = "bsearch",
                       cache: Optional[CacheConfig] = None,
                       expand_kernel: str = "auto",
                       fold_kernel: str = "auto",
                       emit_kernel: str = "auto") -> np.ndarray:
    """Materialize the full result as an (N, n) int32 array over ``order``
    columns — the JAX CLFTJ analogue of :func:`~.clftj_ref.clftj_evaluate`."""
    eng = JaxCachedTrieJoin(q, td, order, db, capacity=capacity,
                            dedup=dedup, impl=impl, cache=cache,
                            expand_kernel=expand_kernel,
                            fold_kernel=fold_kernel,
                            emit_kernel=emit_kernel)
    blocks = list(eng.evaluate())
    if not blocks:
        return np.zeros((0, len(eng.order)), np.int32)
    return np.concatenate(blocks, axis=0)
