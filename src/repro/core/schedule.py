"""Execution IR for the vectorized trie join: one schedule, many engines.

The CLFTJ control flow (paper Fig 2) used to be re-derived three times —
host recursion in ``frontier.py``, the cache-aware copy in
``cached_frontier.py``, and the statically-unrolled variant in
``distributed.py``.  Following Free Join's plan/execution split and
Veldhuizen's view of LFTJ as a composition of per-variable iterator ops,
this module lowers ``(CQ, TreeDecomposition, order)`` into a *linear
instruction schedule* over four ops:

  * ``EXPAND(d)``        — frontier expansion of order variable ``x_d``
  * ``ENTER_CHILD(c)``   — TD-node entry: tier-2 probe + tier-1 dedup,
                           parent chunk parked on an explicit frame stack
  * ``FOLD_CHILD(c)``    — TD-node exit: segment counts, tier-2 insert,
                           factor multiplication (count mode) or replay of
                           representative row blocks through ``orig``
                           (evaluate mode — the paper §3.4's factorized
                           intermediates, materialized; with
                           ``cache_payloads`` the blocks are also stored
                           in / spliced from the tier-2 slab arena)
  * ``EMIT``             — accumulate counts / yield result tuples

The TD recursion is flattened at lowering time: a subtree's ops are *data*
(a bracketed ``ENTER … FOLD`` span in the op list), not Python call frames.
Executors:

  * :class:`ScheduleExecutor` — the host-driven engine: morsel splitting,
    pluggable tier-2 cache (``core/cache.py``), batched chunk admission so
    ``valid.any()`` host syncs happen at most once per op execution (not
    per chunk — every sync is routed through :mod:`hostsync` and
    budget-tested), while parent morsels still run an ENTER…FOLD span
    sequentially so later morsels hit earlier morsels' tier-2 inserts.
  * :func:`execute_static` — a trace-time interpreter of the same schedule:
    fixed capacity, overflow flag instead of splitting, functional cache
    tables — one pure function for ``shard_map`` (``distributed.py``).

Cache, dedup, and sharding are therefore *executor capabilities* driven by
op flags, not engine-subclass overrides.
"""
from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..kernels.expand.xla import valid_first
from ..kernels.fold.xla import (_exit_order,
                                merge_compact as _merge_compact,
                                replay_step as _replay_step,
                                splice_step as _splice_step)
from .hostsync import AsyncFetchQueue, device_get, device_get_async

MAX_KEY_BITS = 21  # packed adhesion keys: values must fit in 21 bits
# EXPAND launches between admissions (each one blocking sync) of a level
ADMIT_BATCH = 128

# ---------------------------------------------------------------------------
# The IR
# ---------------------------------------------------------------------------

EXPAND = "expand"
ENTER_CHILD = "enter_child"
FOLD_CHILD = "fold_child"
EMIT = "emit"


@dataclass(frozen=True)
class Op:
    """One schedule instruction (see module docstring for semantics).

    ``probe``/``dedup`` are *eligibility* flags resolved at lowering time
    (key packs into int64, adhesion dim <= 2, node enabled, engine dedup
    setting); the executor still ANDs ``probe`` with its runtime cache
    state (manager enabled, table materialized, count-vs-evaluate mode).
    """

    kind: str
    d: int = -1                      # EXPAND: depth (order position)
    node: int = -1                   # ENTER/FOLD: TD node id
    adhesion: Tuple[int, ...] = ()   # ENTER/FOLD: order positions of α
    probe: bool = False              # ENTER: tier-2 eligible (FOLD: insert)
    dedup: bool = False              # ENTER: tier-1 eligible
    sub_first: int = -1              # FOLD: first depth owned inside t|c
    sub_last: int = -1               # FOLD: last depth owned inside t|c

    def __str__(self) -> str:
        if self.kind == EXPAND:
            return f"EXPAND(d={self.d})"
        if self.kind == ENTER_CHILD:
            return (f"ENTER_CHILD(c={self.node}, α={self.adhesion}, "
                    f"probe={self.probe}, dedup={self.dedup})")
        if self.kind == FOLD_CHILD:
            return (f"FOLD_CHILD(c={self.node}, "
                    f"sub=[{self.sub_first},{self.sub_last}])")
        return "EMIT"


@dataclass(frozen=True)
class Schedule:
    """A lowered, validated linear op list for one (query, TD, order)."""

    ops: Tuple[Op, ...]
    n: int  # number of order variables

    def __post_init__(self):
        depths = [op.d for op in self.ops if op.kind == EXPAND]
        if depths != list(range(self.n)):
            raise ValueError(f"EXPAND depths {depths} != 0..{self.n - 1}")
        if not self.ops or self.ops[-1].kind != EMIT:
            raise ValueError("schedule must end with EMIT")
        stack: List[int] = []
        for op in self.ops:
            if op.kind == ENTER_CHILD:
                stack.append(op.node)
            elif op.kind == FOLD_CHILD:
                if not stack or stack[-1] != op.node:
                    raise ValueError(
                        f"FOLD_CHILD({op.node}) does not match open "
                        f"ENTER stack {stack}")
                stack.pop()
        if stack:
            raise ValueError(f"unclosed ENTER_CHILD nodes {stack}")

    def describe(self) -> str:
        return "\n".join(str(op) for op in self.ops)

    def signature(self) -> str:
        """Stable structural hash of the lowered op list (kind, depth, node,
        adhesion and the eligibility flags of every op).  Two engines with
        equal signatures execute the same instruction stream, so persisted
        tier-2 state keyed by it (``repro/serve/persist.py``) can be
        replayed safely; a lowering change invalidates old snapshots by
        changing the signature, never by corrupting a replay."""
        import hashlib
        parts = [(op.kind, op.d, op.node, op.adhesion, op.probe, op.dedup,
                  op.sub_first, op.sub_last) for op in self.ops]
        blob = repr((self.n, parts)).encode()
        return hashlib.sha256(blob).hexdigest()[:16]


def lower(n: int, plan: Optional[Any] = None,
          cacheable: Optional[Callable[[int], bool]] = None,
          dedup: bool = True) -> Schedule:
    """Compile ``(order length, Plan)`` into a linear schedule.

    ``plan`` is a :class:`~.clftj_ref.Plan` (TD/order correspondence);
    ``plan=None`` lowers the vanilla LFTJ (no TD): EXPAND over every depth
    then EMIT.  ``cacheable(c)`` resolves per-node key eligibility
    (packability, adhesion dimension, enabled_nodes); ``dedup`` is the
    engine's tier-1 switch — both are baked into op flags so every
    executor runs the same gating.
    """
    ops: List[Op] = []
    if plan is None:
        ops.extend(Op(EXPAND, d=d) for d in range(n))
    else:
        can = cacheable if cacheable is not None else (lambda c: False)

        def emit_node(v: int) -> None:
            if v in plan.first_d:
                ops.extend(Op(EXPAND, d=d) for d in
                           range(plan.first_d[v], plan.last_d[v] + 1))
            for c in plan.td.children[v]:
                keyable = bool(can(c))
                adh = tuple(plan.adhesion_idx[c])
                ops.append(Op(ENTER_CHILD, node=c, adhesion=adh,
                              probe=keyable, dedup=keyable and dedup))
                emit_node(c)
                ops.append(Op(FOLD_CHILD, node=c, adhesion=adh,
                              probe=keyable, dedup=keyable and dedup,
                              sub_first=plan.first_d[c],
                              sub_last=plan.subtree_last[c]))

        emit_node(plan.td.root)
    ops.append(Op(EMIT))
    return Schedule(tuple(ops), n)


# ---------------------------------------------------------------------------
# Shared jitted chunk ops (used by every executor; chunk type is any
# Frontier-shaped NamedTuple — assign/factor/valid/orig/lo/hi)
# ---------------------------------------------------------------------------


def _pack_keys(assign: jnp.ndarray, idx: Tuple[int, ...],
               node: int) -> jnp.ndarray:
    """Pack <=2 adhesion columns + node id into one int64 key."""
    key = jnp.full((assign.shape[0],), np.int64(node))
    for i in idx:
        key = (key << MAX_KEY_BITS) | assign[:, i].astype(jnp.int64)
    return key


@jax.jit
def _dedup(keys: jnp.ndarray, active: jnp.ndarray):
    """Unique active keys: returns (first_idx, rep_of_row, n_reps).

    * ``first_idx[r]``   — row index of representative r (garbage for r >=
      n_reps),
    * ``rep_of_row[i]``  — representative id of row i (garbage if inactive),
    * ``n_reps``         — number of distinct active keys.
    """
    C = keys.shape[0]
    big = jnp.int64(2 ** 62)
    k = jnp.where(active, keys, big)  # inactive rows sort to the back
    order = jnp.argsort(k, stable=True)
    ks = k[order]
    isfirst = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    isfirst = isfirst & (ks != big)
    rep_sorted = jnp.cumsum(isfirst.astype(jnp.int32)) - 1
    n_reps = jnp.sum(isfirst.astype(jnp.int32))
    rep_of_row = jnp.zeros((C,), jnp.int32).at[order].set(rep_sorted)
    # first occurrence row index per rep (scatter-max; -1 writes are no-ops)
    first_idx = jnp.zeros((C,), jnp.int32).at[
        jnp.clip(rep_sorted, 0, C - 1)].max(
        jnp.where(isfirst, order, -1).astype(jnp.int32))
    return first_idx, rep_of_row, n_reps


@jax.jit
def _make_rep_frontier(F, first_idx: jnp.ndarray, n_reps: jnp.ndarray):
    C = F.assign.shape[0]
    rep_valid = jnp.arange(C, dtype=jnp.int32) < n_reps
    src = jnp.clip(first_idx, 0, C - 1)
    return F._replace(assign=F.assign[src],
                      factor=jnp.where(rep_valid, 1, 0).astype(jnp.int64),
                      valid=rep_valid,
                      orig=jnp.arange(C, dtype=jnp.int32),
                      lo=F.lo[src], hi=F.hi[src])


@jax.jit
def _identity_reps(F, active: jnp.ndarray):
    """Degenerate dedup: every active row is its own representative."""
    C = F.assign.shape[0]
    return F._replace(factor=jnp.where(active, 1, 0).astype(jnp.int64),
                      valid=active,
                      orig=jnp.arange(C, dtype=jnp.int32))


@jax.jit
def _apply_counts(F, hit, hvals, rep_of_row, cnt):
    mult = jnp.where(hit, hvals, cnt[jnp.clip(rep_of_row, 0, cnt.shape[0] - 1)])
    factor = F.factor * mult
    return F._replace(factor=factor, valid=F.valid & (factor > 0))


@jax.jit
def _chunk_stats(F) -> jnp.ndarray:
    """``[valid rows, min orig, max orig]`` of a valid-prefix chunk (the
    orig bounds over valid rows; empty chunks give ``[0, max, -1]``)."""
    big = jnp.iinfo(jnp.int32).max
    return jnp.stack([jnp.sum(F.valid.astype(jnp.int32)),
                      jnp.min(jnp.where(F.valid, F.orig, big)),
                      jnp.max(jnp.where(F.valid, F.orig, -1))])


@jax.jit
def _merge_chunks(A, B):
    """A's valid rows then B's, in order, packed into one chunk (the
    caller guarantees they fit); either may have holes in ``valid``."""
    C = A.valid.shape[0]
    cat = type(A)(*(jnp.concatenate([a, b]) for a, b in zip(A, B)))
    perm, k = valid_first(cat.valid, C)
    out = type(A)(*(x[perm] for x in cat))
    return out._replace(valid=jnp.arange(C) < k)


@functools.partial(jax.jit, static_argnames=("n_slots",))
def _segment_counts(exit_F, n_slots: int) -> jnp.ndarray:
    contrib = jnp.where(exit_F.valid, exit_F.factor, 0)
    return jnp.zeros((n_slots,), jnp.int64).at[
        jnp.clip(exit_F.orig, 0, n_slots - 1)].add(contrib)


@functools.partial(jax.jit, static_argnames=("d0", "d1", "sorted_exits"))
def _store_blocks(slab, E, poff, admit, *, d0: int, d1: int,
                  sorted_exits: bool = True):
    """Write one exit chunk's per-representative row blocks into the slab
    arena (tier-2 payload insert, evaluation mode).

    Exit rows are ordered by representative id exactly as in
    :func:`_replay_step` (``sorted_exits`` as there); rep *r*'s rows land
    contiguously at ``poff[r]``.
    Refused or invalid rows are routed to the arena's scratch row (the
    last one) — a masked ``.set`` must never target a live slot, or a
    "keep old value" no-op could land after a real write and clobber it.
    """
    C = E.assign.shape[0]
    R = slab.shape[0] - 1  # last row = scratch
    ecnt = jnp.zeros((C,), jnp.int32).at[
        jnp.clip(E.orig, 0, C - 1)].add(E.valid.astype(jnp.int32))
    eorder = _exit_order(E, sorted_exits)
    estart = jnp.cumsum(ecnt) - ecnt
    j = jnp.arange(C, dtype=jnp.int32)
    rep = jnp.clip(E.orig[eorder], 0, C - 1)
    ok = E.valid[eorder] & admit[rep]
    dest = jnp.where(ok, jnp.clip(poff[rep] + (j - estart[rep]), 0, R - 1),
                     R)
    rows = E.assign[eorder, d0:d1 + 1]
    return slab.at[dest].set(jnp.where(ok[:, None], rows, slab[dest]))


@functools.partial(jax.jit, static_argnames=("cap",))
def _alloc_blocks_static(bump, tplen, lens, cand, *, cap: int):
    """Functional twin of :meth:`~.cache.DeviceCache.alloc_blocks` for the
    trace-time executor: bump-allocate one batch of variable-length slab
    blocks with the arena state (``bump`` pointer, ``tplen`` metadata
    plane) threaded as traced values.  Same rules as the host allocator —
    blocks larger than the whole arena are refused outright; if the batch
    does not fit the remaining arena and the arena is non-empty, every
    payload is epoch-flushed (``tplen`` reset to -1) before admitting;
    candidates still beyond capacity are refused prefix-wise.  Returns
    ``(offsets, admitted, bump', tplen', flushed)``."""
    lens = jnp.where(cand, lens.astype(jnp.int32), 0)
    lens = jnp.where(lens <= cap, lens, 0)
    total = jnp.sum(lens)
    flushed = (total > cap - bump) & (bump > 0) & (total > 0)
    bump = jnp.where(flushed, 0, bump)
    tplen = jnp.where(flushed, jnp.full_like(tplen, -1), tplen)
    cum = jnp.cumsum(lens)
    admit = (lens > 0) & (cum <= cap - bump)
    offs = jnp.where(admit, bump + cum - lens, 0).astype(jnp.int32)
    bump = bump + jnp.sum(jnp.where(admit, lens, 0))
    return offs, admit, bump, tplen, flushed


# ---------------------------------------------------------------------------
# Host-driven executor
# ---------------------------------------------------------------------------
# (The FOLD-step primitives — replay, splice, merge — moved to
# ``kernels/fold/xla.py`` so the registry can dispatch FOLD between the
# XLA chain and the fused Pallas kernel; they are re-imported above under
# their historical underscore names for the static executor's direct
# fallback and for in-repo reference.)


@dataclass
class _Frame:
    """Parked parent chunk of one ENTER_CHILD (the explicit chunk-stack)."""

    F: Any                       # parent chunk
    keys: Optional[jnp.ndarray]
    hit: jnp.ndarray
    hvals: jnp.ndarray
    rep_of_row: jnp.ndarray
    first_idx: Optional[jnp.ndarray]
    n_reps: Optional[jnp.ndarray]
    use_t1: bool
    use_t2: bool
    # evaluation-mode tier-2: per-row payload pointers of the probe hits
    poff: Optional[jnp.ndarray] = None
    plen: Optional[jnp.ndarray] = None


class ScheduleExecutor:
    """Execute a :class:`Schedule` over morsel chunks (host-driven).

    A recursive interpreter over the linear op list: an ENTER…FOLD
    bracket executes its interior once per parent chunk (``_exec`` on the
    bracketed slice), folds, and continues past the bracket — the op list
    is still the single source of control flow; only the bracket nesting
    is walked as Python recursion (bounded by TD depth).

    Two orders compose here:

    * **Within an op, chunks batch.**  All chunks at an op are processed
      together, so device→host syncs are O(ops), not O(chunks): one
      planning fetch plus one batched ``valid.any()`` admission check per
      op execution, via :func:`hostsync.device_get`.
    * **Across an ENTER…FOLD span, parent chunks run sequentially.**
      Parent chunk *i*'s subtree is probed, expanded, and its results
      *inserted into the tier-2 table* before chunk *i+1* probes — the
      paper's cache[α, μ|α] reuse across morsels (Fig 10's hit rates
      come precisely from later morsels hitting earlier morsels'
      inserts; a probe-everything-then-insert pass would never hit
      within a query).

    ``mode="count"`` multiplies subtree counts into factors (tier 1 + 2);
    ``mode="evaluate"`` materializes tuples: FOLD replays representative
    row blocks through ``orig`` — drained one-shot by :meth:`evaluate`
    or streamed by :meth:`evaluate_stream` (blocks leave through a
    bounded async fetch queue as they are produced, and — with the
    engine's ``stream_interior`` knob on — every top-level parent
    morsel's continuations run the remaining schedule suffix
    immediately, interior spans included; DESIGN.md §2.8/§2.10).  The
    FOLD replay/splice steps and the EMIT pack are registry-dispatched
    kernels (``fold_kernel``/``emit_kernel`` knobs), like EXPAND.
    With ``cache_payloads`` on, evaluation
    also uses tier 2: ENTER probes the payload table, hit rows skip the
    bag entirely, and FOLD splices their cached factorized blocks back
    through the same jitted replay step while storing the miss
    representatives' fresh blocks (DESIGN.md §2.6).  Count-only tables
    are still bypassed — caching stays an optimization, never a
    correctness requirement.

    Instrumentation rides what the executor does anyway.  Each op
    execution is a host span in a profiler trace (``clftj.op.expand``,
    ``clftj.op.enter``, ``clftj.op.fold``, ``clftj.op.emit``; admission
    is ``clftj.admit``), closed before any ``yield``.  The EXPAND
    counters (``expand_rows_in``, ``expand_candidates``,
    ``expand_rows_out``) are read from the planning and admission
    fetches, the FOLD counters (``fold_rows_replayed``,
    ``fold_rows_spliced``) from the replay plan, and the device-side
    counters (tier-1 collapses, tier-2 accumulators) ride the pass's last
    fetch where it has one (``emit-total``, ``emit-rows``): no counter
    adds a sync.
    """

    def __init__(self, engine, mode: str = "count"):
        if mode not in ("count", "evaluate"):
            raise ValueError(mode)
        self.engine = engine
        self.schedule: Schedule = engine.schedule
        self.mode = mode
        self.cache = getattr(engine, "cache", None)
        self.dedup = bool(getattr(engine, "dedup", False))
        self._bracket: Dict[int, int] = {}
        open_pcs: List[int] = []
        for pc, op in enumerate(self.schedule.ops):
            if op.kind == ENTER_CHILD:
                open_pcs.append(pc)
            elif op.kind == FOLD_CHILD:
                self._bracket[open_pcs.pop()] = pc
        self._total = jnp.zeros((), jnp.int64)
        self._t1_collapsed = jnp.zeros((), jnp.int64)
        # host values of _device_stats(), when they rode the last fetch
        self._stats_h: Optional[Tuple[Any, Dict[int, Dict[str, Any]]]] = None
        self.subtree_launches = 0
        # EXPAND work: valid rows entering, (row, candidate) pairs laid
        # out, valid rows surviving — all read from fetches made anyway
        self.expand_rows_in = 0
        self.expand_candidates = 0
        self.expand_rows_out = 0
        # FOLD work (evaluate mode): rows its replay steps write from
        # subtree exits and its splice steps from tier-2 slab payloads —
        # summed from the replay plan the fold fetches anyway
        self.fold_rows_replayed = 0
        self.fold_rows_spliced = 0
        # op-execution counters: span interiors re-run once per parent
        # morsel, so the sync budget scales with these, never with the
        # number of chunks inside one op execution
        self.op_runs = {"expand": 0, "span": 0, "fold": 0, "emit": 0}
        # chunk launches per kernel path (the registry's choice is per
        # spec; see kernels/registry.py and Result.expand_paths /
        # Result.fold_paths)
        self.expand_path_runs = {"pallas": 0, "xla": 0}
        self.fold_path_runs = {"pallas": 0, "xla": 0}
        self.emit_path_runs = {"pallas": 0, "xla": 0}
        self._emitted: List[Tuple[Any, Any]] = []  # (packed, k) pairs
        # streaming emit (DESIGN.md §2.8): bound on in-flight device→host
        # block copies; with ``stream_interior`` (DESIGN.md §2.10) every
        # top-level parent morsel's fold continuations run the remaining
        # schedule suffix — interior spans included — the moment the fold
        # closes, instead of only a fold that happens to be the last op
        # before EMIT
        self.emit_in_flight = int(getattr(engine, "emit_in_flight", 8))
        self.stream_interior = bool(getattr(engine, "stream_interior",
                                            True))
        self._stream_async = False  # set per pass by _iter_emitted
        self.emitted_blocks = 0
        self.emit_queue: Optional[AsyncFetchQueue] = None  # set by stream

    # -- public entry points -------------------------------------------
    def count(self) -> int:
        for _ in self._iter_emitted():
            pass
        total, self._stats_h = device_get(
            (self._total, self._device_stats()), "emit-total")
        return int(total)

    def evaluate(self) -> Iterator[np.ndarray]:
        """Yields (k, n) int32 blocks of result assignments (order cols).

        One-shot drain: blocks are buffered on device until the pass
        completes, then fetched with a single batched sync (``emit-rows``).
        :meth:`evaluate_stream` is the overlapped alternative."""
        for pairs in self._iter_emitted():
            self._emitted.extend(pairs)
        if not self._emitted:
            return
        blocks, self._stats_h = device_get(
            (self._emitted, self._device_stats()), "emit-rows")
        for packed, k in blocks:
            k = int(k)
            if k:
                yield np.asarray(packed)[:k]

    def evaluate_stream(self) -> Iterator[np.ndarray]:
        """Streaming evaluation (DESIGN.md §2.8): yields the same (k, n)
        int32 blocks as :meth:`evaluate`, in the same (production) order,
        but each block's device→host copy is *issued asynchronously the
        moment the block is produced* — every interior-span fold's
        continuations run the remaining schedule suffix per parent morsel
        (DESIGN.md §2.10) and EMIT chunks enter a bounded
        :class:`~.hostsync.AsyncFetchQueue` whose copies overlap the next
        morsel's EXPAND work instead of draining in one blocking fetch at
        pass end.  Async issues ride ``SyncCounter.async_count``/
        ``label_counts["emit-stream"]``; the blocking-sync budget stays
        O(ops)."""
        # The queue persists on the ENGINE (double-buffered host staging
        # arrays survive across passes — the serving layer streams many
        # sessions through one plan-cached engine); per-pass accounting
        # (issued/high_water/labels) resets here so each session audits
        # its own issue counts.  Kept on self too so tests/benchmarks can
        # audit the in-flight bound after the stream is drained.
        queue = getattr(self.engine, "_emit_queue", None)
        if queue is None or queue.max_in_flight != self.emit_in_flight:
            queue = AsyncFetchQueue(self.emit_in_flight, double_buffer=True)
            self.engine._emit_queue = queue
        else:
            for _ in queue.drain():  # abandoned prior stream's leftovers
                pass
            queue.reset()
        self.emit_queue = queue
        for pairs in self._iter_emitted(stream=True):
            for pair in pairs:
                for done in queue.put(pair, "emit-stream"):
                    row = self._materialize(done)
                    if row is not None:
                        yield row
            for done in queue.poll():
                row = self._materialize(done)
                if row is not None:
                    yield row
        for done in queue.drain():
            row = self._materialize(done)
            if row is not None:
                yield row

    @staticmethod
    def _materialize(pair: Tuple[Any, Any]) -> Optional[np.ndarray]:
        packed, k = pair
        k = int(k)
        if k == 0:
            return None
        # copy out of the fetch buffer: with a double-buffered queue the
        # backing host array is recycled by a later fetch
        return np.array(np.asarray(packed)[:k])

    def _device_stats(self):
        """The device-side counters: tier-1 collapses and every tier-2
        table's accumulators (fetched with the pass's last sync)."""
        tables = self.cache.tables if self.cache is not None else {}
        return (self._t1_collapsed,
                {v: t.accumulators() for v, t in tables.items()})

    def t1_rows_collapsed(self) -> int:
        if self._stats_h is not None:
            return int(self._stats_h[0])
        # a pass with no final fetch (a stream) pays for its own
        return int(device_get(self._t1_collapsed, "stats-t1"))

    def cache_accumulators(self) -> Optional[Dict[int, Dict[str, Any]]]:
        """Host values of the tier-2 accumulators if they rode the pass's
        last fetch, else None (``CacheManager.stats`` then fetches)."""
        return None if self._stats_h is None else self._stats_h[1]

    # -- the interpreter -----------------------------------------------
    def _iter_emitted(self, stream: bool = False
                      ) -> Iterator[List[Tuple[Any, Any]]]:
        """Run the schedule; yields lists of emitted ``(packed, k)``
        device pairs — the registry-dispatched EMIT pack's output —
        (evaluate mode only; count mode yields nothing).

        With ``stream=True`` (and the engine's ``stream_interior`` knob
        on, the default), every *top-level* parent morsel's fold
        continuations run the remaining schedule suffix the moment their
        fold closes — interior spans included, not just a fold that
        happens to be the last op before EMIT — so result blocks reach
        the async emit queue while the next parent morsel still has
        device work in flight.  Per-table tier-2 probe/insert order is
        unchanged: one bracket's parent morsels still run sequentially,
        and a bracket's table is touched only by its own ENTER/FOLD ops,
        so forwarding a morsel through *later* brackets cannot reorder
        any table's operation sequence."""
        forward = (stream and self.mode == "evaluate"
                   and self.stream_interior)
        # in forwarding mode the replay plans ride async issues too
        # ("replay-plan-async"): see _fold_one_evaluate
        self._stream_async = forward
        yield from self._exec([self.engine.initial_frontier()], 0,
                              len(self.schedule.ops), 0, forward)

    def _exec(self, chunks: List[Any], pc: int, end: int, depth: int,
              forward: bool) -> Any:
        """Execute ``ops[pc:end]`` over ``chunks``: yields emitted block
        lists and *returns* the surviving chunks at ``end`` (a generator
        return value — callers consume it via ``yield from``)."""
        ops = self.schedule.ops
        while pc < end:
            op = ops[pc]
            if op.kind == EXPAND:
                with TraceAnnotation("clftj.op.expand"):
                    chunks = self._op_expand(chunks, op)
                pc += 1
            elif op.kind == ENTER_CHILD:
                fold_pc = self._bracket[pc]
                if not chunks:  # nothing reaches this subtree: skip span
                    pc = fold_pc + 1
                    continue
                self.op_runs["span"] += 1
                conts: List[Any] = []
                # parent chunks run the interior SEQUENTIALLY: chunk i's
                # subtree results are inserted into tier 2 before chunk
                # i+1 probes (cross-morsel reuse within one query)
                for F in chunks:
                    with TraceAnnotation("clftj.op.enter"):
                        frame, R = self._enter_one(F, op)
                    exits = yield from self._exec([R], pc + 1, fold_pc,
                                                  depth + 1, forward)
                    with TraceAnnotation("clftj.op.fold"):
                        parts = self._fold_one(frame, exits, ops[fold_pc])
                    if forward and depth == 0:
                        # interior-span streaming (DESIGN.md §2.10):
                        # this morsel's continuations run the suffix now
                        yield from self._exec(
                            self._admit(parts, "fold-admit"),
                            fold_pc + 1, end, depth, forward)
                    else:
                        conts.extend(parts)
                if forward and depth == 0:
                    return []  # the suffix already ran per parent morsel
                chunks = self._admit(conts, "fold-admit")
                pc = fold_pc + 1
            else:  # EMIT
                with TraceAnnotation("clftj.op.emit"):
                    pairs = self._op_emit(chunks)
                if pairs:  # spans close before a yield
                    yield pairs
                pc += 1
        return chunks

    # -- EMIT ----------------------------------------------------------
    def _op_emit(self, chunks) -> Optional[List[Tuple[Any, Any]]]:
        """Count mode: add the chunks' factors to the total.  Evaluate
        mode: pack valid rows to the front (registry-dispatched EMIT
        kernel) and return only the ``(packed, k)`` pairs — holding whole
        Frontiers until the fetch would keep factor/orig/lo/hi alive for
        every result chunk."""
        self.op_runs["emit"] += 1
        if self.mode == "count":
            for F in chunks:
                self._total = self._total + jnp.sum(
                    jnp.where(F.valid, F.factor, 0))
            return None
        if not chunks:
            return None
        efn = self.engine._emit_fn()
        path = getattr(self.engine, "emit_path", "xla")
        self.emit_path_runs[path] = (
            self.emit_path_runs.get(path, 0) + len(chunks))
        self.emitted_blocks += len(chunks)
        return [efn(F.assign, F.valid) for F in chunks]

    # -- EXPAND --------------------------------------------------------
    def _op_expand(self, chunks, op: Op):
        if not chunks:
            return []
        self.op_runs["expand"] += 1
        eng = self.engine
        d = op.d
        g_ai, rs, _ = eng.expand_plan(d)
        cap = eng.capacity
        # one planning fetch for every chunk at this op
        lo_h, hi_h, va_h = device_get(
            (jnp.stack([F.lo[:, g_ai] for F in chunks]),
             jnp.stack([F.hi[:, g_ai] for F in chunks]),
             jnp.stack([F.valid for F in chunks])), "expand-plan")
        self.expand_rows_in += int(va_h.sum())
        to_run: List[Any] = []
        oversized: List[Tuple[Any, np.ndarray]] = []
        for i, F in enumerate(chunks):
            r0 = np.searchsorted(rs, lo_h[i], side="left")
            r1 = np.searchsorted(rs, hi_h[i], side="left")
            counts = np.where(va_h[i], r1 - r0, 0).astype(np.int64)
            n_pairs = int(counts.sum())
            self.expand_candidates += n_pairs
            if n_pairs <= cap:
                to_run.append(F)
            else:
                oversized.append((F, counts))
        pieces: Iterator[Any] = iter(to_run)
        if oversized:
            # one batched fetch for every chunk that needs morsel splitting
            hosts = device_get([F._asdict() for F, _ in oversized],
                               "expand-split")
            pieces = itertools.chain(pieces, *(
                eng.split_chunk_host({k: np.asarray(v)
                                      for k, v in host.items()}, d, counts)
                for (_, counts), host in zip(oversized, hosts)))
        fn = eng._expand_fn(d)
        path = getattr(eng, "expand_paths", {}).get(d, "xla")
        # pieces go to the device lazily and their outputs are admitted
        # (coalesced) every ADMIT_BATCH launches, so a wide level holds
        # at most one batch of sparse outputs at a time
        kept: List[Any] = []
        while True:
            batch = [fn(F)[0] for F in itertools.islice(pieces, ADMIT_BATCH)]
            if not batch:
                return kept
            self.expand_path_runs[path] = (
                self.expand_path_runs.get(path, 0) + len(batch))
            carried = kept[-1:]
            kept[-1:], rows = self._admit_rows(carried + batch,
                                               "expand-admit")
            # the carried chunk's rows were counted with its own batch
            self.expand_rows_out += int(rows[len(carried):].sum())

    # -- ENTER_CHILD (one parent chunk) --------------------------------
    def _enter_one(self, F, op: Op) -> Tuple[_Frame, Any]:
        C = self.engine.capacity
        cache_on = self.cache is not None and self.cache.enabled
        # evaluation mode probes tier 2 only when row-block payloads are
        # on: count tables cannot replay tuples (the PR-2 bypass)
        use_t2 = op.probe and cache_on and (
            self.mode == "count" or self.cache.config.cache_payloads)
        use_t1 = op.dedup and self.dedup
        keys = (_pack_keys(F.assign, op.adhesion, op.node)
                if (op.probe or op.dedup) else None)
        poff = plen = None
        if use_t2 and self.mode == "evaluate":
            # a payload hit means: splice the cached factorized block at
            # FOLD instead of descending into the bag for this row
            hit, poff, plen = self.cache.get(op.node).probe_payload(
                keys, F.valid)
            hvals = jnp.zeros((C,), jnp.int64)
        elif use_t2:
            hit, hvals = self.cache.get(op.node).probe(keys, F.valid)
        else:
            hit = jnp.zeros((C,), bool)
            hvals = jnp.zeros((C,), jnp.int64)
        active = F.valid & ~hit
        if use_t1:
            first_idx, rep_of_row, n_reps = _dedup(keys, active)
            self._t1_collapsed = self._t1_collapsed + (
                jnp.sum(active.astype(jnp.int64)) - n_reps)
            R = _make_rep_frontier(F, first_idx, n_reps)
        else:
            first_idx, n_reps = None, None
            rep_of_row = jnp.arange(C, dtype=jnp.int32)
            R = _identity_reps(F, active)
        self.subtree_launches += 1
        return _Frame(F=F, keys=keys, hit=hit, hvals=hvals,
                      rep_of_row=rep_of_row, first_idx=first_idx,
                      n_reps=n_reps, use_t1=use_t1, use_t2=use_t2,
                      poff=poff, plen=plen), R

    # -- FOLD_CHILD (one parent chunk's subtree exits) -----------------
    def _fold_one(self, fr: _Frame, exits: List[Any], op: Op) -> List[Any]:
        self.op_runs["fold"] += 1
        if self.mode == "evaluate":
            return self._fold_one_evaluate(fr, exits, op)
        C = self.engine.capacity
        cnt = jnp.zeros((C,), jnp.int64)
        for E in exits:
            cnt = cnt + _segment_counts(E, C)
        if fr.use_t2:
            if fr.use_t1:
                rep_keys = fr.keys[jnp.clip(fr.first_idx, 0, C - 1)]
                rep_active = jnp.arange(C) < fr.n_reps
            else:
                rep_keys = fr.keys
                rep_active = fr.F.valid & ~fr.hit
            # insert BEFORE the next parent chunk's probe (cross-morsel
            # reuse — the entire point of tier 2 within one query)
            self.cache.get(op.node).insert(rep_keys, cnt, rep_active)
            self.cache.maybe_resize(op.node)
        return [_apply_counts(fr.F, fr.hit, fr.hvals, fr.rep_of_row, cnt)]

    def _fold_one_evaluate(self, fr: _Frame, exits: List[Any],
                           op: Op) -> List[Any]:
        use_pay = fr.use_t2
        if not exits and not use_pay:
            return []
        eng = self.engine
        C = eng.capacity
        d0, d1 = op.sub_first, op.sub_last
        keys_h = None
        if use_pay:
            # with tier-1 dedup off, every parent row is its own rep —
            # the store path needs the key values to collapse duplicates,
            # so they ride the same fetch (still one sync per fold)
            extra = ((fr.hit, fr.plen) if fr.use_t1
                     else (fr.hit, fr.plen, fr.keys))
        else:
            extra = ()
        pplan = (fr.rep_of_row, fr.F.valid & ~fr.hit) + extra
        if self._stream_async:
            # interior-streaming mode: the replay plan rides ASYNC issues
            # ("replay-plan-async") — one per exit chunk plus one for the
            # parent plan — so later exits' copies land while earlier
            # exits' replay launches are being enqueued; completing a
            # fetch (AsyncFetch.get) is not a counted blocking sync
            efetches = [device_get_async((E.orig, E.valid),
                                         "replay-plan-async")
                        for E in exits]
            host = device_get_async(pplan, "replay-plan-async").get()
            exits_h: List[Any] = [None] * len(exits)
        else:
            # ONE planning fetch per fold: exit orig/valid, the parent
            # rep map, and (payload mode) the probe's hit mask + block
            # lengths — the payload plan rides the same batched
            # device_get, O(ops) syncs
            efetches = None
            exits_h, host = device_get(
                ([(E.orig, E.valid) for E in exits], pplan), "replay-plan")
        ror_h, active_h = host[0], host[1]
        if use_pay:
            hit_h, plen_h = host[2], host[3]
            if not fr.use_t1:
                keys_h = host[4]
        active_dev = fr.F.valid & ~fr.hit
        # registry-dispatched FOLD steps (fold_kernel knob).  The fused
        # replay kernel needs sorted exits — guaranteed here: every exit
        # chunk is an EXPAND output or a fold continuation (bracket
        # interiors always contain >=1 EXPAND), both of which are
        # valid-prefix compacted with nondecreasing orig.
        fold_replay = eng._fold_fn(d0, d1, True, False) if exits else None
        rpath = getattr(eng, "fold_paths", {}).get(
            (d0, d1, True, False), "xla")
        out: List[Any] = []
        ecnts: List[np.ndarray] = []
        for j, E in enumerate(exits):
            eorig, evalid = (efetches[j].get() if efetches is not None
                             else exits_h[j])
            ecnt = np.zeros(C, np.int64)
            np.add.at(ecnt, np.clip(eorig, 0, C - 1),
                      evalid.astype(np.int64))
            ecnts.append(ecnt)
            pcnt = np.where(active_h, ecnt[np.clip(ror_h, 0, C - 1)], 0)
            self.fold_rows_replayed += int(pcnt.sum())
            for mask in _pack_parent_morsels(pcnt, C):
                cont, _stats = fold_replay(
                    fr.F, active_dev & jnp.asarray(mask), fr.rep_of_row, E)
                self.fold_path_runs[rpath] = (
                    self.fold_path_runs.get(rpath, 0) + 1)
                out.append(cont)
        if use_pay:
            tbl = self.cache.get(op.node)
            if hit_h.any():
                # splice FIRST: hit parents never descended into the bag —
                # their cached factorized blocks re-expand through the
                # replay step specialized to slab sources.  The probe's
                # (poff, plen) pointers are only guaranteed until this
                # table's next insert (which may epoch-flush and reuse the
                # arena rows), so the splice must precede the insert below.
                fold_splice = eng._fold_fn(d0, d1, False, True)
                spath = getattr(eng, "fold_paths", {}).get(
                    (d0, d1, False, True), "xla")
                pcnt = np.where(hit_h, plen_h, 0).astype(np.int64)
                self.fold_rows_spliced += int(pcnt.sum())
                for mask in _pack_parent_morsels(pcnt, C):
                    spl, _stats = fold_splice(
                        fr.F, fr.hit & jnp.asarray(mask), fr.poff, fr.plen,
                        tbl.slab)
                    self.fold_path_runs[spath] = (
                        self.fold_path_runs.get(spath, 0) + 1)
                    out.append(spl)
            # feed the admission throttle from the masks this fold already
            # fetched (no extra sync): probes = hit + miss parent rows
            n_hit = int(hit_h.sum())
            tbl.note_eval_probes(n_hit + int(active_h.sum()), n_hit)
            launches0 = tbl.window_launches
            if exits:
                probation = self.cache.config.payload_probation
                if tbl.store_throttled():
                    # keys don't recur on this table — stop paying the
                    # arena-write overhead.  Every Nth throttled fold
                    # still stores (probation): with nothing resident the
                    # hit rate could never recover on a workload shift.
                    tbl.payload_throttled += 1
                    if probation and tbl.payload_throttled % probation == 0:
                        self._insert_payload_blocks(fr, exits, ecnts,
                                                    active_h, keys_h, op)
                else:
                    # store the miss representatives' blocks BEFORE the
                    # next parent morsel probes (cross-morsel reuse, as in
                    # count mode); complete blocks only — a rep whose exit
                    # rows spread over several chunks would cache a
                    # partial result
                    self._insert_payload_blocks(fr, exits, ecnts,
                                                active_h, keys_h, op)
            # the sizing controller must keep running while the store
            # throttle is engaged (its whole point is handing memory back
            # on exactly these low-reuse tables) — its launch clock
            # normally advances via insert(), so tick it for insert-less
            # folds (throttled, or nothing eligible) before deciding
            if tbl.window_launches == launches0:
                tbl.window_launches = launches0 + 1
            self.cache.maybe_resize(op.node)
        return out

    def _insert_payload_blocks(self, fr: _Frame, exits: List[Any],
                               ecnts: List[np.ndarray], active_h,
                               keys_h: Optional[np.ndarray], op: Op
                               ) -> None:
        """Tier-2 payload insert at FOLD (evaluation mode): slab-write the
        representatives' row blocks and admit their keys.

        Morsel splitting partitions *rows* across exit chunks, so most
        representatives' exits live entirely in one chunk; a block is
        admitted from chunk *j* exactly when all of its rep's exit rows
        are in chunk *j* (``ecnt_j == total``).  Reps genuinely spread
        over chunks (oversized-row splits, nested-subtree morsels) would
        cache a *partial* — hence wrong — result and are skipped, which
        only costs recomputation (optionality)."""
        tbl = self.cache.get(op.node)
        C = self.engine.capacity
        total = ecnts[0] if len(ecnts) == 1 else np.sum(ecnts, axis=0)
        if fr.use_t1:
            # valid reps are exactly the rows ecnt can be nonzero at
            rep_keys = fr.keys[jnp.clip(fr.first_idx, 0, C - 1)]
            eligible = total > 0
        else:
            rep_keys = fr.keys
            eligible = (total > 0) & active_h
            if keys_h is not None:
                # dedup off: duplicate adhesion keys each carry their own
                # (identical) block, but only one copy per key can be
                # admitted — keep the first, or the rest leak arena rows
                big = np.int64(2 ** 62)
                k = np.where(eligible, keys_h, big)
                order = np.argsort(k, kind="stable")
                ks = k[order]
                isfirst = np.ones(ks.shape[0], bool)
                isfirst[1:] = ks[1:] != ks[:-1]
                isfirst &= ks != big
                first = np.zeros_like(eligible)
                first[order[isfirst]] = True
                eligible &= first
        stored = np.zeros(C, bool)
        poff_all = np.zeros(C, np.int32)
        flushes0 = tbl.payload_flushes
        for E, ecnt in zip(exits, ecnts):
            cand = eligible & (ecnt == total)
            if not cand.any():
                continue  # empty subtrees are not cached (no negatives)
            tbl.ensure_slab(op.sub_last - op.sub_first + 1)
            poff_np, admit_np = tbl.alloc_blocks(ecnt, cand)
            if tbl.payload_flushes != flushes0:
                # an epoch flush rewound the arena mid-fold: offsets
                # accumulated from earlier chunks may now be overwritten —
                # drop them from the batched admission (recompute later)
                stored[:] = False
                flushes0 = tbl.payload_flushes
            if not admit_np.any():
                continue
            tbl.slab = _store_blocks(tbl.slab, E, jnp.asarray(poff_np),
                                     jnp.asarray(admit_np),
                                     d0=op.sub_first, d1=op.sub_last)
            poff_all = np.where(admit_np, poff_np, poff_all)
            stored |= admit_np
        if stored.any():
            # one batched key admission for the whole fold (a rep is
            # complete in at most one chunk, so the admit sets are
            # disjoint); vals = block length = the exact subtree count
            # (factors are all 1 in evaluation mode), so count() can
            # reuse the entries
            lens = jnp.asarray(total)
            tbl.insert(rep_keys, lens, jnp.asarray(stored),
                       poff=jnp.asarray(poff_all),
                       plen=lens.astype(jnp.int32))
        tbl.payload_skips += int((eligible & ~stored).sum())

    # -- shared --------------------------------------------------------
    def _admit(self, out, label: str):
        """Drop empty chunks and coalesce sparse neighbours, with ONE
        batched host sync for the whole op.

        In count mode, consecutive chunks merge while their valid rows fit
        one chunk and ``orig`` stays nondecreasing across the seam (the
        sorted-exits invariant), so the row sequence is unchanged.
        Without this an op over a wide level keeps every sparse output
        chunk alive: the level's device memory then scales with the
        candidate count instead of the surviving rows.  Evaluation keeps
        chunk boundaries: they decide which tier-2 probes a payload insert
        precedes, hence the order of replayed and spliced rows, and a
        streamed pass must emit the rows of a one-shot pass in order."""
        return self._admit_rows(out, label)[0]

    def _admit_rows(self, out, label: str) -> Tuple[List[Any], np.ndarray]:
        """:meth:`_admit`, also returning the valid rows of each chunk of
        ``out`` (read from the same fetch)."""
        if not out:
            return [], np.zeros(0, np.int64)
        C = self.engine.capacity
        coalesce = self.mode == "count"
        with TraceAnnotation("clftj.admit"):
            stats = np.asarray(device_get(
                jnp.stack([_chunk_stats(F) for F in out]), label))
            kept: List[Any] = []
            acc, acc_n, acc_hi = None, 0, 0
            for F, (n, lo, hi) in zip(out, stats.tolist()):
                if n == 0:
                    continue
                if (coalesce and acc is not None and acc_n + n <= C
                        and acc_hi <= lo):
                    acc = _merge_chunks(acc, F)
                    acc_n, acc_hi = acc_n + n, hi
                    continue
                if acc is not None:
                    kept.append(acc)
                acc, acc_n, acc_hi = F, n, hi
            if acc is not None:
                kept.append(acc)
        return kept, stats[:, 0]


def _pack_parent_morsels(pcnt: np.ndarray, cap: int) -> List[np.ndarray]:
    """Greedy-pack parent rows into masks whose total replay size fits one
    chunk.  A single parent's pair count is <= the exit chunk's valid rows
    <= cap, so packing always succeeds."""
    masks: List[np.ndarray] = []
    cur = np.zeros(pcnt.shape[0], bool)
    acc = 0
    for i in np.flatnonzero(pcnt > 0):
        c = int(pcnt[i])
        if acc and acc + c > cap:
            masks.append(cur)
            cur = np.zeros(pcnt.shape[0], bool)
            acc = 0
        cur[i] = True
        acc += c
    if acc:
        masks.append(cur)
    return masks


# ---------------------------------------------------------------------------
# Static (fully-jittable) executor
# ---------------------------------------------------------------------------


def execute_static(schedule: Schedule, engine, F0, tables: Dict[int, tuple],
                   cfg, mode: str = "count"):
    """Trace-time interpreter of ``schedule``: one pure computation.

    Fixed chunk capacity (overflow is flagged, not split), tier-2 tables
    threaded functionally, LRU tick statically unrolled.  ``tables[c]`` is
    either the count-only ``(keys, vals, used, stamp, cost)`` tuple of
    ``core/cache.py`` or — payload-capable evaluation (DESIGN.md §2.8) —
    the 9-tuple extending it with ``(pay_off, pay_len, slab, bump)``: the
    §2.6 row-block region with the arena bump pointer as a traced scalar,
    so slab allocation/epoch-flush happen inside the pure computation
    (:func:`_alloc_blocks_static`).

    ``mode="count"`` returns ``(count, overflow, tables)`` —
    ``shard_map``-able as-is.  ``mode="evaluate"`` materializes: FOLD
    replays miss representatives through ``orig``, splices payload hits
    from the slab (hit rows never descend into the bag), and merges both
    continuations into the one fixed-capacity chunk — as ONE
    registry-dispatched FOLD step (``engine._fold_fn``; the fused Pallas
    kernel or the XLA chain of ``kernels/fold/``, per the ``fold_kernel``
    knob), overflow-checked against the step's stats triple — then stores
    the fresh blocks; returns ``(assign, valid, count, overflow,
    replay_hits, tables)`` where ``(assign, valid)`` is the result chunk
    with the valid rows packed to the front by the registry-dispatched
    EMIT kernel (``emit_kernel`` knob).  The fused FOLD requires its exit
    chunk sorted by ``orig``; the interpreter tracks that invariant
    statically (EXPAND preserves it, a merged fold output breaks it) and
    routes folds over unsorted exits straight to the XLA chain.
    Count-only tables are bypassed in evaluation mode (optionality), as in
    the host executor.  EXPAND ops route through the same
    registry-dispatched kernels as the host executor (``engine._expand_fn``
    resolves the ``expand_kernel`` knob at build time, so the choice is
    baked in before tracing).
    """
    from .cache import (_insert as cache_insert, _probe as cache_probe,
                        _probe_payload as cache_probe_payload)
    if mode not in ("count", "evaluate"):
        raise ValueError(mode)
    C = engine.capacity
    F = F0
    ov = jnp.zeros((), bool)
    stack: List[tuple] = []
    tick = 0
    total = jnp.zeros((), jnp.int64)
    n_replay = jnp.zeros((), jnp.int64)
    rows = rvalid = None
    # static sortedness tracking for the fused FOLD's sorted-exit
    # precondition: F0's orig is constant/ascending (True); ENTER resets
    # the flag (rep frontiers carry orig=arange); EXPAND gathers orig
    # monotonically, preserving it; a replay-only fold output is sorted
    # iff its parent was; a merged (replay+splice) output is two sorted
    # regions concatenated — not globally sorted
    sorted_now = True
    for op in schedule.ops:
        if op.kind == EXPAND:
            F, needed = engine._expand_fn(op.d)(F)
            ov = ov | (needed > C)
        elif op.kind == ENTER_CHILD:
            keys = (_pack_keys(F.assign, op.adhesion, op.node)
                    if (op.probe or op.dedup) else None)
            tbl = tables.get(op.node)
            has_pay = tbl is not None and len(tbl) > 5
            # evaluation probes tier 2 only on payload-capable tables:
            # count-only entries cannot replay tuples (optionality)
            use_t2 = op.probe and tbl is not None and (
                mode == "count" or has_pay)
            poff = plen = None
            if use_t2 and mode == "evaluate":
                tk, tv, tu, ts, tc, tpoff, tplen, slab, bump = tbl
                tick += 1
                hit, poff, plen, ts = cache_probe_payload(
                    tk, tu, ts, tpoff, tplen, keys, F.valid,
                    jnp.int32(tick))
                hvals = jnp.zeros((C,), jnp.int64)
                n_replay = n_replay + jnp.sum(hit.astype(jnp.int64))
                tables = dict(tables)
                tables[op.node] = (tk, tv, tu, ts, tc, tpoff, tplen,
                                   slab, bump)
            elif use_t2:
                tk, tv, tu, ts, tc = tbl[:5]
                tick += 1
                hit, hvals, ts = cache_probe(tk, tv, tu, ts, keys, F.valid,
                                             jnp.int32(tick))
                tables = dict(tables)
                tables[op.node] = (tk, tv, tu, ts, tc) + tuple(tbl[5:])
            else:
                hit = jnp.zeros((C,), bool)
                hvals = jnp.zeros((C,), jnp.int64)
            active = F.valid & ~hit
            if op.dedup:
                first_idx, rep_of_row, n_reps = _dedup(keys, active)
                R = _make_rep_frontier(F, first_idx, n_reps)
            else:
                first_idx, n_reps = None, None
                rep_of_row = jnp.arange(C, dtype=jnp.int32)
                R = _identity_reps(F, active)
            stack.append((F, keys, hit, hvals, rep_of_row, first_idx,
                          n_reps, active, use_t2, poff, plen, sorted_now))
            F = R
            sorted_now = True  # rep frontiers carry orig = arange
        elif op.kind == FOLD_CHILD:
            (P, keys, hit, hvals, rep_of_row, first_idx, n_reps, active,
             use_t2, poff, plen, parent_sorted) = stack.pop()
            if mode == "evaluate":
                E, e_sorted = F, sorted_now
                d0, d1 = op.sub_first, op.sub_last
                # one registry-dispatched FOLD step: replay the miss
                # representatives' exits through orig and (payload
                # tables) splice + merge the hit rows' cached blocks.
                # The fused kernel needs E sorted; an unsorted exit
                # (nested merged fold) routes to the XLA chain directly.
                if e_sorted:
                    ffn = engine._fold_fn(d0, d1, True, use_t2)
                else:
                    from ..kernels.fold import xla as _fxla
                    ffn = _fxla.build(d0=d0, d1=d1, with_replay=True,
                                      with_splice=use_t2,
                                      sorted_exits=False)
                if use_t2:
                    (tk, tv, tu, ts, tc, tpoff, tplen, slab,
                     bump) = tables[op.node]
                    # the splice happens BEFORE this table's insert (an
                    # epoch flush below may reuse the probed arena rows);
                    # the host executor pre-packs hit morsels to fit,
                    # but the static path replays + splices everything
                    # at once, so all three stats figures (replay pairs,
                    # splice rows, merged valid total) are
                    # overflow-checked explicitly
                    F, stats = ffn(P, active, rep_of_row, E,
                                   hit, poff, plen, slab)
                    ov = (ov | (stats[0] > C) | (stats[1] > C)
                          | (stats[2] > C))
                    sorted_now = False  # two sorted regions, concatenated
                    # store the miss reps' blocks: single exit chunk, so
                    # every rep's block is complete by construction
                    ecnt = jnp.zeros((C,), jnp.int32).at[
                        jnp.clip(E.orig, 0, C - 1)].add(
                        E.valid.astype(jnp.int32))
                    if op.dedup:
                        rep_keys = keys[jnp.clip(first_idx, 0, C - 1)]
                        eligible = (ecnt > 0) & (jnp.arange(C) < n_reps)
                    else:
                        rep_keys = keys
                        eligible = (ecnt > 0) & active
                        # duplicate adhesion keys: only the first
                        # occurrence may store (or the rest leak arena
                        # rows), mirroring the host executor's host-side
                        # collapse
                        fi, _, nr = _dedup(keys, eligible)
                        isrep = jnp.zeros((C,), jnp.int32).at[
                            jnp.clip(fi, 0, C - 1)].max(
                            (jnp.arange(C) < nr).astype(jnp.int32))
                        eligible = eligible & (isrep > 0)
                    offs, admit, bump, tplen, _fl = _alloc_blocks_static(
                        bump, tplen, ecnt, eligible,
                        cap=int(cfg.payload_rows))
                    slab = _store_blocks(slab, E, offs, admit,
                                         d0=d0, d1=d1,
                                         sorted_exits=e_sorted)
                    tick += 1
                    lens = ecnt.astype(jnp.int64)
                    out = cache_insert(
                        tk, tv, tu, ts, tc, rep_keys, lens,
                        jnp.maximum(lens, 1), admit, jnp.int32(tick),
                        policy=cfg.policy, rounds=min(cfg.ways, 8),
                        pay=(tpoff, tplen, offs, ecnt))
                    tables = dict(tables)
                    tables[op.node] = out[:7] + (slab, bump)
                else:
                    F, stats = ffn(P, active, rep_of_row, E)
                    ov = ov | (stats[0] > C)
                    # the continuation inherits the parent's row order
                    sorted_now = parent_sorted
            else:
                cnt = _segment_counts(F, C)
                sorted_now = parent_sorted  # _apply_counts keeps row order
                if use_t2:
                    if op.dedup:
                        rep_keys = keys[jnp.clip(first_idx, 0, C - 1)]
                        rep_active = jnp.arange(C) < n_reps
                    else:
                        rep_keys, rep_active = keys, active
                    tbl = tables[op.node]
                    tick += 1
                    if len(tbl) > 5:
                        # payload table in count mode: carry the metadata
                        # planes with the -1 sentinel, so an evicting
                        # count insert never leaves a stale block
                        # reachable (the §2.6 eviction-coupling rule)
                        tpoff, tplen, slab, bump = tbl[5:]
                        sent_off = jnp.zeros((C,), jnp.int32)
                        sent_len = jnp.full((C,), -1, jnp.int32)
                        out = cache_insert(
                            *tbl[:5], rep_keys, cnt, jnp.maximum(cnt, 1),
                            rep_active, jnp.int32(tick), policy=cfg.policy,
                            rounds=min(cfg.ways, 8),
                            pay=(tpoff, tplen, sent_off, sent_len))
                        new_tbl = out[:7] + (slab, bump)
                    else:
                        out = cache_insert(*tbl, rep_keys, cnt,
                                           jnp.maximum(cnt, 1), rep_active,
                                           jnp.int32(tick),
                                           policy=cfg.policy,
                                           rounds=min(cfg.ways, 8))
                        new_tbl = out[:5]
                    tables = dict(tables)
                    tables[op.node] = new_tbl
                F = _apply_counts(P, hit, hvals, rep_of_row, cnt)
        else:  # EMIT
            if mode == "count":
                total = jnp.sum(jnp.where(F.valid, F.factor, 0))
            else:
                # registry-dispatched EMIT pack: valid rows to the front,
                # result mask becomes a plain prefix predicate
                rows, k = engine._emit_fn()(F.assign, F.valid)
                rvalid = jnp.arange(C, dtype=jnp.int32) < k
                total = k.astype(jnp.int64)
    if mode == "count":
        return total, ov, tables
    return rows, rvalid, total, ov, n_replay, tables
