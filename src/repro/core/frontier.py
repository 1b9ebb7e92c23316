"""Vectorized (level-synchronous) trie join in JAX — the TPU-native LFTJ.

See DESIGN.md §2.  The depth-first RJoin recursion of the paper's Figure 1 is
re-derived as breadth-first *frontier expansion*: a frontier is a fixed
capacity matrix of partial assignments (+ per-atom trie ranges); expanding
variable ``x_d`` enumerates, for every row, the distinct candidate values of a
*guard* atom (via precomputed run-start arrays — the columnar trie) and
verifies membership in every other participating atom with batched bounded
binary search.  The expansion step itself is a kernel behind the dispatch
registry (``kernels/registry.py`` → fused Pallas or the XLA op chain in
``kernels/expand/``, per the ``expand_kernel`` knob; DESIGN.md §2.7).
The frontier after level d contains
exactly the depth-d partial assignments LFTJ would visit, so worst-case
optimality is inherited.  The static chunk capacity bounds *device* memory
per launch (each morsel is one fixed-shape chunk); the executor holds a
level's morsels on the host side of the schedule pass, so host/heap use
scales with the widest frontier level.  Evaluation mode either buffers
emitted ``(assign, valid)`` blocks until the pass completes
(``evaluate()``, one batched drain) or streams them
(``evaluate_stream()``: each block's device→host copy is issued
asynchronously as it is produced, bounded by ``emit_in_flight`` —
DESIGN.md §2.8).  A frontier row spliced
from the tier-2 payload slab (cached-subtree replay, DESIGN.md §2.6) is
indistinguishable downstream from one produced by expansion — the cache
only ever substitutes for recomputation.

Execution goes through the shared instruction schedule (DESIGN.md §2.5):
this class owns the *data plane* (tries, guard selection, the jitted
expansion step, morsel splitting); control flow — which op runs when, chunk
admission, count/evaluate emission — is ``core/schedule.py``'s
:class:`~.schedule.ScheduleExecutor` interpreting the lowered op list.

Counting uses 64-bit factors; engine entry points run under an
``enable_x64`` scope (the LM substrate stays 32-bit — the scope is local).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, NamedTuple, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..kernels import registry as kernels
from .cq import CQ
from .db import Database
from .schedule import MAX_KEY_BITS, ScheduleExecutor, lower

__all__ = ["MAX_KEY_BITS", "Frontier", "AtomLevel", "JaxTrieJoin",
           "jax_lftj_count", "jax_lftj_evaluate"]


class Frontier(NamedTuple):
    """One fixed-capacity chunk of partial assignments (a morsel)."""

    assign: jnp.ndarray   # (C, n) int32  — assignment columns (valid prefix)
    factor: jnp.ndarray   # (C,)  int64  — carried count factor (paper's f)
    valid: jnp.ndarray    # (C,)  bool
    orig: jnp.ndarray     # (C,)  int32  — origin row for segment aggregation
    lo: jnp.ndarray       # (C, m) int32 — per-atom trie range start
    hi: jnp.ndarray       # (C, m) int32 — per-atom trie range end


@dataclass(frozen=True)
class AtomLevel:
    """Columnar trie level: value column + run-start index (CSR)."""

    col: jnp.ndarray        # (N,) int32 — rows[:, level]
    runstarts: jnp.ndarray  # (R,) int32 — positions where rows[:, :level+1] changes
    col_np: np.ndarray
    runstarts_np: np.ndarray


def _build_levels(rows: np.ndarray) -> List[AtomLevel]:
    n, k = rows.shape
    levels = []
    for l in range(k):
        if n == 0:
            rs = np.zeros(0, dtype=np.int32)
        else:
            prefix = rows[:, :l + 1]
            change = np.ones(n, dtype=bool)
            change[1:] = (prefix[1:] != prefix[:-1]).any(axis=1)
            rs = np.flatnonzero(change).astype(np.int32)
        col = rows[:, l].astype(np.int32)
        levels.append(AtomLevel(jnp.asarray(col), jnp.asarray(rs), col, rs))
    return levels


class JaxTrieJoin:
    """Vectorized LFTJ: count / evaluate a full CQ over a fixed order."""

    def __init__(self, q: CQ, order: Sequence[str], db: Database,
                 capacity: int = 1 << 17, impl: str = "bsearch",
                 expand_kernel: str = "auto", emit_in_flight: int = 8,
                 fold_kernel: str = "auto", emit_kernel: str = "auto",
                 stream_interior: bool = True):
        if expand_kernel not in kernels.EXPAND_MODES:
            raise ValueError(f"expand_kernel must be one of "
                             f"{kernels.EXPAND_MODES}, got {expand_kernel!r}")
        if fold_kernel not in kernels.KERNEL_MODES:
            raise ValueError(f"fold_kernel must be one of "
                             f"{kernels.KERNEL_MODES}, got {fold_kernel!r}")
        if emit_kernel not in kernels.KERNEL_MODES:
            raise ValueError(f"emit_kernel must be one of "
                             f"{kernels.KERNEL_MODES}, got {emit_kernel!r}")
        self.q = q
        self.order = tuple(order)
        self.n = len(self.order)
        self.db = db
        self.capacity = int(capacity)
        self.impl = impl
        self.expand_kernel = expand_kernel
        self.fold_kernel = fold_kernel
        self.emit_kernel = emit_kernel
        # streaming-emit bound: max in-flight device→host result-block
        # copies (DESIGN.md §2.8); consumed by ScheduleExecutor.  With
        # ``stream_interior`` (the default) evaluate_stream also forwards
        # each top-level parent morsel's fold continuations through the
        # remaining schedule suffix immediately (DESIGN.md §2.10)
        self.emit_in_flight = int(emit_in_flight)
        self.stream_interior = bool(stream_interior)
        # depth -> impl the registry resolved for that EXPAND(d)
        self.expand_paths: Dict[int, str] = {}
        # (d0, d1, with_replay, with_splice) -> resolved FOLD impl
        self.fold_paths: Dict[Tuple[int, int, bool, bool], str] = {}
        self.emit_path: str = "xla"  # resolved on first _emit_fn build
        pos = {x: i for i, x in enumerate(self.order)}

        # per-atom tries, variables permuted into global order
        self.atom_rows: List[np.ndarray] = []
        self.atom_vars: List[Tuple[str, ...]] = []
        for a in q.atoms:
            uniq, first_col = [], {}
            for c, v in enumerate(a.vars):
                if v not in first_col:
                    first_col[v] = c
                    uniq.append(v)
            ordered = tuple(sorted(uniq, key=pos.get))
            rows = db.relations[a.relation]
            for c, v in enumerate(a.vars):
                if first_col[v] != c:
                    rows = rows[rows[:, c] == rows[:, first_col[v]]]
            rows = np.unique(rows[:, [first_col[v] for v in ordered]], axis=0)
            if rows.size and int(rows.max()) >= (1 << 31) - 1:
                raise ValueError("values must fit int32")
            self.atom_rows.append(rows.astype(np.int64))
            self.atom_vars.append(ordered)
        self.m = len(q.atoms)
        self.levels: List[List[AtomLevel]] = [
            _build_levels(r) for r in self.atom_rows]
        self.sizes = [r.shape[0] for r in self.atom_rows]

        # participants per depth; guard = the atom whose trie has the
        # DEEPEST bound prefix (most selective sibling list — LFTJ's seek
        # discipline), tie-broken by smaller relation.  Choosing by relation
        # size alone can pick an unconstrained level-0 iterator and blow the
        # frontier up by the whole value domain (§Perf join iteration log).
        self.at_depth: List[List[Tuple[int, int]]] = []
        self.guard: List[int] = []
        for x in self.order:
            parts = [(ai, self.atom_vars[ai].index(x))
                     for ai in range(self.m) if x in self.atom_vars[ai]]
            assert parts, f"variable {x} not covered"
            self.at_depth.append(parts)
            scores = [lvl * (1 << 40) - self.sizes[ai] for ai, lvl in parts]
            self.guard.append(int(np.argmax(scores)))
        self._expand_jits: Dict[int, object] = {}
        self._fold_jits: Dict[Tuple[int, int, bool, bool], object] = {}
        self._emit_jit: object = None
        # vanilla LFTJ lowers to the trivial schedule: EXPAND over every
        # depth, then EMIT (subclasses re-lower with their TD plan)
        self.schedule = lower(self.n)

    # ------------------------------------------------------------------
    def initial_frontier(self) -> Frontier:
        C, n, m = self.capacity, self.n, self.m
        lo = jnp.zeros((C, m), jnp.int32)
        hi = jnp.zeros((C, m), jnp.int32).at[0, :].set(
            jnp.asarray(self.sizes, jnp.int32))
        return Frontier(
            assign=jnp.zeros((C, n), jnp.int32),
            factor=jnp.zeros((C,), jnp.int64).at[0].set(1),
            valid=jnp.zeros((C,), bool).at[0].set(True),
            orig=jnp.zeros((C,), jnp.int32),
            lo=lo, hi=hi)

    # ------------------------------------------------------------------
    def _expand_fn(self, d: int):
        """Return the registry-dispatched expansion step for depth d
        (fused Pallas or the XLA chain, per ``expand_kernel`` — the
        chosen path is recorded in ``expand_paths[d]``).  The XLA step
        stays module-level jitted in ``kernels/expand/xla.py`` so its
        jit cache is shared across engine instances."""
        if d in self._expand_jits:
            return self._expand_jits[d]
        args = self.expand_kernel_args(d)
        spec = kernels.ExpandSpec(
            capacity=self.capacity, n_vars=self.n, n_atoms=self.m,
            n_others=len(args["other_ais"]),
            dtype=str(args["g_col"].dtype),
            x64=bool(jax.config.jax_enable_x64))
        fn, chosen = kernels.expand_fn(
            spec, mode=self.expand_kernel, impl=self.impl,
            sizes=self.sizes, **args)
        self.expand_paths[d] = chosen
        self._expand_jits[d] = fn
        return fn

    def expand_kernel_args(self, d: int) -> Dict:
        """The per-depth kernel-builder arguments derived from the
        columnar tries (the single source the registry, tests, and
        benchmarks build EXPAND(d) kernels from)."""
        parts = self.at_depth[d]
        gi = self.guard[d]
        g_ai, g_lvl = parts[gi]
        g = self.levels[g_ai][g_lvl]
        others = tuple((ai, lvl) for k, (ai, lvl) in enumerate(parts)
                       if k != gi)
        return dict(d=d, g_ai=g_ai,
                    other_ais=tuple(ai for ai, _ in others),
                    g_col=g.col, g_rs=g.runstarts,
                    other_cols=tuple(self.levels[ai][lvl].col
                                     for ai, lvl in others),
                    n_rows_g=self.sizes[g_ai])

    def expand_impl(self, d: int) -> str:
        """Which kernel path EXPAND(d) runs on ("pallas" | "xla")."""
        self._expand_fn(d)
        return self.expand_paths[d]

    def expand_call_counts(self) -> Dict[str, int]:
        """Per-path EXPAND chunk-launch counts of the last execution."""
        ex = getattr(self, "last_executor", None)
        if ex is None:
            return {}
        return dict(ex.expand_path_runs)

    # ------------------------------------------------------------------
    def _fold_fn(self, d0: int, d1: int, with_replay: bool,
                 with_splice: bool):
        """Return the registry-dispatched FOLD step for bracket [d0, d1]
        (fused Pallas or the XLA chain, per ``fold_kernel``).  The three
        arities — replay-only, splice-only, merged — are keyed by the
        static ``with_replay``/``with_splice`` flags; the chosen path is
        recorded in ``fold_paths[(d0, d1, with_replay, with_splice)]``."""
        key = (d0, d1, with_replay, with_splice)
        if key in self._fold_jits:
            return self._fold_jits[key]
        spec = kernels.FoldSpec(
            capacity=self.capacity, n_vars=self.n, n_atoms=self.m,
            width=d1 - d0 + 1, with_replay=with_replay,
            with_splice=with_splice, dtype="int32",
            x64=bool(jax.config.jax_enable_x64))
        fn, chosen = kernels.fold_fn(
            spec, mode=self.fold_kernel, d0=d0, d1=d1)
        self.fold_paths[key] = chosen
        self._fold_jits[key] = fn
        return fn

    def _emit_fn(self):
        """Return the registry-dispatched EMIT pack
        ``(assign, valid) -> (packed, k)`` per ``emit_kernel``; the
        chosen path is recorded in ``emit_path``."""
        if self._emit_jit is None:
            spec = kernels.EmitSpec(
                capacity=self.capacity, n_vars=self.n, dtype="int32",
                x64=bool(jax.config.jax_enable_x64))
            fn, chosen = kernels.emit_fn(spec, mode=self.emit_kernel)
            self.emit_path = chosen
            self._emit_jit = fn
        return self._emit_jit

    def fold_call_counts(self) -> Dict[str, int]:
        """Per-path FOLD chunk-launch counts of the last execution."""
        ex = getattr(self, "last_executor", None)
        if ex is None:
            return {}
        return dict(ex.fold_path_runs)

    # ------------------------------------------------------------------
    def expand_plan(self, d: int) -> Tuple[int, np.ndarray, int]:
        """Host-side planning arrays for depth d's guard: the executor
        fetches (lo, hi, valid) once per op and derives candidate counts
        for morsel admission/splitting from these."""
        parts = self.at_depth[d]
        g_ai, g_lvl = parts[self.guard[d]]
        return g_ai, self.levels[g_ai][g_lvl].runstarts_np, self.sizes[g_ai]

    def split_chunk_host(self, host: Dict[str, np.ndarray], d: int,
                         counts: np.ndarray) -> Iterator[Frontier]:
        """Split a chunk whose expansion would overflow capacity.

        ``host`` is the chunk already fetched to host (one batched sync by
        the executor).  A row whose candidates fit stays whole; an
        oversized row is split by guard *run ranges* of at most C runs, so
        each piece enumerates a disjoint slice of its candidate values.
        The resulting entries are greedily packed, in order, into pieces
        of at most C rows and at most C candidates, yielded one at a time
        (each piece reaches the device only when the caller takes it).
        Vectorized: a few array passes per chunk, no per-row Python work.
        """
        with TraceAnnotation("clftj.morsel_split"):
            C = self.capacity
            g_ai, rs, n_rows_g = self.expand_plan(d)
            idx = np.flatnonzero(host["valid"])
            lo_g = host["lo"][idx, g_ai].astype(np.int64)
            hi_g = host["hi"][idx, g_ai].astype(np.int64)
            r0 = np.searchsorted(rs, lo_g, side="left")
            r1 = np.searchsorted(rs, hi_g, side="left")
            big = counts[idx] > C
            # entries: one per whole row, ceil(runs / C) per oversized row
            n_seg = np.where(big, -(-(r1 - r0) // C), 1)
            row = np.repeat(idx, n_seg)
            seg = np.arange(row.size) - np.repeat(np.cumsum(n_seg) - n_seg,
                                                  n_seg)
            split = np.repeat(big, n_seg)
            a = np.repeat(r0, n_seg) + seg * C
            b = np.minimum(a + C, np.repeat(r1, n_seg))
            ext = np.append(rs, n_rows_g).astype(np.int64)
            e_lo = np.where(split, ext[np.minimum(a, len(rs))],
                            np.repeat(lo_g, n_seg))
            e_hi = np.where(split, ext[np.minimum(b, len(rs))],
                            np.repeat(hi_g, n_seg))
            cnt = np.where(split, b - a, np.repeat(r1 - r0, n_seg))
            cum = np.concatenate([[0], np.cumsum(cnt)])
        # greedy pack: a piece grows while its rows <= C and count <= C
        # (each piece's host work is one span, closed before its yield)
        p = 0
        while p < row.size:
            with TraceAnnotation("clftj.morsel_split"):
                q = int(np.searchsorted(cum, cum[p] + C, side="right")) - 1
                q = max(p + 1, min(q, p + C, row.size))
                fields = {k: v[row[p:q]] for k, v in host.items()}
                fields["lo"][:, g_ai] = e_lo[p:q]
                fields["hi"][:, g_ai] = e_hi[p:q]
                piece = self._pack_rows(fields, q - p)
            yield piece
            p = q

    def _pack_rows(self, fields: Dict[str, np.ndarray], n: int) -> Frontier:
        """A chunk holding ``n`` rows (``fields``' leading axis), padded
        with invalid zero rows to capacity."""
        C = self.capacity
        out = {}
        for k in Frontier._fields:
            v = fields[k]
            arr = np.zeros((C,) + v.shape[1:], dtype=v.dtype)
            arr[:n] = v
            out[k] = jnp.asarray(arr)
        out["valid"] = jnp.asarray(np.arange(C) < n)
        return Frontier(**out)

    # ------------------------------------------------------------------
    def count(self) -> int:
        with jax.enable_x64(True):
            ex = ScheduleExecutor(self, mode="count")
            self.last_executor = ex  # op_runs / sync diagnostics
            return ex.count()

    def evaluate(self) -> Iterator[np.ndarray]:
        """Yields (k, n) blocks of result assignments (order columns)."""
        with jax.enable_x64(True):
            ex = ScheduleExecutor(self, mode="evaluate")
            self.last_executor = ex
            yield from ex.evaluate()

    def evaluate_stream(self) -> Iterator[np.ndarray]:
        """Streaming evaluation: the same blocks as :meth:`evaluate`, in
        the same order, with each block's device→host copy issued
        asynchronously as the block is produced (bounded by
        ``emit_in_flight``; DESIGN.md §2.8)."""
        with jax.enable_x64(True):
            ex = ScheduleExecutor(self, mode="evaluate")
            self.last_executor = ex
            yield from ex.evaluate_stream()


def jax_lftj_count(q: CQ, order: Sequence[str], db: Database,
                   capacity: int = 1 << 17, impl: str = "bsearch",
                   expand_kernel: str = "auto",
                   fold_kernel: str = "auto",
                   emit_kernel: str = "auto") -> int:
    return JaxTrieJoin(q, order, db, capacity=capacity, impl=impl,
                       expand_kernel=expand_kernel, fold_kernel=fold_kernel,
                       emit_kernel=emit_kernel).count()


def jax_lftj_evaluate(q: CQ, order: Sequence[str], db: Database,
                      capacity: int = 1 << 17, impl: str = "bsearch",
                      expand_kernel: str = "auto",
                      fold_kernel: str = "auto",
                      emit_kernel: str = "auto") -> np.ndarray:
    eng = JaxTrieJoin(q, order, db, capacity=capacity, impl=impl,
                      expand_kernel=expand_kernel, fold_kernel=fold_kernel,
                      emit_kernel=emit_kernel)
    blocks = list(eng.evaluate())
    if not blocks:
        return np.zeros((0, len(eng.order)), np.int32)
    return np.concatenate(blocks, axis=0)
