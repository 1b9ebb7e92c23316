"""The XLA-op EXPAND path: one frontier expansion as a jnp op chain.

This is the expansion step that used to live in ``core/frontier.py``
(DESIGN.md §2.1), relocated behind the kernel registry so every EXPAND
implementation shares one entry-point convention.  Semantics are the
contract the fused Pallas kernel (``fused.py``) is held to, and both are
validated against the plain-numpy oracle in ``ref.py``:

* enumerate each valid row's guard candidate runs (one gather from the
  guard level's run-rank table at each end of the row's window: no
  search), lay the (row, candidate) pairs out over output slots (cumsum
  of the counts, inverted by counting: :func:`count_le`);
* verify each candidate's membership in every other participating atom
  with bounded binary search (two per atom), narrowing that atom's
  [lo, hi) trie window;
* compact surviving rows to the front of the chunk (stable partition,
  the survivor scan inverted the same way).

XLA materializes ~6 intermediate arrays per participating atom here — the
memory-traffic motivation for the fused kernel.  The functions are generic
over any Frontier-shaped NamedTuple (assign/factor/valid/orig/lo/hi).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..registry import lower_bound, upper_bound

__all__ = ["build", "expand_step", "compact", "count_le", "valid_first"]


def count_le(offsets, out: int):
    """``#{i : offsets[i] <= j}`` for ``j`` in ``0..out-1``, for any
    non-negative, non-decreasing int32 ``offsets``: the inverse of a
    prefix sum, i.e. ``searchsorted(offsets, j, side="right")``.

    One scatter-add of ones at ``min(offsets, out)`` (sorted, because
    ``offsets`` is monotone) and one scan.  A binary search would be a
    loop of ``log2(n)`` full-width gathers on a TPU."""
    hist = jnp.zeros(out + 1, jnp.int32).at[jnp.minimum(offsets, out)].add(
        1, indices_are_sorted=True)
    return jnp.cumsum(hist)[:out]


def valid_first(valid, out: int | None = None):
    """``(perm, k)``: the stable valid-first order of a chunk without a
    sort or a search.  Slot j takes the (j+1)-th valid row, the first
    index whose inclusive valid count reaches j+1, i.e. the number of rows
    whose count is at most j; ``k`` is the valid count and slots from
    ``k`` on point at arbitrary rows (clipped into the chunk).  ``out``
    (default: every row) is how many slots to build.  (A sort of 2^16
    keys takes tens of seconds to compile for TPU; this takes about one.)"""
    n = valid.shape[0]
    csum = jnp.cumsum(valid.astype(jnp.int32))
    perm = count_le(csum, n if out is None else out)
    return jnp.clip(perm, 0, n - 1), csum[-1]


@jax.jit
def compact(F):
    """Stable-partition valid rows to the front of the chunk (rows past
    the valid prefix are garbage with ``valid=False``)."""
    perm, k = valid_first(F.valid)
    out = type(F)(*(x[perm] for x in F))
    return out._replace(valid=jnp.arange(F.valid.shape[0]) < k)


@functools.partial(
    jax.jit,
    static_argnames=("d", "g_ai", "other_ais", "n_rows_g", "impl"))
def expand_step(F, g_col, g_rs, g_rank, other_cols, *, d: int, g_ai: int,
                other_ais: Tuple[int, ...], n_rows_g: int, impl: str):
    """One frontier expansion (module-level so the jit cache is shared by
    every engine instance with the same query structure / array shapes).

    ``g_rank`` is the guard level's run-rank table (``AtomLevel.rank``,
    ``n_rows_g + 1`` entries): a window ``[lo, hi)`` holds the guard runs
    ``g_rank[lo] .. g_rank[hi] - 1``.  A valid row's window lies in
    ``[0, n_rows_g]``; the clip only keeps invalid rows' gathers in
    bounds.

    The three phases carry named scopes, ``layout`` (guard runs, slot
    offsets, slot-to-row map, row gathers), ``verify`` (the other
    atoms' bounded searches) and ``compact``, so a profiler trace splits
    the module's device time by phase; scopes change HLO metadata only."""
    C = F.assign.shape[0]
    nruns = g_rs.shape[0]
    with jax.named_scope("layout"):
        r0 = g_rank[jnp.clip(F.lo[:, g_ai], 0, n_rows_g)]
        r1 = g_rank[jnp.clip(F.hi[:, g_ai], 0, n_rows_g)]
        counts = jnp.where(F.valid, r1 - r0, 0).astype(jnp.int32)
        offsets = jnp.cumsum(counts) - counts               # exclusive
        needed = offsets[-1] + counts[-1]
        slot = jnp.arange(C, dtype=jnp.int32)
        src = jnp.clip(count_le(offsets, C) - 1, 0, C - 1)
        delta = slot - offsets[src]
        ok = (slot < needed) & (delta < counts[src])
        if nruns:
            k = jnp.clip(r0[src] + delta, 0, nruns - 1)
            pos = g_rs[k]
            value = g_col[jnp.clip(pos, 0, max(n_rows_g - 1, 0))]
            run_end = jnp.where(k + 1 < nruns,
                                g_rs[jnp.clip(k + 1, 0, nruns - 1)],
                                n_rows_g).astype(jnp.int32)
        else:
            k = jnp.zeros_like(slot)
            pos = jnp.zeros_like(slot)
            value = jnp.zeros_like(slot)
            run_end = jnp.zeros_like(slot)
            ok = ok & False
        lo2 = F.lo[src].at[:, g_ai].set(pos)
        hi2 = F.hi[src].at[:, g_ai].set(run_end)
        assign2 = F.assign[src].at[:, d].set(value.astype(jnp.int32))
        factor2, orig2 = F.factor[src], F.orig[src]
    with jax.named_scope("verify"):
        for ai, col in zip(other_ais, other_cols):
            s = lower_bound(col, value, F.lo[src, ai], F.hi[src, ai],
                            impl=impl)
            e = upper_bound(col, value, s, F.hi[src, ai], impl=impl)
            ok = ok & (s < e)
            lo2 = lo2.at[:, ai].set(s.astype(jnp.int32))
            hi2 = hi2.at[:, ai].set(e.astype(jnp.int32))
    out = F._replace(assign=assign2, factor=factor2, valid=ok,
                     orig=orig2, lo=lo2.astype(jnp.int32),
                     hi=hi2.astype(jnp.int32))
    with jax.named_scope("compact"):
        return compact(out), needed


def build(*, d: int, g_ai: int, other_ais: Tuple[int, ...], n_rows_g: int,
          impl: str, g_col, g_rs, g_rank, other_cols):
    """Close the per-depth arrays over :func:`expand_step` → fn(F)."""

    def fn(F):
        return expand_step(F, g_col, g_rs, g_rank, other_cols, d=d,
                           g_ai=g_ai, other_ais=other_ais, n_rows_g=n_rows_g,
                           impl=impl)

    return fn
