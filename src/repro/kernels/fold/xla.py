"""XLA op-chain FOLD: replay, splice, and merge as jitted jnp steps.

The three steps were born in ``core/schedule.py`` (which still re-exports
them under their old underscore names) and moved here so the registry can
dispatch FOLD between this chain and the fused Pallas kernel
(``fused.py``) exactly like EXPAND.

:func:`build` composes them into the registry's FOLD contract — one of
three arities, selected statically by ``with_replay``/``with_splice``:

  * replay-only:  ``fn(P, active, rep_of_row, E) -> (cont, stats)``
  * splice-only:  ``fn(P, hit, poff, plen, slab) -> (cont, stats)``
  * merged:       ``fn(P, active, rep_of_row, E, hit, poff, plen, slab)
                  -> (cont, stats)``

``stats`` is an int64 ``(3,)`` vector ``[needed, n_spl, n_valid]``: the
replay pair total, the splice pair total (each 0 when that path is absent
from the variant), and the combined valid-output total
``min(needed, C) + min(n_spl, C)`` — the three figures the static
executor overflow-checks against the chunk capacity.  Outputs are
valid-prefix compacted (replay rows first, then splice rows); rows past
the valid prefix are garbage — only their ``valid=False`` is contractual,
as in the EXPAND kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..expand.xla import compact, count_le

__all__ = ["replay_step", "splice_step", "merge_compact", "build"]


@functools.partial(jax.jit, static_argnames=("d0", "d1", "sorted_exits"))
def replay_step(P, active, rep_of_row, E, *, d0: int, d1: int,
                sorted_exits: bool = True):
    """Scatter one subtree exit chunk back through ``orig`` (evaluate mode).

    For every active parent row *i* (representative ``rep_of_row[i]``) and
    every valid exit row *e* with ``E.orig == rep_of_row[i]``, produce one
    output row: the parent's assignment with the subtree columns
    ``[d0, d1]`` replaced by the exit row's — the factorized intermediate
    of paper §3.4, re-expanded.  Caller guarantees the total pair count
    fits the chunk capacity (``active`` is a pre-packed morsel mask).
    ``sorted_exits`` promises ``E`` is valid-prefix compacted with
    nondecreasing ``orig`` (the executors' sorted-exits invariant), which
    makes the exits' order by representative the identity; otherwise they
    are sorted here.
    """
    C = P.assign.shape[0]
    # exits per representative, and exit rows ordered by representative id
    ecnt = jnp.zeros((C,), jnp.int32).at[
        jnp.clip(E.orig, 0, C - 1)].add(E.valid.astype(jnp.int32))
    eorder = _exit_order(E, sorted_exits)
    estart = jnp.cumsum(ecnt) - ecnt
    # enumerate (parent, exit) pairs exactly like _expand_step enumerates
    # (row, candidate) pairs: cumsum offsets, inverted by counting
    rep = jnp.clip(rep_of_row, 0, C - 1)
    pcnt = jnp.where(active, ecnt[rep], 0).astype(jnp.int32)
    offsets = jnp.cumsum(pcnt) - pcnt
    needed = offsets[-1] + pcnt[-1]
    slot = jnp.arange(C, dtype=jnp.int32)
    src = jnp.clip(count_le(offsets, C) - 1, 0, C - 1)
    delta = slot - offsets[src]
    ok = (slot < needed) & (delta < pcnt[src])
    eidx = eorder[jnp.clip(estart[rep[src]] + delta, 0, C - 1)]
    cols = jnp.arange(P.assign.shape[1], dtype=jnp.int32)
    insub = (cols >= d0) & (cols <= d1)
    assign = jnp.where(insub[None, :], E.assign[eidx], P.assign[src])
    out = P._replace(assign=assign,
                     factor=P.factor[src] * E.factor[eidx],
                     valid=ok,
                     orig=P.orig[src],
                     lo=P.lo[src], hi=P.hi[src])
    return compact(out), needed


def _exit_order(E, sorted_exits: bool):
    """Exit row indices ordered by representative id (``orig``), invalid
    rows last: the identity for sorted exits, a stable sort otherwise."""
    C = E.orig.shape[0]
    if sorted_exits:
        return jnp.arange(C, dtype=jnp.int32)
    ekey = jnp.where(E.valid, jnp.clip(E.orig, 0, C - 1), jnp.int32(C))
    return jnp.argsort(ekey, stable=True)


@functools.partial(jax.jit, static_argnames=("d0", "d1"))
def splice_step(P, mask, poff, plen, slab, *, d0: int, d1: int):
    """:func:`replay_step` specialized to slab-resident blocks (splice).

    For every masked parent row *i* with a tier-2 payload hit, emit
    ``plen[i]`` continuation rows: the parent's assignment with the
    subtree columns ``[d0, d1]`` gathered from its cached factorized
    block — the same (parent, exit)-pair enumeration as the replay step,
    with the exit chunk replaced by slab rows (blocks are stored
    contiguously, so no per-rep sort is needed).  Caller guarantees the
    masked total fits the chunk capacity (pre-packed morsel mask).
    """
    C = P.assign.shape[0]
    R = slab.shape[0] - 1
    pcnt = jnp.where(mask, plen, 0).astype(jnp.int32)
    offsets = jnp.cumsum(pcnt) - pcnt
    needed = offsets[-1] + pcnt[-1]
    slot = jnp.arange(C, dtype=jnp.int32)
    src = jnp.clip(count_le(offsets, C) - 1, 0, C - 1)
    delta = slot - offsets[src]
    ok = (slot < needed) & (delta < pcnt[src])
    sidx = jnp.where(ok, jnp.clip(poff[src] + delta, 0, R - 1), R)
    sub = slab[sidx]                                   # (C, d1-d0+1)
    assign = P.assign[src].at[:, d0:d1 + 1].set(sub)
    out = P._replace(assign=assign,
                     factor=P.factor[src],
                     valid=ok,
                     orig=P.orig[src],
                     lo=P.lo[src], hi=P.hi[src])
    return compact(out)


@jax.jit
def merge_compact(A, B):
    """Append chunk B's valid prefix after chunk A's (both valid-prefix
    compacted, as every replay/splice output is).  Returns the merged
    chunk plus the total valid count — the caller flags overflow when it
    exceeds capacity (static executor: no morsel splitting)."""
    C = A.valid.shape[0]
    n1 = jnp.sum(A.valid.astype(jnp.int32))
    n2 = jnp.sum(B.valid.astype(jnp.int32))
    slot = jnp.arange(C, dtype=jnp.int32)
    fromB = slot >= n1
    bidx = jnp.clip(slot - n1, 0, C - 1)

    def pick(a, b):
        m = fromB.reshape((C,) + (1,) * (a.ndim - 1))
        return jnp.where(m, b[bidx], a)

    out = type(A)(*(pick(a, b) for a, b in zip(A, B)))
    return out._replace(valid=slot < jnp.minimum(n1 + n2, C)), n1 + n2


def _stats(C: int, needed, n_spl) -> jnp.ndarray:
    needed = jnp.asarray(needed).astype(jnp.int64)
    n_spl = jnp.asarray(n_spl).astype(jnp.int64)
    n_valid = jnp.minimum(needed, C) + jnp.minimum(n_spl, C)
    return jnp.stack([needed, n_spl, n_valid])


def build(*, d0: int, d1: int, with_replay: bool, with_splice: bool,
          sorted_exits: bool = True):
    """FOLD step under the registry contract (module docstring): the
    always-available XLA op-chain composition.  ``sorted_exits=False``
    accepts exit chunks that break the sorted-exits invariant (the static
    executor's folds over merged continuations).  The step compiles to a
    module named ``jit_fold_step``, its parts under the named scopes
    ``replay``, ``splice`` and ``merge``."""
    if not (with_replay or with_splice):
        raise ValueError("FOLD build needs at least one of replay/splice")

    if with_replay and with_splice:
        @jax.jit
        def fold_step(P, active, rep_of_row, E, hit, poff, plen, slab):
            C = P.valid.shape[0]
            with jax.named_scope("replay"):
                cont, needed = replay_step(P, active, rep_of_row, E,
                                           d0=d0, d1=d1,
                                           sorted_exits=sorted_exits)
            # splice the payload hits, then append after the replay rows —
            # identical row order to the fused kernel's two-region layout
            with jax.named_scope("splice"):
                spl = splice_step(P, hit, poff, plen, slab, d0=d0, d1=d1)
                n_spl = jnp.sum(jnp.where(hit, plen, 0).astype(jnp.int64))
            with jax.named_scope("merge"):
                merged, _ = merge_compact(cont, spl)
            return merged, _stats(C, needed, n_spl)

        return fold_step

    if with_replay:
        @jax.jit
        def fold_step(P, active, rep_of_row, E):
            C = P.valid.shape[0]
            with jax.named_scope("replay"):
                cont, needed = replay_step(P, active, rep_of_row, E,
                                           d0=d0, d1=d1,
                                           sorted_exits=sorted_exits)
            return cont, _stats(C, needed, 0)

        return fold_step

    @jax.jit
    def fold_step(P, hit, poff, plen, slab):
        C = P.valid.shape[0]
        with jax.named_scope("splice"):
            spl = splice_step(P, hit, poff, plen, slab, d0=d0, d1=d1)
            n_spl = jnp.sum(jnp.where(hit, plen, 0).astype(jnp.int64))
        return spl, _stats(C, 0, n_spl)

    return fold_step
