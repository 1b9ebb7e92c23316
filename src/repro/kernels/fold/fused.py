"""Fused-FOLD Pallas kernel: replay + splice + merge in a single launch.

The XLA chain (``xla.py``) materializes ~10 intermediate HBM arrays per
FOLD — exit histogram, stable sort, two cumsum offset maps, the
searchsorted inversion, the gathers, and a full-chunk compaction permute
for *each* of the replay and splice paths, plus the merge.  This kernel
performs the whole step —

  1. **plan** (first grid iteration): the per-representative exit ranges
     (two bounded binary searches over the exit ``orig`` keys), the
     per-parent pair counts and exclusive-cumsum slot offsets for the
     replay region, and the hit-length counts/offsets for the splice
     region, into VMEM scratch; the ``stats`` triple falls out of the
     offset tails;
  2. **emit** (every tile): per output slot, invert the offset map
     (upper-bound search) to find the source parent row, gather the
     exit row (replay region) or the slab block row (splice region),
     and substitute the subtree columns ``[d0, d1]``;

— in ONE ``pallas_call``.  The wrapper is ≤2 device ops per FOLD: the
launch plus the int64 ``stats`` cast (``bench_fold_kernel`` pins this).

**No compaction phase.**  The replay offsets partition ``[0, needed)``,
so output slot ``s < needed`` always lands inside its parent's count —
the survivor set is a *prefix* by construction and the XLA chain's
stable-sort compaction is the identity on it.  The merged layout is
``[replay rows | splice rows]``: bit-identical to
``merge_compact(replay, splice)`` on the valid prefix.

**Sorted-exits precondition.**  The per-representative exit ranges are
recovered with binary searches instead of the XLA chain's
histogram+sort, which requires the exit chunk's ``orig`` to be
nondecreasing over its valid prefix (and ``valid`` to be a prefix).
Every chunk the executors feed a FOLD satisfies this — EXPAND outputs,
replay/splice continuations, and rep frontiers are all built by
monotone gathers over already-sorted parents — EXCEPT the merged
continuation of a *nested* payload fold in the static executor, which
tracks a sortedness flag and routes those folds to the XLA chain.

**Dispatch/testing story** (DESIGN.md §2.7/§2.10): compiled on TPU/GPU,
interpret mode on CPU, where the conformance zoo forces
``fold_kernel="pallas"`` — bit-exact against the XLA chain on the valid
prefix (invalid tail rows are garbage in both paths, only their
``valid=False`` is contractual).  The registry falls back to the XLA
chain if this kernel fails to build on a backend.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import interpret_default

__all__ = ["FusedFoldConfig", "build"]

DEFAULT_BLOCK_Q = 1024


@dataclass(frozen=True)
class FusedFoldConfig:
    """Grid/block-size knobs for the fused FOLD kernel.

    ``block_q`` — output slots per grid iteration (snapped to a divisor
    of the chunk capacity); ``interpret`` — force the Pallas interpreter
    (None = auto: interpret everywhere except TPU/GPU)."""

    block_q: int = DEFAULT_BLOCK_Q
    interpret: Optional[bool] = None

    def resolve_block_q(self, capacity: int) -> int:
        return math.gcd(capacity, min(self.block_q, capacity))

    def resolve_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return interpret_default()


def _search(col, values, lo, hi, *, strict: bool):
    """Branchless fixed-trip bounded binary search on in-register values
    (same trip count and insertion-point semantics as ``jnp.searchsorted``
    / ``registry._bsearch``, so results are bit-identical)."""
    n = col.shape[0]
    if n == 0:
        return lo
    trips = max(1, int(math.ceil(math.log2(n + 1))) + 1)

    def body(_, lh):
        lo_, hi_ = lh
        go = lo_ < hi_
        mid = (lo_ + hi_) >> 1
        x = col[jnp.clip(mid, 0, n - 1)]
        pred = (x < values) if strict else (x <= values)
        return (jnp.where(go & pred, mid + 1, lo_),
                jnp.where(go & ~pred, mid, hi_))

    lo_, _ = jax.lax.fori_loop(0, trips, body, (lo, hi))
    return lo_


def _make_kernel(*, C: int, n_vars: int, d0: int, d1: int, nslab: int,
                 block_q: int, with_replay: bool, with_splice: bool):
    i32 = jnp.int32
    w = d1 - d0 + 1

    def kernel(*refs):
        it = iter(refs)
        p_assign, p_factor, p_orig, p_lo, p_hi = (next(it) for _ in range(5))
        if with_replay:
            active_ref, ror_ref, e_assign, e_factor, e_valid, e_orig = (
                next(it) for _ in range(6))
        if with_splice:
            hit_ref, poff_ref, plen_ref, slab_ref = (
                next(it) for _ in range(4))
        (o_assign, o_factor, o_valid, o_orig, o_lo, o_hi,
         o_stats) = (next(it) for _ in range(7))
        if with_replay:
            s_estart, s_pcnt, s_roff = (next(it) for _ in range(3))
        if with_splice:
            s_scnt, s_soff = (next(it) for _ in range(2))

        j = pl.program_id(0)
        base = j * block_q
        zeros_c = jnp.zeros((C,), i32)
        full_c = jnp.full((C,), C, i32)

        @pl.when(j == 0)
        def _plan():
            needed = jnp.zeros((), i32)
            n_spl = jnp.zeros((), i32)
            if with_replay:
                # sorted-exits precondition: ekey is nondecreasing, so the
                # per-rep exit range is two binary searches — no histogram,
                # no sort (see module docstring)
                reps = jax.lax.iota(i32, C)
                ekey = jnp.where(e_valid[...],
                                 jnp.clip(e_orig[...], 0, C - 1),
                                 C).astype(i32)
                lb = _search(ekey, reps, zeros_c, full_c, strict=True)
                ub = _search(ekey, reps, zeros_c, full_c, strict=False)
                ecnt = (ub - lb).astype(i32)
                rep = jnp.clip(ror_ref[...], 0, C - 1)
                pcnt = jnp.where(active_ref[...], ecnt[rep], 0).astype(i32)
                roff = (jnp.cumsum(pcnt) - pcnt).astype(i32)
                s_estart[...] = lb.astype(i32)
                s_pcnt[...] = pcnt
                s_roff[...] = roff
                needed = roff[C - 1] + pcnt[C - 1]
            if with_splice:
                scnt = jnp.where(hit_ref[...], plen_ref[...], 0).astype(i32)
                soff = (jnp.cumsum(scnt) - scnt).astype(i32)
                s_scnt[...] = scnt
                s_soff[...] = soff
                n_spl = soff[C - 1] + scnt[C - 1]
            o_stats[0] = needed
            o_stats[1] = n_spl
            o_stats[2] = jnp.minimum(needed, C) + jnp.minimum(n_spl, C)

        slots = base + jax.lax.iota(i32, block_q)
        zeros_b = jnp.zeros((block_q,), i32)
        full_b = jnp.full((block_q,), C, i32)
        cols = jax.lax.iota(i32, n_vars)
        insub = (cols >= d0) & (cols <= d1)

        n1 = jnp.zeros((), i32)
        if with_replay:
            pcnt, roff = s_pcnt[...], s_roff[...]
            needed = roff[C - 1] + pcnt[C - 1]
            n1 = jnp.minimum(needed, C)
            rsrc = jnp.clip(_search(roff, slots, zeros_b, full_b,
                                    strict=False) - 1, 0, C - 1)
            rdelta = slots - roff[rsrc]
            rep = jnp.clip(ror_ref[...][rsrc], 0, C - 1)
            eidx = jnp.clip(s_estart[...][rep] + rdelta, 0, C - 1)
            r_assign = jnp.where(insub[None, :], e_assign[...][eidx],
                                 p_assign[...][rsrc])
            r_factor = p_factor[...][rsrc] * e_factor[...][eidx]
            r_orig = p_orig[...][rsrc]
            r_lo = p_lo[...][rsrc]
            r_hi = p_hi[...][rsrc]
        n2 = jnp.zeros((), i32)
        if with_splice:
            scnt, soff = s_scnt[...], s_soff[...]
            n_spl = soff[C - 1] + scnt[C - 1]
            n2 = jnp.minimum(n_spl, C)
            u = slots - n1  # splice slots follow the replay region
            ssrc = jnp.clip(_search(soff, u, zeros_b, full_b,
                                    strict=False) - 1, 0, C - 1)
            sdelta = u - soff[ssrc]
            sidx = jnp.clip(poff_ref[...][ssrc] + sdelta, 0,
                            max(nslab - 2, 0))
            sub = slab_ref[...][sidx]               # (block_q, w)
            widx = jnp.clip(cols - d0, 0, w - 1)
            s_assign = jnp.where(insub[None, :], sub[:, widx],
                                 p_assign[...][ssrc])
            s_factor = p_factor[...][ssrc]
            s_orig = p_orig[...][ssrc]
            s_lo = p_lo[...][ssrc]
            s_hi = p_hi[...][ssrc]
        if with_replay and with_splice:
            use_spl = slots >= n1
            o_assign[...] = jnp.where(use_spl[:, None], s_assign, r_assign)
            o_factor[...] = jnp.where(use_spl, s_factor, r_factor)
            o_orig[...] = jnp.where(use_spl, s_orig, r_orig)
            o_lo[...] = jnp.where(use_spl[:, None], s_lo, r_lo)
            o_hi[...] = jnp.where(use_spl[:, None], s_hi, r_hi)
        elif with_replay:
            o_assign[...] = r_assign
            o_factor[...] = r_factor
            o_orig[...] = r_orig
            o_lo[...] = r_lo
            o_hi[...] = r_hi
        else:
            o_assign[...] = s_assign
            o_factor[...] = s_factor
            o_orig[...] = s_orig
            o_lo[...] = s_lo
            o_hi[...] = s_hi
        o_valid[...] = slots < jnp.minimum(n1 + n2, C)

    return kernel


def build(*, d0: int, d1: int, with_replay: bool, with_splice: bool,
          config: Optional[FusedFoldConfig] = None):
    """Close the fold shape over the fused kernel → the registry's FOLD
    contract (see ``xla.build`` for the three arities and the ``stats``
    semantics), jitted — the pallas_call is (re)constructed at trace time
    from the chunk's shapes/dtypes, so one built fn serves x64 on/off."""
    if not (with_replay or with_splice):
        raise ValueError("FOLD build needs at least one of replay/splice")
    config = config or FusedFoldConfig()

    def _call(P, rep, spl):
        C, n_vars = P.assign.shape
        m = P.lo.shape[1]
        block_q = config.resolve_block_q(C)
        nb = C // block_q
        nslab = int(spl[3].shape[0]) if spl is not None else 0
        kernel = _make_kernel(C=C, n_vars=n_vars, d0=d0, d1=d1,
                              nslab=nslab, block_q=block_q,
                              with_replay=rep is not None,
                              with_splice=spl is not None)
        full = lambda shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape))
        tile1 = pl.BlockSpec((block_q,), lambda j: (j,))
        tile2 = lambda wd: pl.BlockSpec((block_q, wd), lambda j: (j, 0))
        inputs = [P.assign, P.factor, P.orig, P.lo, P.hi]
        in_specs = [full((C, n_vars)), full((C,)), full((C,)),
                    full((C, m)), full((C, m))]
        scratch = []
        if rep is not None:
            active, ror, E = rep
            inputs += [active, ror, E.assign, E.factor, E.valid, E.orig]
            in_specs += [full((C,)), full((C,)), full((C, n_vars)),
                         full((C,)), full((C,)), full((C,))]
            scratch += [pltpu.VMEM((C,), jnp.int32)] * 3  # estart/pcnt/roff
        if spl is not None:
            hit, poff, plen, slab = spl
            in_specs += [full((C,)), full((C,)), full((C,)),
                         full((int(slab.shape[0]), int(slab.shape[1])))]
            inputs += [hit, poff, plen, slab]
            scratch += [pltpu.VMEM((C,), jnp.int32)] * 2  # scnt/soff
        outs = pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=in_specs,
            out_specs=[tile2(n_vars), tile1, tile1, tile1, tile2(m),
                       tile2(m), pl.BlockSpec((3,), lambda j: (0,))],
            out_shape=[
                jax.ShapeDtypeStruct((C, n_vars), P.assign.dtype),
                jax.ShapeDtypeStruct((C,), P.factor.dtype),
                jax.ShapeDtypeStruct((C,), jnp.bool_),
                jax.ShapeDtypeStruct((C,), P.orig.dtype),
                jax.ShapeDtypeStruct((C, m), P.lo.dtype),
                jax.ShapeDtypeStruct((C, m), P.hi.dtype),
                jax.ShapeDtypeStruct((3,), jnp.int32),
            ],
            scratch_shapes=scratch,
            interpret=config.resolve_interpret(),
        )(*inputs)
        o_assign, o_factor, o_valid, o_orig, o_lo, o_hi, o_stats = outs
        out = P._replace(assign=o_assign, factor=o_factor, valid=o_valid,
                         orig=o_orig, lo=o_lo, hi=o_hi)
        return out, o_stats.astype(jnp.int64)

    if with_replay and with_splice:
        @jax.jit
        def fn(P, active, rep_of_row, E, hit, poff, plen, slab):
            return _call(P, (active, rep_of_row, E), (hit, poff, plen, slab))

        return fn

    if with_replay:
        @jax.jit
        def fn(P, active, rep_of_row, E):
            return _call(P, (active, rep_of_row, E), None)

        return fn

    @jax.jit
    def fn(P, hit, poff, plen, slab):
        return _call(P, None, (hit, poff, plen, slab))

    return fn
