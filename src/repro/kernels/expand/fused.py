"""Fused-EXPAND Pallas kernel: one frontier expansion in a single launch.

The XLA chain (``xla.py``) materializes ~6 intermediate HBM arrays per
participating atom per ``EXPAND(d)`` — guard-run enumeration, two
``searchsorted`` results per atom, the mask, and the compaction permute
each round-trip through memory.  This kernel performs the whole step —

  1. **plan**: per-row guard run range (bounded binary search over the
     run-start array), candidate counts, exclusive-cumsum slot offsets,
     and the ``needed`` total;
  2. **expand**: per output slot, invert the offset map (upper-bound
     search), gather the candidate value and its run window, and verify
     membership in every other participating atom with two bounded
     binary searches, narrowing that atom's [lo, hi) trie window;
  3. **compact**: inclusive-scan the survivor mask and gather the j-th
     surviving row into output slot j (a stable partition computed as a
     dest-side lower-bound search — no sort primitive needed);

— in ONE ``pallas_call``, staging intermediates in VMEM scratch instead
of HBM.  The wrapper is ≤2 device ops per EXPAND: the launch plus the
``needed`` scalar extraction (`bench_expand_kernel` pins this).

**Grid/blocking.**  ``grid = (2, C // block_q)``: the slower axis is the
phase (expand, then compact — TPU grids iterate sequentially, so phase 1
sees phase 0's scratch), the faster axis tiles the chunk's output slots
so per-iteration vector work stays inside a VMEM-sized window.  Trie
columns and the parent chunk are resident across iterations (constant
index maps); the frontier ``capacity`` therefore bounds the working set,
exactly as it bounds device memory for the rest of the engine.  The plan
and scan sub-steps run once each (first iteration of their phase) into
scratch shared by the later tiles.

**Dispatch/testing story** (DESIGN.md §2.7): compiled on TPU/GPU,
interpret mode on CPU — where it is exercised by the conformance zoo
with ``expand_kernel="pallas"`` forced (bit-exact against the XLA chain
on the valid prefix; invalid tail rows are garbage in both paths, only
their ``valid=False`` is contractual).  Outputs match the XLA chain's
compaction exactly: same survivor order (both are stable), same
``needed``.  Where the backend's compiler refuses this kernel, the
registry's ``"auto"`` takes the XLA chain and a forced ``"pallas"``
raises.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import interpret_default

__all__ = ["FusedExpandConfig", "build"]

DEFAULT_BLOCK_Q = 1024


@dataclass(frozen=True)
class FusedExpandConfig:
    """Grid/block-size knobs for the fused kernel.

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
    (the in-kernel twin of ``registry._bsearch`` — same trip count, same
    insertion-point semantics, so results are bit-identical)."""
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


def _make_kernel(*, C: int, d: int, g_ai: int, other_ais: Tuple[int, ...],
                 nruns: int, n_rows_g: int, block_q: int):
    n_others = len(other_ais)
    i32 = jnp.int32

    def kernel(*refs):
        (assign_ref, factor_ref, valid_ref, orig_ref, lo_ref, hi_ref,
         gcol_ref, grs_ref) = refs[:8]
        other_refs = refs[8:8 + n_others]
        (o_assign, o_factor, o_valid, o_orig, o_lo, o_hi,
         o_needed) = refs[8 + n_others:15 + n_others]
        (s_r0, s_cnt, s_off, s_ok, s_csum, s_assign, s_factor, s_orig,
         s_lo, s_hi) = refs[15 + n_others:]

        phase = pl.program_id(0)
        j = pl.program_id(1)
        base = j * block_q
        zeros_c = jnp.zeros((C,), i32)

        @pl.when((phase == 0) & (j == 0))
        def _plan():
            grs = grs_ref[...]
            r0 = _search(grs, lo_ref[...][:, g_ai], zeros_c,
                         jnp.full((C,), nruns, i32), strict=True)
            r1 = _search(grs, hi_ref[...][:, g_ai], zeros_c,
                         jnp.full((C,), nruns, i32), strict=True)
            cnt = jnp.where(valid_ref[...], r1 - r0, 0).astype(i32)
            off = (jnp.cumsum(cnt) - cnt).astype(i32)
            s_r0[...] = r0.astype(i32)
            s_cnt[...] = cnt
            s_off[...] = off
            o_needed[0] = off[C - 1] + cnt[C - 1]

        @pl.when(phase == 0)
        def _expand():
            slots = base + jax.lax.iota(i32, block_q)
            off, cnt = s_off[...], s_cnt[...]
            needed = off[C - 1] + cnt[C - 1]
            src = _search(off, slots, jnp.zeros((block_q,), i32),
                          jnp.full((block_q,), C, i32), strict=False) - 1
            src = jnp.clip(src, 0, C - 1)
            delta = slots - off[src]
            ok = (slots < needed) & (delta < cnt[src])
            k = jnp.clip(s_r0[...][src] + delta, 0, nruns - 1)
            grs = grs_ref[...]
            pos = grs[k]
            value = gcol_ref[...][jnp.clip(pos, 0, max(n_rows_g - 1, 0))]
            run_end = jnp.where(k + 1 < nruns,
                                grs[jnp.clip(k + 1, 0, nruns - 1)],
                                n_rows_g).astype(i32)
            lo_full, hi_full = lo_ref[...], hi_ref[...]
            lo2 = lo_full[src].at[:, g_ai].set(pos)
            hi2 = hi_full[src].at[:, g_ai].set(run_end)
            for ai, col_ref in zip(other_ais, other_refs):
                col = col_ref[...]
                s = _search(col, value, lo_full[src, ai], hi_full[src, ai],
                            strict=True)
                e = _search(col, value, s, hi_full[src, ai], strict=False)
                ok = ok & (s < e)
                lo2 = lo2.at[:, ai].set(s.astype(i32))
                hi2 = hi2.at[:, ai].set(e.astype(i32))
            blk = pl.ds(base, block_q)
            s_assign[blk, :] = assign_ref[...][src].at[:, d].set(
                value.astype(i32))
            s_factor[blk] = factor_ref[...][src]
            s_orig[blk] = orig_ref[...][src]
            s_lo[blk, :] = lo2.astype(i32)
            s_hi[blk, :] = hi2.astype(i32)
            s_ok[blk] = ok.astype(i32)

        @pl.when((phase == 1) & (j == 0))
        def _scan():
            s_csum[...] = jnp.cumsum(s_ok[...]).astype(i32)

        @pl.when(phase == 1)
        def _compact():
            dest = base + jax.lax.iota(i32, block_q)
            csum = s_csum[...]
            # stable partition as a gather: output slot j takes the j-th
            # surviving staged row = first index with csum == j+1
            t = _search(csum, dest + 1, jnp.zeros((block_q,), i32),
                        jnp.full((block_q,), C, i32), strict=True)
            t = jnp.clip(t, 0, C - 1)
            o_assign[...] = s_assign[...][t]
            o_factor[...] = s_factor[...][t]
            o_valid[...] = dest < csum[C - 1]
            o_orig[...] = s_orig[...][t]
            o_lo[...] = s_lo[...][t]
            o_hi[...] = s_hi[...][t]

    return kernel


def build(*, d: int, g_ai: int, other_ais: Tuple[int, ...], n_rows_g: int,
          g_col, g_rs, other_cols, config: Optional[FusedExpandConfig] = None):
    """Close the per-depth arrays over the fused kernel → fn(F) ->
    (F', needed), jitted (the pallas_call is (re)constructed at trace
    time from the chunk's shapes/dtypes, so one built fn serves x64 on
    and off)."""
    config = config or FusedExpandConfig()
    nruns = int(g_rs.shape[0])
    assert nruns > 0 and n_rows_g > 0, \
        "degenerate guard tries take the XLA path (registry dispatch)"

    @jax.jit
    def fn(F):
        C, n_vars = F.assign.shape
        m = F.lo.shape[1]
        block_q = config.resolve_block_q(C)
        nb = C // block_q
        kernel = _make_kernel(C=C, d=d, g_ai=g_ai, other_ais=other_ais,
                              nruns=nruns, n_rows_g=n_rows_g,
                              block_q=block_q)
        full = lambda shape: pl.BlockSpec(shape, lambda p, j: (0,) * len(shape))
        tile1 = pl.BlockSpec((block_q,), lambda p, j: (j,))
        tile2 = lambda w: pl.BlockSpec((block_q, w), lambda p, j: (j, 0))
        outs = pl.pallas_call(
            kernel,
            grid=(2, nb),
            in_specs=[
                full((C, n_vars)), full((C,)), full((C,)), full((C,)),
                full((C, m)), full((C, m)),
                full((n_rows_g,)), full((nruns,)),
                *[full((int(c.shape[0]),)) for c in other_cols],
            ],
            out_specs=[
                tile2(n_vars), tile1, tile1, tile1, tile2(m), tile2(m),
                pl.BlockSpec((1,), lambda p, j: (0,)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((C, n_vars), F.assign.dtype),
                jax.ShapeDtypeStruct((C,), F.factor.dtype),
                jax.ShapeDtypeStruct((C,), jnp.bool_),
                jax.ShapeDtypeStruct((C,), F.orig.dtype),
                jax.ShapeDtypeStruct((C, m), F.lo.dtype),
                jax.ShapeDtypeStruct((C, m), F.hi.dtype),
                jax.ShapeDtypeStruct((1,), jnp.int32),
            ],
            scratch_shapes=[
                pltpu.VMEM((C,), jnp.int32),            # s_r0
                pltpu.VMEM((C,), jnp.int32),            # s_cnt
                pltpu.VMEM((C,), jnp.int32),            # s_off
                pltpu.VMEM((C,), jnp.int32),            # s_ok
                pltpu.VMEM((C,), jnp.int32),            # s_csum
                pltpu.VMEM((C, n_vars), F.assign.dtype),  # s_assign
                pltpu.VMEM((C,), F.factor.dtype),       # s_factor
                pltpu.VMEM((C,), F.orig.dtype),         # s_orig
                pltpu.VMEM((C, m), F.lo.dtype),         # s_lo
                pltpu.VMEM((C, m), F.hi.dtype),         # s_hi
            ],
            interpret=config.resolve_interpret(),
        )(F.assign, F.factor, F.valid, F.orig, F.lo, F.hi,
          g_col, g_rs, *other_cols)
        o_assign, o_factor, o_valid, o_orig, o_lo, o_hi, o_needed = outs
        return F._replace(assign=o_assign, factor=o_factor, valid=o_valid,
                          orig=o_orig, lo=o_lo, hi=o_hi), o_needed[0]

    return fn
