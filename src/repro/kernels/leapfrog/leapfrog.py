"""Pallas TPU kernel: batched bounded lower/upper bound ("leapfrog seek").

TPU adaptation (see DESIGN.md §2): the scalar galloping search of LFTJ maps
poorly onto the VPU — per-lane dynamic gathers from a large HBM-resident
array are the exact anti-pattern.  Instead each (query-block × column-block)
grid cell does a *dense masked comparison count*: for query q with window
[lo_q, hi_q), the bounded insertion index is

    lower_bound(q) = lo_q + |{ p : lo_q <= p < hi_q  and  col[p] < v_q }|

which is an (BQ × BC) broadcast compare + row reduction — pure VPU work on
VMEM tiles, accumulated across column blocks by the sequential TPU grid.
Block sizes keep the working set (BQ·BC comparisons) inside VMEM and the
lanes (last dim = BC) a multiple of 128.

For fixed relation size N this is O(N) per query versus O(log N) for the
scalar search; the crossover in the engine's regime (many thousand queries
per expansion against relation columns) favours the dense form on TPU, and
the column blocks stream at HBM bandwidth.  The host/CPU path of the engine
uses the branchless binary search in ``ops.py`` instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import interpret_default

DEFAULT_BQ = 512     # queries per block
DEFAULT_BC = 1024    # column elements per block (multiple of 128)


def _bound_kernel(v_ref, lo_ref, hi_ref, col_ref, out_ref, *,
                  n_valid: int, block_c: int, strict: bool):
    j = pl.program_id(1)
    base = j * block_c
    v = v_ref[...]          # (BQ,)
    lo = lo_ref[...]
    hi = hi_ref[...]
    col = col_ref[...]      # (BC,)
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], block_c), 1)
    cmp = (col[None, :] < v[:, None]) if strict else (col[None, :] <= v[:, None])
    mask = cmp & (pos >= lo[:, None]) & (pos < hi[:, None]) & (pos < n_valid)
    # pin the accumulator dtype: under enable_x64 jnp.sum would promote
    # int32 to int64 and the store into the int32 out_ref would fail
    partial = jnp.sum(mask.astype(jnp.int32), axis=1, dtype=jnp.int32)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = lo

    out_ref[...] += partial


def _bound_pallas(col: jnp.ndarray, values: jnp.ndarray,
                  lo: jnp.ndarray, hi: jnp.ndarray, *, strict: bool,
                  block_q: int = DEFAULT_BQ, block_c: int = DEFAULT_BC,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    m = values.shape[0]
    n = col.shape[0]
    if n == 0:
        return lo
    grid = (pl.cdiv(m, block_q), pl.cdiv(n, block_c))
    kernel = functools.partial(_bound_kernel, n_valid=n, block_c=block_c,
                               strict=strict)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q,), lambda i, j: (i,)),   # values
            pl.BlockSpec((block_q,), lambda i, j: (i,)),   # lo
            pl.BlockSpec((block_q,), lambda i, j: (i,)),   # hi
            pl.BlockSpec((block_c,), lambda i, j: (j,)),   # column block
        ],
        out_specs=pl.BlockSpec((block_q,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((m,), lo.dtype),
        interpret=interpret_default() if interpret is None else interpret,
    )(values.astype(col.dtype), lo.astype(jnp.int32), hi.astype(jnp.int32),
      col)
    return out


def lower_bound_pallas(col, values, lo, hi, **kw):
    return _bound_pallas(col, values, lo, hi, strict=True, **kw)


def upper_bound_pallas(col, values, lo, hi, **kw):
    return _bound_pallas(col, values, lo, hi, strict=False, **kw)
