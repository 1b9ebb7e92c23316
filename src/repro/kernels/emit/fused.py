"""Fused-EMIT Pallas kernel: stable valid-row packing in a single launch.

The XLA chain (``xla.py``) pays a full-chunk stable sort plus a gather —
two HBM round-trips — per emitted block.  This kernel computes the
inclusive survivor scan once into VMEM scratch (first grid iteration)
and then, per output tile, gathers the j-th surviving row with a
dest-side lower-bound search (the same sort-free stable-partition trick
as the EXPAND kernel's compact phase) — ONE ``pallas_call``, ≤2 device
ops per EMIT with the ``k`` scalar extraction (``bench_fold_kernel``
pins this).  Contract identical to ``xla.build``: ``fn(assign, valid)
-> (packed, k)``; rows past ``k`` are garbage.
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

from ..fold.fused import _search

__all__ = ["FusedEmitConfig", "build"]

DEFAULT_BLOCK_Q = 1024


@dataclass(frozen=True)
class FusedEmitConfig:
    """Grid/block-size knobs (same semantics as the EXPAND/FOLD configs)."""

    block_q: int = DEFAULT_BLOCK_Q
    interpret: Optional[bool] = None

    def resolve_block_q(self, capacity: int) -> int:
        return math.gcd(capacity, min(self.block_q, capacity))

    def resolve_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return interpret_default()


def _make_kernel(*, C: int, block_q: int):
    i32 = jnp.int32

    def kernel(assign_ref, valid_ref, o_packed, o_k, s_csum):
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _scan():
            csum = jnp.cumsum(valid_ref[...].astype(i32)).astype(i32)
            s_csum[...] = csum
            o_k[0] = csum[C - 1]

        dest = j * block_q + jax.lax.iota(i32, block_q)
        csum = s_csum[...]
        # stable pack as a gather: output slot j takes the j-th valid
        # row = first index with csum == j+1
        t = _search(csum, dest + 1, jnp.zeros((block_q,), i32),
                    jnp.full((block_q,), C, i32), strict=True)
        o_packed[...] = assign_ref[...][jnp.clip(t, 0, C - 1)]

    return kernel


def build(*, config: Optional[FusedEmitConfig] = None):
    """EMIT pack under the registry contract, as one fused launch."""
    config = config or FusedEmitConfig()

    @jax.jit
    def fn(assign, valid):
        C, n_vars = assign.shape
        block_q = config.resolve_block_q(C)
        nb = C // block_q
        kernel = _make_kernel(C=C, block_q=block_q)
        outs = pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[pl.BlockSpec((C, n_vars), lambda j: (0, 0)),
                      pl.BlockSpec((C,), lambda j: (0,))],
            out_specs=[pl.BlockSpec((block_q, n_vars), lambda j: (j, 0)),
                       pl.BlockSpec((1,), lambda j: (0,))],
            out_shape=[jax.ShapeDtypeStruct((C, n_vars), assign.dtype),
                       jax.ShapeDtypeStruct((1,), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((C,), jnp.int32)],
            interpret=config.resolve_interpret(),
        )(assign, valid)
        packed, k = outs
        return packed, k[0]

    return fn
