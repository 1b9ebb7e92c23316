"""XLA op-chain EMIT: pack the valid result rows into a dense prefix.

The registry's EMIT contract: ``fn(assign, valid) -> (packed, k)`` where
``packed`` keeps the chunk shape ``(C, n)`` with the valid rows moved to
the front in stable (row) order and ``k`` is the valid count — the
device-side twin of the host's ``assign[valid]`` masked gather, so the
executor can slice ``packed[:k]`` after one D2H copy instead of shipping
the whole chunk and masking on the host.  Rows past ``k`` are garbage
(only ``k`` is contractual), as in the other kernels.
"""
from __future__ import annotations

import jax

from ..expand.xla import valid_first

__all__ = ["build"]


def build():
    """EMIT pack under the registry contract (module docstring): the
    always-available XLA composition (valid-first order + gather),
    compiled to a module named ``jit_emit_step`` with its work under the
    named scope ``pack``."""

    @jax.jit
    def emit_step(assign, valid):
        with jax.named_scope("pack"):
            perm, k = valid_first(valid)
            return assign[perm], k

    return emit_step
