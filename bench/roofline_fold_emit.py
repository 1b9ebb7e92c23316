"""Bytes that the FOLD and EMIT kernels must move, whatever implements them.

The convention of ``bench/roofline.py``: the kernels are int32 work with no
floating-point operations, so bytes bound them; a kernel's bytes are the
chunks it must read and write at its spec's capacity and widths
(``roofline.frontier_row_bytes`` a frontier row), not the searches and
gathers an implementation may add.  ``capacity`` is C, the rows of a chunk.

FOLD, one launch: it re-expands parent rows with their subtree's rows.

- reads the parent chunk: ``C * frontier_row_bytes(n_vars, n_atoms)``;
- reads one source of at most C rows, an exit chunk (replay) or tier-2 slab
  rows (splice), counted at its narrowest: the subtree's ``sub_vars``
  int32 columns, ``C * 4 * sub_vars`` (an exit chunk holds more);
- writes one chunk: ``C * frontier_row_bytes(n_vars, n_atoms)``.

EMIT, one launch: it packs a chunk's valid rows to the front.

- reads ``assign`` and ``valid``: ``C * (4 * n_vars + 1)``;
- writes the packed block and its int32 count ``k``: ``C * 4 * n_vars + 4``.
"""
from __future__ import annotations

from bench.roofline import frontier_row_bytes


def fold_bytes(capacity: int, n_vars: int, n_atoms: int,
               sub_vars: int) -> int:
    """FOLD reads the parent chunk and ``sub_vars`` columns of one source,
    and writes one chunk."""
    return capacity * (2 * frontier_row_bytes(n_vars, n_atoms)
                       + 4 * sub_vars)


def emit_bytes(capacity: int, n_vars: int) -> int:
    """EMIT reads ``assign`` and ``valid``, and writes the packed block and
    ``k``."""
    return capacity * (4 * n_vars + 1) + capacity * 4 * n_vars + 4
