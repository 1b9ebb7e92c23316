"""Bytes that a frontier kernel must move, whatever implements it.

A frontier chunk holds ``capacity`` rows of: ``assign`` (one int32 per
query variable), ``factor`` (int64), ``valid`` (bool), ``orig`` (int32),
and ``lo``/``hi`` (one int32 per atom each).  A kernel's bytes are the
chunks it must read and write at its spec's capacity and widths; trie
searches and gathers, which an implementation may avoid, are not counted.
The kernels are int32 work with no floating-point operations, so bytes
bound them.
"""
from __future__ import annotations


def frontier_row_bytes(n_vars: int, n_atoms: int) -> int:
    return 4 * n_vars + 8 + 1 + 4 + 2 * 4 * n_atoms


def expand_bytes(capacity: int, n_vars: int, n_atoms: int) -> int:
    """EXPAND reads one chunk and writes the expanded one."""
    return 2 * capacity * frontier_row_bytes(n_vars, n_atoms)
