"""Data plane: the share of EXPAND's (row, candidate) pairs that pass the
membership test in the other atoms and leave as valid rows, over the
window's requests: the program's ``expand_rows_out`` counter over its
``expand_candidates`` (%).  The rest is what ``verify`` discards."""


def read(run):
    pairs = sum(r.counters.get("expand_candidates", 0)
                for r in run.requests)
    out = sum(r.counters.get("expand_rows_out", 0) for r in run.requests)
    if pairs <= 0:
        return None
    return 100.0 * out / pairs
