"""Data plane: (row, candidate) pairs that EXPAND lays out per valid row
entering it, over the window's requests: the program's
``expand_candidates`` counter over its ``expand_rows_in`` (pairs/row)."""


def read(run):
    rows = sum(r.counters.get("expand_rows_in", 0) for r in run.requests)
    pairs = sum(r.counters.get("expand_candidates", 0)
                for r in run.requests)
    if rows <= 0 or pairs <= 0:
        return None
    return pairs / rows
