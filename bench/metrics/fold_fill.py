"""Schedule interpreter: the share of FOLD's launched slots that carry a
row, over the window's requests: the program's ``fold_rows_replayed`` plus
``fold_rows_spliced`` counters over FOLD launches (``fold_calls_*``) times
the frontier capacity (%).  How full the morsel packing leaves each FOLD
launch."""


def read(run):
    rows = sum(r.counters.get("fold_rows_replayed", 0)
               + r.counters.get("fold_rows_spliced", 0)
               for r in run.requests)
    launches = sum(v for r in run.requests for k, v in r.counters.items()
                   if k.startswith("fold_calls_"))
    if rows <= 0 or launches <= 0:
        return None
    return 100.0 * rows / (launches * run.capacity)
