"""Data plane: the share of EXPAND's launched slots that carry a
(row, candidate) pair, over the window's requests: the program's
``expand_candidates`` counter over EXPAND launches (``expand_calls_*``)
times the frontier capacity (%)."""


def read(run):
    pairs = sum(r.counters.get("expand_candidates", 0)
                for r in run.requests)
    launches = sum(v for r in run.requests for k, v in r.counters.items()
                   if k.startswith("expand_calls_"))
    if pairs <= 0 or launches <= 0:
        return None
    return 100.0 * pairs / (launches * run.capacity)
