"""Tier 2: probes answered by the cache over probes made, summed over the
window's requests (``tier2_hits / tier2_probes`` of ``Result.counters``)."""


def read(run):
    probes = sum(r.counters.get("tier2_probes", 0) for r in run.requests)
    if probes <= 0:
        return None
    hits = sum(r.counters.get("tier2_hits", 0) for r in run.requests)
    return 100.0 * hits / probes
