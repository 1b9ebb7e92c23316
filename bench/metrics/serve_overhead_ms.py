"""Serving layer: client-side latency minus the program's own
``Result.wall_s``, averaged over the window's requests (ms/query)."""


def read(run):
    walls = [(r.latency_s, r.wall_s) for r in run.requests
             if r.wall_s is not None]
    if not walls:
        return None
    return 1e3 * sum(lat - w for lat, w in walls) / len(walls)
