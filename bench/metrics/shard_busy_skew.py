"""Mesh: the busiest device's busy time over the mean of the cell's
devices, from the trace."""


def read(run):
    t = run.trace
    if t is None or len(t.devices) < 2:
        return None
    busy = [d.busy_s for d in t.devices]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return max(busy) / mean
