"""Schedule interpreter: device 0's idle time in the traced window that
falls inside the program's op spans (the union of ``clftj.op.*``), per
request of the window (ms/query)."""
from bench import trace

OP_SPANS = "clftj.op."


def overlap(a, b):
    """Length of the intersection of two merged interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_spans_and_idle(t):
    """The union of the op spans in the window, and device 0's idle gaps
    there."""
    lo, hi = t.window
    ops = trace.union(trace.clip(
        ((a, b) for name, a, b in t.spans if name.startswith(OP_SPANS)),
        lo, hi))
    return ops, trace.gaps(t.devices[0].busy, lo, hi)


def read(run):
    t = run.trace
    if t is None or not run.requests:
        return None
    ops, idle = op_spans_and_idle(t)
    if not ops:
        return None
    return 1e-6 * overlap(ops, idle) / len(run.requests)
