"""Device-to-host emit: the time the program waits for streamed blocks'
device-to-host copies, the union of its ``clftj.fetch_wait.emit-stream``
spans in the traced window, per request of the window (ms/query)."""
from bench import trace

SPAN = "clftj.fetch_wait.emit-stream"


def read(run):
    t = run.trace
    if t is None or not run.requests:
        return None
    lo, hi = t.window
    waits = trace.union(trace.clip(
        ((a, b) for name, a, b in t.spans if name == SPAN), lo, hi))
    if not waits:
        return None
    return 1e-6 * trace.length(waits) / len(run.requests)
