"""Schedule interpreter: device 0's idle time inside the program's op
spans (``clftj.op.*``) during one request, the program's
``clftj.serve.execute`` span, median over the window's requests
(ms/query).  ``interp_idle_ms`` is the mean over the window, which one
stall moves; this median does not."""
import statistics

from bench import trace
from bench.metrics.interp_idle_ms import op_spans_and_idle, overlap

EXECUTE_SPAN = "clftj.serve.execute"


def read(run):
    t = run.trace
    if t is None:
        return None
    ops, idle = op_spans_and_idle(t)
    lo, hi = t.window
    per = [1e-6 * overlap(trace.union(trace.clip(ops, a, b)), idle)
           for name, a, b in t.spans
           if name == EXECUTE_SPAN and a < hi and b > lo]
    if not ops or not per:
        return None
    return statistics.median(per)
