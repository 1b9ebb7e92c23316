"""EMIT kernel: its share of the byte roofline over the traced window.

Device time is the time of the module that runs EMIT (``jit_emit_step``);
each launch moves ``roofline_fold_emit.emit_bytes`` at the cell's capacity
and widths."""
import re

from bench import roofline_fold_emit, trace

MODULES = re.compile(r"^jit_emit_step$")


def read(run):
    if run.trace is None:
        return None
    secs = sum(v for k, v in run.trace.module_s().items() if MODULES.match(k))
    launches = sum(v for k, v in run.trace.module_n().items()
                   if MODULES.match(k))
    return trace.share(
        launches * roofline_fold_emit.emit_bytes(run.capacity, run.n_vars),
        secs, run.peaks["hbm_bytes_per_s"])
