"""FOLD kernel: its share of the byte roofline over the traced window.

Device time is the time of the module that runs FOLD (``jit_fold_step``,
its replay, splice and merged variants alike); each launch moves
``roofline_fold_emit.fold_bytes`` at the cell's capacity and widths, with a
source of ``SUB_VARS`` columns."""
import re

from bench import roofline_fold_emit, trace

MODULES = re.compile(r"^jit_fold_step$")
# the mutual 2-hop's child bag binds one variable (x3); no FOLD's source
# has fewer columns, so no launch is counted above its bytes
SUB_VARS = 1


def read(run):
    if run.trace is None:
        return None
    secs = sum(v for k, v in run.trace.module_s().items() if MODULES.match(k))
    launches = sum(v for k, v in run.trace.module_n().items()
                   if MODULES.match(k))
    return trace.share(
        launches * roofline_fold_emit.fold_bytes(
            run.capacity, run.n_vars, run.n_atoms, SUB_VARS),
        secs, run.peaks["hbm_bytes_per_s"])
