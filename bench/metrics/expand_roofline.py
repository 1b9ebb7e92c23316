"""EXPAND kernel: its share of the byte roofline over the traced window.

Device time is the time of the modules that run EXPAND, matched by name;
each launch moves ``roofline.expand_bytes`` at the cell's capacity and
widths."""
import re

from bench import roofline, trace

MODULES = re.compile(r"^jit_expand_step$")


def read(run):
    if run.trace is None:
        return None
    secs = sum(v for k, v in run.trace.module_s().items() if MODULES.match(k))
    launches = sum(v for k, v in run.trace.module_n().items()
                   if MODULES.match(k))
    return trace.share(
        launches * roofline.expand_bytes(run.capacity, run.n_vars,
                                         run.n_atoms),
        secs, run.peaks["hbm_bytes_per_s"])
