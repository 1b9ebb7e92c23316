"""The control of the comparison that decides ``correct``.

The configurations promise exact answers over int32 vertex ids.  The step
that would tempt a later change is a narrower id: columns of int16 or int8
move half or a quarter of the bytes.  The control puts the plain reference
in the engine's place with every id held in the widest integer type below
int32 that the configuration's ids do not fit (ids wrap modulo 2^bits),
and hands its answers, as timed requests, to the harness's own comparison
(``run.verdict``), which must find them not correct.

    python bench/control.py --workload <name> --seed <n> [--seed <n> ...]

prints, for each seed, the numbers compared beside their limits.  It needs
no chip and is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def narrow_bits(nv: int) -> int:
    """The widest of 16 and 8 bits that ids below ``nv`` do not fit."""
    for bits in (16, 8):
        if nv > 1 << (bits - 1):
            return bits
    raise ValueError(f"ids below {nv} fit 8 bits; no narrower type")


def narrowed(edges: np.ndarray, nv: int) -> np.ndarray:
    """``edges`` with ids wrapped to ``narrow_bits(nv)`` signed bits and
    read back as non-negative ids, without the self loops and repeats the
    wrapping makes."""
    mask = (1 << narrow_bits(nv)) - 1
    e = edges & mask
    e = e[e[:, 0] != e[:, 1]]
    return np.unique(e, axis=0)


def requests(traffic: dict, edges: np.ndarray, nv: int, order,
             n: int = 2) -> list:
    """``n`` timed requests as the control answers them: the reference's
    answers over the narrowed ids, in the engine's place."""
    from bench import reference
    from bench.run import Request

    e = narrowed(edges, nv)
    rows = None
    if traffic["mode"] == "stream":
        rows = reference.join_rows(traffic["query"], e, nv, order)
        count = len(rows)
    else:
        count = reference.answers(traffic, e, nv, order)["count"]
    return [Request(0.0, 1.0, count, rows=rows, order=tuple(order))
            for _ in range(n)]


def checks(workload, seed: int, root: Path = ROOT,
           graph_overrides: Optional[dict] = None):
    """``(correct, failed, checks)`` of the control, as the harness's own
    comparison (``run.verdict``) decides them."""
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import graphs
    from bench.run import load_cell, verdict

    _, _, config, traffic = load_cell(workload, root)
    graph = dict(config, **(graph_overrides or {}))
    nv = graphs.vertices(graph)
    edges = graphs.build(graph, seed)
    order = tuple(traffic.get("order") or
                  dict.fromkeys(v for _, a, b in traffic["query"]
                                for v in (a, b)))
    return verdict(requests(traffic, edges, nv, order), traffic, edges, nv)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    for seed in args.seed:
        correct, n_failed, c = checks(args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct, "failed": n_failed,
                          "checks": c}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
