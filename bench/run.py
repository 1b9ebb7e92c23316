"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``: the graph and the engine's
settings) and a traffic mix (``bench/traffic/<name>.json``: the query, its
variable order, the mode, the entry point and the reference).  One
process:

1. builds the configuration's graph from ``--seed`` and loads it;
2. opens the cell's entry point (``engine.serve`` or the mesh count);
3. warms up by sending the cell's own request until one runs with no
   compilation (set-up ends here: ``setup_s``);
4. measures for ``--seconds`` with one closed-loop client; a request that
   starts before the deadline runs to its end and counts;
5. reads the device's memory peak, frees the engine, computes the plain
   reference on the host and compares every timed answer with it;
6. prints the end-to-end metrics (``--trace 0``) or, from a profiler trace
   of the same window, the per-layer metrics (``--trace 1``), each read by
   its own file under ``bench/metrics``.

The run exits non-zero without a result when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind that ``bench/peaks.json`` lacks.
JAX's persistent compilation cache is kept in ``.jax_cache/bench`` inside
the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache" / "bench"
WARMUP_MAX = 5          # requests sent before giving up on a warm one


class NoChip(RuntimeError):
    """The machine lacks what the cell needs; nothing is measured."""


# -- the cell's files ----------------------------------------------------


def load_cell(workload, root: Path = ROOT):
    """``(bench, cell, config, traffic)`` for a workload: the name of a
    cell in ``BENCHMARK.json``, or a cell's entry itself (a mix that no
    cell lists yet, as the CPU tests run them)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if isinstance(workload, dict):
        cell = workload
    else:
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        cell = cells[workload]
    config = json.loads(
        (root / "bench" / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def load_peaks(root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "peaks.json").read_text())["devices"]


def devices_for(chips: int, peaks: dict):
    """The cell's devices, or :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    peak_row(devs[0].device_kind, peaks)
    return devs[:chips]


def peak_row(kind: str, peaks: dict) -> dict:
    """This device kind's peaks, or :class:`NoChip`."""
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Counts JAX's compile events (tracing, lowering, compiling and
    persistent-cache reads) from the moment it is installed."""

    def __init__(self) -> None:
        import jax.monitoring

        self.n = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_kw) -> None:
        if name.startswith(("/jax/core/compile", "/jax/compilation_cache")):
            with self._lock:
                self.n += 1


# -- the traffic mix's query ----------------------------------------------


def make_query(traffic: dict):
    from repro.core.cq import CQ, Atom

    return CQ(tuple(Atom(rel, (a, b)) for rel, a, b in traffic["query"]))


@dataclasses.dataclass
class Request:
    start: float
    end: float
    count: int
    first_block_s: Optional[float] = None
    rows: Optional[np.ndarray] = None
    order: Optional[tuple] = None
    wall_s: Optional[float] = None       # the program's own Result.wall_s
    syncs: Optional[int] = None          # blocking device->host syncs
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    overflow: int = 0
    compiles: int = 0

    @property
    def latency_s(self) -> float:
        return self.end - self.start


class ServeDriver:
    """One closed-loop client of ``engine.serve(db)``."""

    def __init__(self, edges, config: dict, traffic: dict, devices,
                 engine_overrides: Optional[dict] = None) -> None:
        from repro.configs import paper_clftj
        from repro.core import choose_plan, engine
        from repro.core.db import graph_db

        self.devices = devices
        eng = config["engine"]
        self.engine_config = dataclasses.replace(
            getattr(paper_clftj, eng["preset"]),
            **{k: v for k, v in eng.items() if k != "preset"},
            **(engine_overrides or {}))
        self.mode = traffic["mode"]
        self.query = make_query(traffic)
        db = graph_db(edges)
        self.td, order = choose_plan(self.query, db.stats())
        self.order = tuple(traffic.get("order") or order)
        self.server = engine.serve(db, self.engine_config)
        self.capacity = self.engine_config.frontier_capacity

    def request(self) -> Request:
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("submit"):
            sess = self.server.submit(self.query, self.mode, self.td,
                                      self.order)
        first, rows = None, None
        if self.mode == "stream":
            blocks = []
            it = sess.blocks()
            while True:
                with TraceAnnotation("wait_block"):
                    b = next(it, None)
                if b is None:
                    break
                if first is None:
                    first = time.perf_counter() - t0
                blocks.append(b)
            rows = (np.concatenate(blocks) if blocks
                    else np.zeros((0, len(self.order)), np.int32))
        with TraceAnnotation("wait_result"):
            res = sess.result()
        t1 = time.perf_counter()
        return Request(t0, t1, int(res.count),
                       first_block_s=(t1 - t0 if first is None and rows
                                      is not None else first),
                       rows=rows, order=sess.order, wall_s=res.wall_s,
                       syncs=sess.sync.count, counters=dict(res.counters))

    def warm(self, req: Request) -> bool:
        return req.counters.get("plan_cache_hit", 0) == 1

    def close(self) -> None:
        self.server.close()
        self.server = None


class MeshDriver:
    """One closed-loop caller of ``make_distributed_count``'s jitted
    function over a mesh of the cell's chips."""

    def __init__(self, edges, config: dict, traffic: dict, devices,
                 engine_overrides: Optional[dict] = None) -> None:
        import jax
        from jax.sharding import AxisType

        from repro.core import choose_plan
        from repro.core.db import graph_db
        from repro.core.distributed import make_distributed_count

        if traffic["mode"] != "count":
            raise ValueError("the mesh entry counts only")
        mesh_cfg = dict(config["mesh"], **(engine_overrides or {}))
        self.devices = devices
        self.capacity = int(mesh_cfg["capacity"])
        self.query = make_query(traffic)
        db = graph_db(edges)
        td, order = choose_plan(self.query, db.stats())
        self.order = tuple(traffic.get("order") or order)
        self.mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                                  (AxisType.Auto,) * 2, devices=devices)
        self.fn, _ = make_distributed_count(
            self.query, td, self.order, db, self.mesh,
            capacity=self.capacity, axes=("data", "model"))

    def request(self) -> Request:
        import jax
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("submit"), self.mesh:
            total, overflow = self.fn()
        with TraceAnnotation("wait_result"):
            total, overflow = jax.block_until_ready((total, overflow))
            count, ov = int(total), int(overflow)
        return Request(t0, time.perf_counter(), count, overflow=ov)

    def warm(self, req: Request) -> bool:
        return True

    def close(self) -> None:
        self.fn = None


DRIVERS = {"serve": ServeDriver, "mesh": MeshDriver}


# -- the window ---------------------------------------------------------


def warm_up(driver, compiles: CompileCount) -> List[Request]:
    """Send the cell's request until one compiles nothing and finds the
    entry warm."""
    done = []
    for _ in range(WARMUP_MAX):
        n0 = compiles.n
        req = driver.request()
        req.compiles = compiles.n - n0
        done.append(req)
        if len(done) >= 2 and req.compiles == 0 and driver.warm(req):
            return done
    raise RuntimeError(f"no warm request after {WARMUP_MAX}: compiles per "
                       f"request {[r.compiles for r in done]}")


def window(driver, seconds: float, compiles: CompileCount) -> List[Request]:
    """Closed loop: the next request is sent when the last returns, until
    a request ends at or after ``seconds`` from the first one's start."""
    from jax.profiler import TraceAnnotation

    out: List[Request] = []
    with TraceAnnotation("window"):
        deadline = time.perf_counter() + seconds
        while True:
            n0 = compiles.n
            req = driver.request()
            req.compiles = compiles.n - n0
            out.append(req)
            if req.end >= deadline:
                return out


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- correctness --------------------------------------------------------


def gaps(requests: List[Request], traffic: dict, edges, nv: int
         ) -> List[Dict[str, int]]:
    """Per timed request, each number compared: the gap between the
    engine's answer and the plain reference's."""
    from bench import reference

    want: Dict[tuple, dict] = {}
    out = []
    for r in requests:
        order = r.order or ()
        if order not in want:
            want[order] = reference.answers(traffic, edges, nv, order)
        g = {"count_gap": abs(r.count - want[order]["count"])}
        if traffic["mode"] == "stream":
            g["row_gap"] = reference.row_gap(r.rows, want[order]["rows"], nv)
        if traffic["entry"] == "mesh":
            g["overflow_shards"] = r.overflow
        out.append(g)
    return out


LIMITS = {"count_gap": 0, "row_gap": 0, "overflow_shards": 0}


def compare(per_request: List[Dict[str, int]]) -> Dict[str, dict]:
    """Each number compared, its largest value over the timed requests
    beside its limit."""
    return {k: {"value": max(g[k] for g in per_request), "limit": LIMITS[k]}
            for k in per_request[0]}


def failed(per_request: List[Dict[str, int]]) -> int:
    """Timed requests whose answer differs from the reference's."""
    return sum(1 for g in per_request
               if any(v > LIMITS[k] for k, v in g.items()))


def verdict(requests: List[Request], traffic: dict, edges, nv: int):
    """``(correct, failed, checks)`` of the timed requests' answers."""
    per_request = gaps(requests, traffic, edges, nv)
    checks = compare(per_request)
    n_failed = failed(per_request)
    correct = n_failed == 0 and all(c["value"] <= c["limit"]
                                    for c in checks.values())
    return correct, n_failed, checks


# -- metrics ------------------------------------------------------------


def p95(xs: List[float]) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def end_to_end(requests: List[Request], setup_s: float) -> Dict[str, float]:
    span = requests[-1].end - requests[0].start
    out = {"setup_s": setup_s,
           "queries_per_s": len(requests) / span,
           "query_s.p95": p95([r.latency_s for r in requests])}
    if requests[0].first_block_s is not None:
        out["first_block_s.p95"] = p95([r.first_block_s for r in requests])
    return out


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader may read."""
    cell: dict
    config: dict
    traffic: dict
    requests: List[Request]
    trace: object                 # bench.trace.Summary
    peaks: dict                   # this device kind's row of peaks.json
    memory_peak_bytes: Optional[int]
    capacity: int                 # frontier rows per chunk
    n_vars: int
    n_atoms: int


def metric_names(bench: dict, cell: dict, per_layer: bool) -> List[dict]:
    """The metric entries of ``BENCHMARK.json`` that this cell reports: an
    entry with ``workloads`` names its cells, one without is every cell's
    (end-to-end metrics only; every per-layer entry lists its cells)."""
    name = cell["name"]
    group = bench["per_layer"] if per_layer else bench["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def read_metric(name: str, view: RunView, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


# -- one run ------------------------------------------------------------


def run(workload, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, require_chip: bool = True,
        graph_overrides: Optional[dict] = None,
        engine_overrides: Optional[dict] = None,
        t_start: float = T_START) -> dict:
    """One run of one cell; returns the result line's object.  Tests pass
    ``require_chip=False`` and small ``graph_overrides``."""
    import jax

    from bench import graphs
    from bench import trace as tr

    bench, cell, config, traffic = load_cell(workload, root)
    peaks = load_peaks(root)
    if require_chip:
        devices = devices_for(int(cell["chips"]), peaks)
        peaks_here = peak_row(devices[0].device_kind, peaks)
    else:
        devices = jax.devices()[:int(cell["chips"])]
        peaks_here = next(iter(peaks.values()))
    compiles = CompileCount()
    graph = dict(config, **(graph_overrides or {}))
    nv = graphs.vertices(graph)
    edges = graphs.build(graph, seed)
    driver = DRIVERS[traffic["entry"]](edges, config, traffic, devices,
                                       engine_overrides)
    warm_up(driver, compiles)
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            requests = window(driver, seconds, compiles)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        mem = memory_peak_bytes(devices)
        summary = tr.load(trace_dir) if trace_dir else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    capacity = driver.capacity
    driver.close()
    del driver
    gc.collect()

    correct, n_failed, checks = verdict(requests, traffic, edges, nv)
    e2e = end_to_end(requests, setup_s)
    unit = {m["name"]: m["unit"] for m in bench["end_to_end"]
            + bench["per_layer"]}
    metrics: Dict[str, dict] = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": len(requests),
           "failed": n_failed}
    if not trace:
        for m in metric_names(bench, cell, per_layer=False):
            metrics[m["name"]] = {"value": e2e[m["name"]],
                                  "unit": m["unit"]}
    else:
        view = RunView(cell, config, traffic, requests, summary, peaks_here,
                       mem, capacity, len({v for _, a, b in
                                          traffic["query"] for v in (a, b)}),
                       len(traffic["query"]))
        for m in metric_names(bench, cell, True):
            v = read_metric(m["name"], view, root)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": unit[m["name"]]}
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": tr.top(summary.module_s()),
                            "idle_gaps": [list(g) for g in
                                          summary.idle_gaps(10)]}
    out["metrics"] = metrics
    out["device"] = device
    out["samples"] = len(requests)
    out["window_compiles"] = sum(r.compiles for r in requests)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    enable_cache()
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
