"""Query serving across processes (DESIGN.md §2.9).

    PYTHONPATH=src python examples/serve_join.py

Runs the two-process demo end to end:

* **process A** opens a :func:`repro.core.engine.serve` server, answers a
  few isomorphic queries (the second is a plan-cache hit — same compiled
  engine, warm tier-2 tables), streams one concurrently, and writes a
  snapshot of the warm state;
* **process B** — a genuinely separate interpreter — loads the snapshot
  and shows that its *first* query is already warm: plan-cache hit,
  ``tier2_replay_hits > 0``, identical answers.

Pass ``a``/``b`` as argv[1] to run one side manually (e.g. on two
machines sharing a filesystem).  The parent imports no JAX: on a TPU a
process that has touched JAX holds the chip, so only the children may.
"""
import os
import subprocess
import sys
import tempfile

SNAP = os.environ.get("SERVE_SNAP",
                      os.path.join(tempfile.gettempdir(), "serve_join.npz"))


def setup():
    """JAX-side imports and the shared query/database, for one phase."""
    import numpy as np

    from repro.core import path_query
    from repro.core.cq import CQ, Atom
    from repro.core.db import graph_db
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # E(x,y) ⋈ E(y,z) ⋈ E(z,w) — and an isomorphic copy a client might
    # send (vars renamed a/z/b/q, atoms reordered: same plan-cache key)
    q = path_query(4)
    q_iso = CQ((Atom("E", ("b", "q")), Atom("E", ("z", "b")),
                Atom("E", ("a", "z"))))
    rng = np.random.default_rng(7)
    return q, q_iso, graph_db(rng.integers(0, 120, size=(900, 2)))


def process_a() -> None:
    from repro.core.engine import serve

    q, _, db = setup()
    with serve(db) as srv:
        r1 = srv.evaluate(q)
        r2 = srv.evaluate(q)          # same shape: plan-cache hit + replay
        print(f"A: q1 hit={r1.plan_cache_hit} rows={len(r1.tuples)} "
              f"wall={r1.wall_s:.2f}s")
        print(f"A: q2 hit={r2.plan_cache_hit} rows={len(r2.tuples)} "
              f"replay={r2.tier2_replay_hits} wall={r2.wall_s:.2f}s")
        sess = srv.evaluate_stream(q)  # concurrent streaming session
        n = sum(b.shape[0] for b in sess.blocks())
        print(f"A: streamed {n} rows in order {sess.result().order}")
        srv.save_snapshot(SNAP)
        print(f"A: snapshot -> {SNAP} ({os.path.getsize(SNAP)} bytes)")


def process_b() -> None:
    from repro.core.engine import serve

    q, q_iso, db = setup()
    with serve(db) as srv:
        summary = srv.load_snapshot(SNAP)
        print(f"B: loaded {summary}")
        r = srv.evaluate(q_iso)        # FIRST query, isomorphic renaming
        print(f"B: first query hit={r.plan_cache_hit} "
              f"replay={r.tier2_replay_hits} rows={len(r.tuples)} "
              f"wall={r.wall_s:.2f}s")
        assert r.plan_cache_hit and r.tier2_replay_hits > 0
        print("B: warm across the process boundary ✓")


def main() -> None:
    if len(sys.argv) > 1:
        {"a": process_a, "b": process_b}[sys.argv[1]]()
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    for phase in ("a", "b"):
        subprocess.run([sys.executable, __file__, phase], env=env,
                       check=True)


if __name__ == "__main__":
    main()
