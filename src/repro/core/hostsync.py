"""Deliberate device→host synchronization funnel + the async emit queue.

Every host sync on the join-engine hot path goes through :func:`device_get`
so the cost that used to be invisible (``bool(F.valid.any())`` per chunk,
``int(...)`` per stat) is a *counted event*: tests put a :class:`SyncCounter`
around a query and assert the executor stays under a fixed budget
(``tests/test_sync_budget.py``).  The schedule executor batches its
admission checks so the count is O(ops), not O(chunks).

**Async fetches (DESIGN.md §2.8).**  Evaluation-mode emission used to drain
every result block with one blocking fetch at pass end — the device idled
while the host copied.  :func:`device_get_async` instead *issues* the
device→host copy (``jax.Array.copy_to_host_async``) and returns an
:class:`AsyncFetch` handle; the copy proceeds in the background while the
executor keeps launching the next morsel's work.  :class:`AsyncFetchQueue`
bounds how many fetches may be in flight (device buffers pinned per
in-flight block) and preserves FIFO arrival order.

Accounting rules (budget-tested):

* ``SyncCounter.count`` counts **blocking** syncs only — the number that
  must stay O(ops).
* an async *issue* increments ``SyncCounter.async_count`` and rides
  ``label_counts`` under its own label (e.g. ``emit-stream``),
  so in-flight fetches are visible separately and a test can pin their
  frequency without conflating them with blocking syncs.
* *completing* an async fetch (``AsyncFetch.get``) is not a counted event:
  the copy was issued — and accounted — when the handle was created.
* counter scopes are **thread-local**: a ``SyncCounter`` only observes
  syncs issued by the thread that entered it (the serving layer budgets
  each session's worker-thread execution independently).

Every sync is also a host span in a profiler trace, on the device's
clock: ``clftj.sync.<label>`` around a blocking fetch,
``clftj.fetch_issue.<label>`` around an async issue and
``clftj.fetch_wait.<label>`` around its completion
(``jax.profiler.TraceAnnotation``: one check when no trace is active).
"""
from __future__ import annotations

import threading
from collections import Counter, deque
from typing import Any, Deque, Dict, Iterator, List, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

# Counter scopes are PER THREAD: the serving layer (repro/serve) runs many
# client sessions against one process, and a SyncCounter opened around one
# session's query must not absorb syncs issued by another thread's work.
_tls = threading.local()


def _active() -> List["SyncCounter"]:
    lst = getattr(_tls, "counters", None)
    if lst is None:
        lst = _tls.counters = []
    return lst


class SyncCounter:
    """Context manager counting device→host syncs made through this funnel.

    ``count`` is the number of blocking :func:`device_get` calls (each call
    may fetch a whole pytree — that is the point: one batched fetch per op,
    not one per chunk).  ``async_count`` is the number of
    :func:`device_get_async` issues (non-blocking; the copy overlaps device
    work).  ``label_counts`` counts both per label, for diagnosing
    regressions and so budget tests can pin one label's frequency (e.g.
    the evaluation-mode payload plan must ride the per-fold
    ``replay-plan`` fetch — O(ops), not O(hits) — and streaming emission
    must issue ``emit-stream`` fetches asynchronously, never as blocking
    syncs)."""

    def __init__(self) -> None:
        self.count = 0
        self.async_count = 0
        self.label_counts: Counter = Counter()

    def __enter__(self) -> "SyncCounter":
        _active().append(self)
        return self

    def __exit__(self, *exc) -> bool:
        _active().remove(self)
        return False


def device_get(tree: Any, label: str = "") -> Any:
    """``jax.device_get`` with sync accounting (one event per call)."""
    for c in _active():
        c.count += 1
        c.label_counts[label] += 1
    with TraceAnnotation(f"clftj.sync.{label}"):
        return jax.device_get(tree)


# ---------------------------------------------------------------------------
# Async fetches (streaming emit — DESIGN.md §2.8)
# ---------------------------------------------------------------------------


class AsyncFetch:
    """Handle for one issued (in-flight) device→host copy of a pytree.

    Created by :func:`device_get_async`; :meth:`get` materializes the host
    values (fast once the background copy has landed).  Completion is not
    a counted sync — the fetch was accounted at issue time."""

    __slots__ = ("tree", "label")

    def __init__(self, tree: Any, label: str):
        self.tree = tree
        self.label = label

    def ready(self) -> bool:
        """Best-effort readiness: True once every leaf's *producing
        computation* has completed (``jax.Array.is_ready``).  The D2H
        copy issued at creation usually lands with it, but JAX exposes no
        copy-completion signal, so :meth:`get` may still briefly block on
        the transfer itself — ``ready()`` is a scheduling hint (used by
        ``poll`` to avoid obviously-blocking pops), not a no-block
        guarantee."""
        for leaf in jax.tree.leaves(self.tree):
            if isinstance(leaf, jax.Array) and not leaf.is_ready():
                return False
        return True

    def get(self) -> Any:
        with TraceAnnotation(f"clftj.fetch_wait.{self.label}"):
            return jax.device_get(self.tree)


def device_get_async(tree: Any, label: str = "") -> AsyncFetch:
    """Issue a non-blocking device→host copy of ``tree``.

    Starts ``copy_to_host_async`` on every ``jax.Array`` leaf and returns
    an :class:`AsyncFetch`.  Counted as an *async* event (see the module
    docstring's accounting rules): ``SyncCounter.async_count`` and
    ``label_counts[label]`` advance, ``count`` does not."""
    with TraceAnnotation(f"clftj.fetch_issue.{label}"):
        for leaf in jax.tree.leaves(tree):
            if isinstance(leaf, jax.Array):
                try:
                    leaf.copy_to_host_async()
                except (NotImplementedError, AttributeError):
                    # backend without D2H async: .get() still works, it
                    # just blocks on the transfer.  Real failures
                    # (deleted/donated buffers, ...) must surface HERE,
                    # not at some later unrelated .get() — so only the
                    # unsupported cases pass.
                    pass
    for c in _active():
        c.async_count += 1
        c.label_counts[label] += 1
    return AsyncFetch(tree, label)


class AsyncFetchQueue:
    """Bounded FIFO of in-flight async fetches (the streaming emit queue).

    ``put`` issues a new fetch; when the bound is reached the *oldest*
    fetch is completed first (back-pressure: at most ``max_in_flight``
    device blocks are pinned by emission at any moment).  ``poll`` pops
    fetches whose copies have already landed without blocking; ``drain``
    completes everything.  All three return host pytrees in issue order,
    so a consumer that concatenates ``put``/``poll``/``drain`` results
    sees blocks in exact production order.

    ``double_buffer=True`` makes completions land in a ring of
    preallocated host staging buffers per (shape, dtype) — one slot per
    possible in-flight fetch, so repeated same-shape blocks stop
    allocating a fresh host array each (the common case: every EMIT block
    is a full (C, n) chunk).  The returned arrays are *recycled*: a
    consumer must copy what it keeps before issuing/completing further
    fetches of the same shape.

    A queue is reusable across streaming passes: :meth:`reset` rezeroes
    the per-pass accounting (``issued``/``high_water``/``labels``) so a
    drained-then-reused queue reports each session's issue counts alone,
    not the process-lifetime total."""

    def __init__(self, max_in_flight: int = 8, double_buffer: bool = False):
        if max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1")
        self.max_in_flight = int(max_in_flight)
        self.double_buffer = bool(double_buffer)
        self._q: Deque[AsyncFetch] = deque()
        self.issued = 0
        self.high_water = 0  # max simultaneous in-flight fetches observed
        self.labels: Counter = Counter()  # per-label issue counts (per pass)
        # (shape, dtype) -> ring of staging buffers; rotated per completion
        self._rings: Dict[Tuple, List[np.ndarray]] = {}
        self._ring_pos: Dict[Tuple, int] = {}

    @property
    def in_flight(self) -> int:
        return len(self._q)

    def reset(self) -> None:
        """Rezero the per-pass accounting before reusing the queue.

        Refuses while fetches are in flight — accounting for copies that
        were issued in a previous pass must not silently vanish (complete
        or ``drain`` them first).  Staging rings are kept: their whole
        point is surviving across passes."""
        if self._q:
            raise RuntimeError(
                f"reset with {len(self._q)} fetches in flight; drain first")
        self.issued = 0
        self.high_water = 0
        self.labels.clear()

    def _complete(self, fetch: AsyncFetch) -> Any:
        host = fetch.get()
        if not self.double_buffer:
            return host
        return jax.tree.map(self._stage, host)

    def _stage(self, leaf: Any) -> Any:
        arr = np.asarray(leaf)
        if arr.dtype == object or arr.ndim == 0:
            return leaf
        key = (arr.shape, str(arr.dtype))
        ring = self._rings.get(key)
        if ring is None:
            # one slot per possible in-flight fetch: a poll/drain batch can
            # complete up to max_in_flight same-shape blocks before the
            # consumer copies any of them out
            depth = max(2, self.max_in_flight)
            ring = self._rings[key] = [np.empty_like(arr)
                                       for _ in range(depth)]
            self._ring_pos[key] = 0
        i = self._ring_pos[key]
        self._ring_pos[key] = (i + 1) % len(ring)
        np.copyto(ring[i], arr)
        return ring[i]

    def put(self, tree: Any, label: str = "") -> List[Any]:
        """Issue one fetch; returns the host values of any fetches that had
        to be completed to stay under the in-flight bound (oldest first,
        possibly empty)."""
        done: List[Any] = []
        while len(self._q) >= self.max_in_flight:
            done.append(self._complete(self._q.popleft()))
        self._q.append(device_get_async(tree, label))
        self.issued += 1
        self.labels[label] += 1
        self.high_water = max(self.high_water, len(self._q))
        return done

    def poll(self) -> List[Any]:
        """Pop fetches from the head whose producing computation has
        landed (see :meth:`AsyncFetch.ready` for what that does and does
        not guarantee).  FIFO: a ready fetch behind a still-flying one
        stays queued — order is part of the contract."""
        done: List[Any] = []
        while self._q and self._q[0].ready():
            done.append(self._complete(self._q.popleft()))
        return done

    def drain(self) -> Iterator[Any]:
        """Complete every remaining fetch, oldest first."""
        while self._q:
            yield self._complete(self._q.popleft())
