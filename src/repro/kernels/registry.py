"""Kernel registry: the single entry-point convention for device kernels.

Every compute kernel in the repo is reached through this module, never by
importing an implementation module directly:

  * **bounded search** (``lower_bound``/``upper_bound``) — the batched
    leapfrog-seek primitive, with ``impl`` dispatch between the branchless
    fixed-trip binary search (production on CPU/host), the Pallas dense
    count kernel (``kernels/leapfrog``; interpret mode on CPU), and the
    dense jnp oracle (``kernels/leapfrog/ref.py`` — tests).  Folded here
    from the former ``kernels/leapfrog/ops.py``.
  * **fused EXPAND** (``expand_fn``) — one frontier-expansion step
    (DESIGN.md §2.7).  Two implementations: ``"pallas"`` — the fused
    single-pass kernel (``kernels/expand/fused.py``: guard-run
    enumeration, membership binary searches, mask reduction, and frontier
    compaction in one ``pallas_call``; interpret mode on CPU) — and
    ``"xla"`` — the original jnp op chain (``kernels/expand/xla.py``),
    the always-available fallback.  ``kernels/expand/ref.py`` is the
    plain-numpy oracle both are validated against.
  * **fused FOLD** (``fold_fn``) — one bracket-close step (DESIGN.md
    §2.10): miss-path replay, tier-2 hit-path slab splice, and the
    adhesion-key merge/compact, as one ``pallas_call``
    (``kernels/fold/fused.py``) or the relocated jnp op chain
    (``kernels/fold/xla.py``; also the canonical home of
    ``replay_step``/``splice_step``/``merge_compact``).
  * **fused EMIT** (``emit_fn``) — the stable valid-row pack that turns
    an EMIT frontier into ``(packed, k)`` host-transfer form
    (``kernels/emit/fused.py`` | ``kernels/emit/xla.py``).

Dispatch (``select_expand``/``select_fold``/``select_emit``): degenerate
specs (empty guard trie / empty participating relation, where expansion
is statically empty) always take the XLA path.  Whether a fused kernel is
*available* is decided by compiling it — ``lower(...).compile()`` at the
spec's real shapes for the default device — once per (spec, platform);
a refusal keeps the compiler's message in :func:`failures`.  A forced
``"pallas"`` that the compiler refuses raises with that message.
``"auto"`` resolves per spec: on TPU/GPU a refused spec takes the XLA
chain (with a warning); otherwise the fused kernel is measured against
the XLA chain once per (spec, platform) and the winner is cached (the
tiny measured-autotune cache, :func:`autotune_cache`); on CPU ``"auto"``
picks XLA without compiling or measuring (interpret mode exists for
conformance, not speed; pass ``measure=True`` to force a measurement
anywhere).  Selection runs outside any trace: callers that trace the
kernels into a larger program (``core/distributed.py``) resolve them
first.  All three ops share one autotune cache and sidecar; records are
discriminated by an ``"op"`` field (absent → ``"expand"``, so
pre-FOLD/EMIT sidecars load unchanged).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time
import warnings
from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from .leapfrog import leapfrog, ref as leapfrog_ref

__all__ = ["ExpandSpec", "FoldSpec", "EmitSpec",
           "lower_bound", "upper_bound",
           "expand_fn", "fold_fn", "emit_fn",
           "select_expand", "select_fold", "select_emit",
           "autotune_cache", "failures",
           "clear_autotune_cache", "device_op_count",
           "save_autotune_cache", "load_autotune_cache",
           "autotune_entries", "merge_autotune_entries",
           "AUTOTUNE_CACHE_ENV", "EXPAND_MODES", "KERNEL_MODES"]


# ---------------------------------------------------------------------------
# Bounded search (the former kernels/leapfrog/ops.py)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("strict",))
def _bsearch(col: jnp.ndarray, values: jnp.ndarray, lo: jnp.ndarray,
             hi: jnp.ndarray, strict: bool = True) -> jnp.ndarray:
    """Vectorized bounded binary search; log2(N)+1 fixed iterations."""
    n = col.shape[0]
    if n == 0:
        return lo
    trips = max(1, int(math.ceil(math.log2(n + 1))) + 1)
    dtype = lo.dtype

    def body(_, lh):
        lo_, hi_ = lh
        go = lo_ < hi_
        mid = (lo_ + hi_) >> 1
        x = col[jnp.clip(mid, 0, n - 1)]
        pred = (x < values) if strict else (x <= values)
        lo2 = jnp.where(go & pred, mid + 1, lo_)
        hi2 = jnp.where(go & ~pred, mid, hi_)
        return lo2, hi2

    lo_, _ = jax.lax.fori_loop(0, trips, body, (lo.astype(dtype),
                                                hi.astype(dtype)))
    return lo_


def lower_bound(col, values, lo, hi, impl: str = "bsearch"):
    if impl == "bsearch":
        return _bsearch(col, values, lo, hi, strict=True)
    if impl == "pallas":
        return leapfrog.lower_bound_pallas(col, values, lo, hi)
    if impl == "ref":
        return leapfrog_ref.lower_bound_ref(col, values, lo, hi)
    raise ValueError(impl)


def upper_bound(col, values, lo, hi, impl: str = "bsearch"):
    if impl == "bsearch":
        return _bsearch(col, values, lo, hi, strict=False)
    if impl == "pallas":
        return leapfrog.upper_bound_pallas(col, values, lo, hi)
    if impl == "ref":
        return leapfrog_ref.upper_bound_ref(col, values, lo, hi)
    raise ValueError(impl)


# ---------------------------------------------------------------------------
# EXPAND / FOLD / EMIT dispatch + autotune
# ---------------------------------------------------------------------------

EXPAND_MODES = ("auto", "pallas", "xla")
# FOLD and EMIT use the same mode vocabulary; the alias keeps call sites
# honest about which knob they validate without duplicating the tuple.
KERNEL_MODES = EXPAND_MODES


@dataclass(frozen=True)
class ExpandSpec:
    """The dispatch key of one EXPAND(d) op: what the kernel choice may
    legitimately depend on.  Everything else (the actual trie arrays, the
    depth, the guard index) parameterizes the *built* function, not the
    *selection*."""

    capacity: int     # chunk capacity C
    n_vars: int       # assignment columns (order length)
    n_atoms: int      # lo/hi columns (atom count m)
    n_others: int     # participating membership atoms at this depth
    dtype: str        # trie column dtype (e.g. "int32")
    x64: bool         # 64-bit factor arithmetic enabled


@dataclass(frozen=True)
class FoldSpec:
    """The dispatch key of one FOLD_CHILD bracket close (DESIGN.md §2.10).

    ``width`` is the folded variable span ``d1 - d0 + 1`` (the slab's
    column count on the splice path); the two ``with_*`` flags select the
    kernel arity — replay-only (no tier-2 table at this node), splice-only
    (static all-hit schedules), or the merged miss+hit step."""

    capacity: int      # chunk capacity C
    n_vars: int        # assignment columns (order length)
    n_atoms: int       # lo/hi columns (atom count m)
    width: int         # folded span width d1 - d0 + 1
    with_replay: bool  # miss-path replay input present
    with_splice: bool  # tier-2 hit-path slab input present
    dtype: str         # assignment dtype (e.g. "int32")
    x64: bool          # 64-bit factor arithmetic enabled


@dataclass(frozen=True)
class EmitSpec:
    """The dispatch key of one EMIT pack: ``fn(assign, valid) ->
    (packed, k)`` with the valid rows stably moved to the front."""

    capacity: int  # chunk capacity C
    n_vars: int    # assignment columns (order length)
    dtype: str     # assignment dtype (e.g. "int32")
    x64: bool      # 64-bit mode enabled (affects lowering, so keyed)


# sidecar records are discriminated by an "op" field naming the spec
# class; absent → "expand" so pre-FOLD/EMIT sidecars keep loading
_SPEC_CLASSES = {"expand": ExpandSpec, "fold": FoldSpec, "emit": EmitSpec}
_OP_OF_SPEC = {cls: op for op, cls in _SPEC_CLASSES.items()}

# (spec, platform) -> chosen impl
# (spec is an ExpandSpec | FoldSpec | EmitSpec — the dataclasses are
# distinct types, so one dict cannot collide across ops)
_AUTOTUNE: Dict[Tuple[object, str], str] = {}
# (spec, platform) -> None if the fused kernel compiles, else the
# compiler's message
_COMPILES: Dict[Tuple[object, str], Optional[str]] = {}

# measured-autotune persistence (ROADMAP follow-on from the kernel PR):
# autotuning costs one compile+timing of BOTH paths per (spec, platform);
# the sidecar makes that a once-per-machine cost instead of once-per-
# process.  Set REPRO_AUTOTUNE_CACHE to a JSON path to auto-load it before
# the first "auto" resolution and write through after every measurement.
# Only MEASURED decisions persist (``_MEASURED`` tracks them): the
# platform-heuristic defaults are free to recompute and persisting them
# would pre-empt a later ``measure=True`` run with a never-measured guess.
AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_SIDECAR_VERSION = 1
_sidecar_loaded = False
_MEASURED: set = set()  # keys whose _AUTOTUNE entry came from a timing run


def autotune_cache() -> Dict[Tuple[ExpandSpec, str], str]:
    return dict(_AUTOTUNE)


def failures() -> Dict[Tuple[ExpandSpec, str], str]:
    """(spec, platform) -> why the fused kernel is unavailable there."""
    return {key: f"pallas: {why}" for key, why in _COMPILES.items()
            if why is not None}


def clear_autotune_cache() -> None:
    global _sidecar_loaded
    _AUTOTUNE.clear()
    _COMPILES.clear()
    _MEASURED.clear()
    _sidecar_loaded = False


def autotune_entries() -> list:
    """The measured autotune decisions as JSON-able records — the sidecar
    file's ``entries`` list, exposed so larger snapshots (the serving
    layer's ``repro/serve/persist.py``) can embed the same records instead
    of shipping a second file format.  Heuristic (unmeasured) decisions
    are excluded, as in :func:`save_autotune_cache`.

    EXPAND records keep the original (op-less) shape so sidecars written
    here load on pre-FOLD/EMIT revisions; FOLD/EMIT records carry an
    ``"op"`` discriminator (older readers skip them individually — an
    unknown record is a cold-start, not an error)."""
    out = []
    for (spec, platform), choice in _AUTOTUNE.items():
        if (spec, platform) not in _MEASURED:
            continue
        ent = {"spec": dataclasses.asdict(spec), "platform": platform,
               "choice": choice}
        op = _OP_OF_SPEC[type(spec)]
        if op != "expand":
            ent["op"] = op
        out.append(ent)
    return out


def merge_autotune_entries(entries) -> int:
    """Merge sidecar-format records into the in-memory cache.

    In-memory decisions win (this process may have re-measured); malformed
    entries are skipped individually so one bad record cannot poison the
    rest.  Returns the number of entries merged."""
    if not isinstance(entries, (list, tuple)):
        return 0
    fields = {op: {f.name for f in dataclasses.fields(cls)}
              for op, cls in _SPEC_CLASSES.items()}
    n = 0
    for ent in entries:
        try:
            op = str(ent.get("op", "expand"))  # op-less = pre-FOLD/EMIT
            cls = _SPEC_CLASSES.get(op)
            if cls is None:
                continue  # written by a newer op vocabulary
            spec_d = dict(ent["spec"])
            if set(spec_d) != fields[op]:
                continue  # written by a different spec revision
            key = (cls(**spec_d), str(ent["platform"]))
            choice = str(ent["choice"])
            if choice not in ("pallas", "xla"):
                continue
        except (AttributeError, KeyError, TypeError, ValueError):
            continue
        if key not in _AUTOTUNE:
            _AUTOTUNE[key] = choice
            _MEASURED.add(key)  # sidecar entries originate from timing runs
            n += 1
    return n


def save_autotune_cache(path: Optional[str] = None) -> Optional[str]:
    """Persist the measured autotune decisions as a JSON sidecar.

    Entries are keyed by ``(spec, platform)``: each record carries the
    :class:`ExpandSpec` fields verbatim, so a process with a different
    capacity/arity mix shares only the entries that actually match.
    Heuristic (unmeasured) entries are not written — see the module
    comment.  On-disk entries are merged in first (in-memory wins), so
    sequential writers preserve each other's measurements; simultaneous
    writers are best-effort (no file lock — a lost entry just costs one
    re-measurement).  ``path`` defaults to ``$REPRO_AUTOTUNE_CACHE``;
    returns the path written, or ``None`` when there is neither a path
    nor anything to write (an empty save never clobbers an existing
    sidecar)."""
    path = path or os.environ.get(AUTOTUNE_CACHE_ENV)
    if not path:
        return None
    # merge the on-disk entries first (in-memory wins) so a write-through
    # doesn't simply replace what other processes measured.  Best-effort
    # only: the read-merge-replace is not atomic, so two processes
    # writing in the same instant can still lose one entry (it is a
    # cache — the loser re-measures once); no locking for that corner.
    if os.path.exists(path):
        load_autotune_cache(path)
    entries = autotune_entries()
    if not entries:
        return None
    payload = {"version": _SIDECAR_VERSION, "entries": entries}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)  # atomic: a concurrent reader never sees a torn file
    return path


def load_autotune_cache(path: Optional[str] = None) -> int:
    """Merge a JSON sidecar into the in-memory autotune cache.

    Returns the number of entries merged.  In-memory decisions win over
    the sidecar's (this process may have re-measured).  A missing,
    corrupt, or wrong-schema file is a *fallback to measuring*, never an
    error — exactly like a cold cache; malformed entries are skipped
    individually so one bad record cannot poison the rest."""
    path = path or os.environ.get(AUTOTUNE_CACHE_ENV)
    if not path:
        return 0
    try:
        with open(path) as f:
            payload = json.load(f)
        if payload.get("version") != _SIDECAR_VERSION:
            raise ValueError(
                f"sidecar version {payload.get('version')!r} != "
                f"{_SIDECAR_VERSION} (entry semantics may differ)")
        entries = payload["entries"]
        if not isinstance(entries, list):
            raise TypeError("entries must be a list")
    except (OSError, ValueError, KeyError, TypeError, AttributeError) as e:
        if os.path.exists(path):
            warnings.warn(f"ignoring unreadable autotune sidecar {path}: {e}")
        return 0
    return merge_autotune_entries(entries)


def _autoload_sidecar() -> None:
    """Load ``$REPRO_AUTOTUNE_CACHE`` once, lazily, before the first
    dispatch decision (import time would race with env setup in tests)."""
    global _sidecar_loaded
    if _sidecar_loaded:
        return
    _sidecar_loaded = True
    if os.environ.get(AUTOTUNE_CACHE_ENV):
        load_autotune_cache()


class _BenchChunk(NamedTuple):
    """Frontier-shaped chunk for autotune measurement (the kernel builders
    are generic over any assign/factor/valid/orig/lo/hi NamedTuple, so the
    registry does not need to import ``core.frontier``)."""

    assign: jnp.ndarray
    factor: jnp.ndarray
    valid: jnp.ndarray
    orig: jnp.ndarray
    lo: jnp.ndarray
    hi: jnp.ndarray


def _measure_chunk(spec: ExpandSpec, sizes: Sequence[int],
                   cap: int) -> _BenchChunk:
    """A synthetic chunk representative enough to time both paths: the
    first quarter of the rows valid, each spanning its atoms' full tries."""
    C, m, n = cap, spec.n_atoms, spec.n_vars
    n_valid = max(1, C // 4)
    factor_dtype = jnp.int64 if spec.x64 else jnp.int32
    return _BenchChunk(
        assign=jnp.zeros((C, n), jnp.int32),
        factor=jnp.ones((C,), factor_dtype),
        valid=jnp.asarray(np.arange(C) < n_valid),
        orig=jnp.zeros((C,), jnp.int32),
        lo=jnp.zeros((C, m), jnp.int32),
        hi=jnp.tile(jnp.asarray(list(sizes), jnp.int32)[None, :], (C, 1)))


def _measure_fold_args(spec: FoldSpec, cap: int) -> tuple:
    """Synthetic inputs for timing one FOLD step: a quarter-valid parent
    chunk plus (per arity) a sorted exit chunk and a one-row-per-hit slab
    — the same shapes the executor feeds, minus the join semantics."""
    C, m, n, w = cap, spec.n_atoms, spec.n_vars, spec.width
    factor_dtype = jnp.int64 if spec.x64 else jnp.int32
    i32 = jnp.int32
    ar = np.arange(C)
    P = _BenchChunk(
        assign=jnp.zeros((C, n), i32),
        factor=jnp.ones((C,), factor_dtype),
        valid=jnp.asarray(ar < max(1, C // 4)),
        orig=jnp.zeros((C,), i32),
        lo=jnp.zeros((C, m), i32),
        hi=jnp.ones((C, m), i32))
    args: list = [P]
    if spec.with_replay:
        E = P._replace(orig=jnp.asarray(ar, i32),
                       valid=jnp.asarray(ar < max(1, C // 4)))
        args += [jnp.asarray(ar < max(1, C // 8)),       # active
                 jnp.asarray(ar % max(1, C // 4), i32),  # rep_of_row
                 E]
    if spec.with_splice:
        args += [jnp.asarray(ar < max(1, C // 8)),  # hit
                 jnp.asarray(ar, i32),              # poff
                 jnp.ones((C,), i32),               # plen
                 jnp.zeros((C + 1, w), i32)]        # slab
    return tuple(args)


def _measure_emit_args(spec: EmitSpec, cap: int) -> tuple:
    C, n = cap, spec.n_vars
    return (jnp.zeros((C, n), jnp.int32),
            jnp.asarray(np.arange(C) < max(1, C // 4)))


def _time_fn(fn: Callable, args: tuple, reps: int = 2) -> float:
    jax.block_until_ready(fn(*args))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _compile_target():
    """The device the fused kernels are compiled for: the default one."""
    return jax.devices()[0]


def _refusal(spec, build_fused: Callable,
             args: Callable[[], tuple]) -> Optional[str]:
    """Compile the fused kernel at ``spec``'s real shapes (``args`` builds
    example inputs; only their shapes are used) for the target device.
    Returns ``None`` if it compiles, else the compiler's message, which
    is also recorded in :func:`failures`.  Cached per (spec, platform)."""
    dev = _compile_target()
    key = (spec, dev.platform)
    if key not in _COMPILES:
        on_dev = SingleDeviceSharding(dev)
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_dev),
            jax.eval_shape(args))
        try:
            jax.jit(build_fused()).lower(*shapes).compile()
            _COMPILES[key] = None
        except Exception as e:  # the compiler's refusal, kept verbatim
            _COMPILES[key] = f"{type(e).__name__}: {e}"
    return _COMPILES[key]


def _select(knob: str, modes: tuple, spec, mode: str,
            platform: Optional[str], measure: Optional[bool],
            builders: Optional[Dict[str, Callable[[], Callable]]],
            bench_args: Callable[[], tuple],
            compile_args: Callable[[], tuple]) -> str:
    """The shared mode→impl resolution (see :func:`select_expand`).

    ``knob`` names the engine knob in error messages; ``bench_args`` is a
    thunk building the measurement inputs (only called when a measurement
    actually runs); ``compile_args`` builds inputs of the spec's real
    shapes for the availability compile."""
    if mode not in modes:
        raise ValueError(f"{knob} must be one of {modes}, got {mode!r}")
    platform = platform or jax.default_backend()
    if mode != "auto":
        return mode
    _autoload_sidecar()  # a persisted measurement beats re-measuring
    key = (spec, platform)
    if key in _AUTOTUNE:
        return _AUTOTUNE[key]
    accel = platform in ("tpu", "gpu")
    do_measure = accel if measure is None else measure
    if builders is not None and (accel or do_measure):
        why = _refusal(spec, builders["pallas"], compile_args)
        if why is not None:
            warnings.warn(f"{knob}: fused kernel refused by the compiler "
                          f"for {spec}: {why}; falling back to the XLA path")
            _AUTOTUNE[key] = "xla"
            return "xla"
    if not do_measure or builders is None:
        # CPU default: the XLA chain; interpret-mode Pallas is a
        # conformance vehicle, not a perf path
        # heuristic, not measured: cached in-process only (persisting it
        # would pre-empt a future measure=True run with a guess)
        choice = "pallas" if accel else "xla"
        _AUTOTUNE[key] = choice
        return choice
    args = bench_args()
    timings = {name: _time_fn(builders[name](), args)
               for name in ("pallas", "xla")}
    choice = min(timings, key=timings.get)
    _AUTOTUNE[key] = choice
    _MEASURED.add(key)
    _maybe_writethrough()
    return choice


def _expand_args(spec: ExpandSpec, sizes: Optional[Sequence[int]],
                 cap: int) -> tuple:
    return (_measure_chunk(spec, sizes or [1] * spec.n_atoms, cap),)


def select_expand(spec: ExpandSpec, mode: str = "auto",
                  platform: Optional[str] = None,
                  measure: Optional[bool] = None,
                  builders: Optional[Dict[str, Callable[[], Callable]]] = None,
                  sizes: Optional[Sequence[int]] = None) -> str:
    """Resolve ``mode`` to a concrete impl name for ``spec``.

    ``builders`` maps impl name to a zero-arg builder (needed only when a
    measurement actually runs); ``measure`` overrides the platform rule
    (None → measure on tpu/gpu only)."""
    cap = min(spec.capacity, 1 << 9)
    return _select(
        "expand_kernel", EXPAND_MODES, spec, mode, platform, measure,
        builders, lambda: _expand_args(spec, sizes, cap),
        lambda: _expand_args(spec, sizes, spec.capacity))


def select_fold(spec: FoldSpec, mode: str = "auto",
                platform: Optional[str] = None,
                measure: Optional[bool] = None,
                builders: Optional[Dict[str, Callable[[], Callable]]] = None,
                ) -> str:
    """FOLD twin of :func:`select_expand` (``fold_kernel`` knob)."""
    cap = min(spec.capacity, 1 << 9)
    return _select("fold_kernel", KERNEL_MODES, spec, mode, platform,
                   measure, builders, lambda: _measure_fold_args(spec, cap),
                   lambda: _measure_fold_args(spec, spec.capacity))


def select_emit(spec: EmitSpec, mode: str = "auto",
                platform: Optional[str] = None,
                measure: Optional[bool] = None,
                builders: Optional[Dict[str, Callable[[], Callable]]] = None,
                ) -> str:
    """EMIT twin of :func:`select_expand` (``emit_kernel`` knob)."""
    cap = min(spec.capacity, 1 << 9)
    return _select("emit_kernel", KERNEL_MODES, spec, mode, platform,
                   measure, builders, lambda: _measure_emit_args(spec, cap),
                   lambda: _measure_emit_args(spec, spec.capacity))


def _maybe_writethrough() -> None:
    """Persist after every new *measured* decision when the sidecar env
    var is set — the whole point is surviving the process."""
    if os.environ.get(AUTOTUNE_CACHE_ENV):
        try:
            save_autotune_cache()
        except OSError as e:  # pragma: no cover - fs-specific
            warnings.warn(f"could not persist autotune cache: {e}")


def expand_fn(spec: ExpandSpec, *, mode: str = "auto", impl: str = "bsearch",
              config=None, measure: Optional[bool] = None,
              d: int, g_ai: int, other_ais: Tuple[int, ...],
              g_col: jnp.ndarray, g_rs: jnp.ndarray, g_rank: jnp.ndarray,
              other_cols: Tuple[jnp.ndarray, ...], n_rows_g: int,
              sizes: Optional[Sequence[int]] = None,
              ) -> Tuple[Callable, str]:
    """Build the EXPAND(d) step for ``spec``: returns ``(fn, chosen)``
    where ``fn(F) -> (F', needed)`` and ``chosen`` names the impl that
    will actually run.  ``impl`` is the bounded-search flavor used by the
    XLA chain; ``config`` is a :class:`~.expand.fused.FusedExpandConfig`
    for the Pallas path.  ``g_rank``, the guard level's run-rank table,
    goes to the XLA chain alone: the fused kernel searches ``g_rs``."""
    from .expand import fused as _fused, xla as _xla  # lazy: no import cycle

    def build_xla():
        return _xla.build(d=d, g_ai=g_ai, other_ais=other_ais,
                          n_rows_g=n_rows_g, impl=impl,
                          g_col=g_col, g_rs=g_rs, g_rank=g_rank,
                          other_cols=other_cols)

    def build_fused():
        return _fused.build(d=d, g_ai=g_ai, other_ais=other_ais,
                            n_rows_g=n_rows_g, g_col=g_col, g_rs=g_rs,
                            other_cols=other_cols, config=config)

    # statically-empty expansions (no guard runs, or an empty participating
    # relation makes every membership test fail): the XLA chain already
    # short-circuits these shapes — never worth a kernel launch
    degenerate = (n_rows_g == 0 or g_rs.shape[0] == 0
                  or any(c.shape[0] == 0 for c in other_cols))
    if degenerate:
        return build_xla(), "xla"
    chosen = select_expand(
        spec, mode=mode, measure=measure, sizes=sizes,
        builders={"pallas": build_fused, "xla": build_xla})
    return _resolve_built(
        "EXPAND", spec, chosen, build_fused, build_xla,
        lambda: _expand_args(spec, sizes, spec.capacity))


def _resolve_built(op: str, spec, chosen: str, build_fused: Callable,
                   build_xla: Callable, compile_args: Callable[[], tuple],
                   ) -> Tuple[Callable, str]:
    """Build the chosen impl.  A Pallas choice is compiled first at the
    spec's real shapes (:func:`_refusal`, cached), so a kernel the
    compiler refuses raises here with the compiler's message instead of
    failing at the first call mid-query."""
    if chosen == "pallas":
        why = _refusal(spec, build_fused, compile_args)
        if why is not None:
            raise RuntimeError(f"fused {op} refused by the compiler for "
                               f"{spec}: {why}")
        return build_fused(), "pallas"
    return build_xla(), "xla"


def fold_fn(spec: FoldSpec, *, mode: str = "auto", config=None,
            measure: Optional[bool] = None, d0: int, d1: int,
            ) -> Tuple[Callable, str]:
    """Build the FOLD_CHILD close step for ``spec``: ``(fn, chosen)``.

    The built ``fn`` has one of three arities keyed by the spec's
    ``with_replay``/``with_splice`` flags (see ``kernels/fold/xla.build``)
    and always returns ``(cont, stats)`` with ``stats`` the int64 triple
    ``[replay pairs, splice rows, min-capped valid total]``.  The fused
    path additionally REQUIRES the exit chunk to be valid-prefix compacted
    with nondecreasing ``orig`` — the executor's sorted-exits invariant —
    which the XLA chain does not need; callers route unsorted folds
    through ``mode="xla"``.  ``config`` is a
    :class:`~.fold.fused.FusedFoldConfig` for the Pallas path."""
    from .fold import fused as _fused, xla as _xla  # lazy: no import cycle

    def build_xla():
        return _xla.build(d0=d0, d1=d1, with_replay=spec.with_replay,
                          with_splice=spec.with_splice)

    def build_fused():
        return _fused.build(d0=d0, d1=d1, with_replay=spec.with_replay,
                            with_splice=spec.with_splice, config=config)

    chosen = select_fold(spec, mode=mode, measure=measure,
                         builders={"pallas": build_fused, "xla": build_xla})
    return _resolve_built(
        "FOLD", spec, chosen, build_fused, build_xla,
        lambda: _measure_fold_args(spec, spec.capacity))


def emit_fn(spec: EmitSpec, *, mode: str = "auto", config=None,
            measure: Optional[bool] = None) -> Tuple[Callable, str]:
    """Build the EMIT pack for ``spec``: ``(fn, chosen)`` where
    ``fn(assign, valid) -> (packed, k)`` stably moves the valid rows to
    the front (rows past ``k`` are garbage).  ``config`` is a
    :class:`~.emit.fused.FusedEmitConfig` for the Pallas path."""
    from .emit import fused as _fused, xla as _xla  # lazy: no import cycle

    def build_xla():
        return _xla.build()

    def build_fused():
        return _fused.build(config=config)

    chosen = select_emit(spec, mode=mode, measure=measure,
                         builders={"pallas": build_fused, "xla": build_xla})
    return _resolve_built(
        "EMIT", spec, chosen, build_fused, build_xla,
        lambda: _measure_emit_args(spec, spec.capacity))


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------

# primitives that are metadata/layout-only — XLA folds them into their
# producer/consumer, so they are not separately-materialized device ops
_METADATA_PRIMS = frozenset({
    "slice", "squeeze", "reshape", "broadcast_in_dim",
    "convert_element_type", "transpose", "copy"})
_CALL_PRIMS = ("jit", "closed_call", "remat2", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr")


def device_op_count(fn: Callable, *args) -> int:
    """Number of non-metadata primitive applications ``fn`` lowers to —
    the per-EXPAND "device op" figure in ``bench_expand_kernel``.  Call
    wrappers (pjit etc.) are descended into; a ``pallas_call`` counts as
    ONE op (its inner jaxpr is a single fused launch)."""

    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _CALL_PRIMS:
                sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                if sub is not None:
                    n += walk(getattr(sub, "jaxpr", sub))
                    continue
            if name in _METADATA_PRIMS:
                continue
            n += 1
        return n

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)
