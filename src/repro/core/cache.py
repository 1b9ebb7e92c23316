"""Pluggable tier-2 device cache for the vectorized CLFTJ (DESIGN.md §2.3).

The paper's central knob is *flexibility*: "our solution balances memory
usage and repeated computation" by choosing how much cache to keep and what
to admit/evict (§3.4, Fig 10).  The frontier engine realizes the cache as
device arrays updated with functional scatter/gather, so a "policy" here is
a pair of jitted ops (probe, insert) over a fixed table layout:

* ``direct``    — 1-way direct-mapped table: ``slot = hash(key) % S``;
  collisions overwrite unconditionally (hardware-style, zero metadata).
* ``setassoc``  — N-way set-associative with LRU within each set: a key may
  live in any of ``assoc`` ways of its set; the victim is the invalid way
  if one exists, else the least-recently-touched way.  Conflict misses on
  skewed key distributions drop sharply vs ``direct`` at equal slot count.
* ``costaware`` — set-associative layout, but the victim is the *cheapest*
  resident entry and admission is refused when the incumbent is more
  valuable than the candidate.  Cost is the cached subtree count — a proxy
  for the recomputation a future hit would avoid (big subtrees are the
  entries worth pinning).

All policies are *caches of exact results*: correctness never depends on
what is resident, only speed does (the paper's optionality property), so
batched scatter collisions may drop arbitrary writers without harm.

``CacheManager`` owns one ``DeviceCache`` per TD node and the **dynamic
sizing controller** (the Fig 10 size knob made adaptive): between subtree
launches it grows a table whose misses look like conflict pressure (low
hit rate at high occupancy) while total slots stay within ``budget``, and
shrinks tables whose occupancy stays low (memory handed back).  Resizing
rehashes resident entries into the new table with one batched insert;
entries lost to rehash collisions are a performance non-event by the
optionality property above.

**Row-block payloads (DESIGN.md §2.6).**  With ``cache_payloads=True`` a
table additionally stores, per way, an ``(offset, length)`` pointer into a
per-node *slab arena* of factorized row blocks: the subtree-column
assignments of one adhesion key's complete subtree result (paper §3.4's
factorized intermediates).  Evaluation-mode hits replay the block instead
of re-expanding the bag.  The slab is a bump-pointer arena — blocks whose
keys are evicted become dead space until the arena wraps, at which point
every payload is invalidated in one epoch *flush* (keys and counts stay
resident for count mode).  A payload-bearing hit requires ``pay_len >= 0``;
the metadata planes ride :func:`_insert`'s election (the ``pay`` pytree)
on every insert, with count-mode inserts writing the ``-1`` sentinel, so
an evicting write can never leave a stale block reachable under a new key.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .hostsync import device_get

_MIX = np.int64(-7046029254386353131)  # 0x9E3779B97F4A7C15 as signed

POLICIES = ("direct", "setassoc", "costaware")


def _hash_sets(keys: jnp.ndarray, n_sets: int) -> jnp.ndarray:
    h = keys * _MIX
    h = h ^ (h >> 29)
    return jnp.abs(h) % n_sets


@dataclass(frozen=True)
class CacheConfig:
    """Tier-2 cache knobs (engine-facing; see DESIGN.md §2.3).

    * ``policy``: "direct" | "setassoc" | "costaware".
    * ``slots``: initial entries per node table (0 disables tier 2).
    * ``assoc``: ways per set (ignored for "direct", which is 1-way).
    * ``dynamic``: enable the sizing controller.
    * ``budget``: max total slots summed over all node tables (None = only
      bounded by ``max_slots`` per table); also the dynamic controller's
      growth headroom.  Floor: every cached node keeps at least one set,
      so with budget < nodes × ways the total can exceed it by that floor.
    * ``min_slots``/``max_slots``: per-table resize clamps.
    * ``resize_interval``: subtree launches between controller decisions.
    * ``grow_below_hit_rate``: grow when window hit-rate is below this and
      the table looks conflict-bound (occupancy > 1/2).
    * ``shrink_below_occupancy``: shrink when occupancy stays under this.
    * ``enabled_nodes``: restrict caching to these TD nodes (None = all).
    * ``cache_payloads``: additionally store factorized row *blocks* per
      entry (evaluation-mode replay-on-hit, DESIGN.md §2.6).
    * ``payload_rows``: per-node slab arena size in rows (the memory half
      of the paper's size↔recomputation trade-off for evaluation).
    * ``payload_throttle_probes`` / ``payload_throttle_hit_rate``: the
      admission throttle (§3.4's admission flexibility applied to
      blocks): after that many evaluation probes a table whose payload
      hit rate is still below the floor stops *storing* new blocks —
      workloads whose adhesion keys never recur shouldn't pay the
      arena-write overhead.  Splicing of already-stored blocks, and
      storing again if the rate recovers, are unaffected.
    * ``payload_probation``: while throttled, still store on every Nth
      throttled fold (0 disables) — with nothing resident the hit rate
      could never recover on a workload shift.
    """

    policy: str = "direct"
    slots: int = 1 << 16
    assoc: int = 4
    dynamic: bool = False
    budget: Optional[int] = None
    min_slots: int = 1 << 8
    max_slots: int = 1 << 22
    resize_interval: int = 8
    grow_below_hit_rate: float = 0.5
    shrink_below_occupancy: float = 0.125
    enabled_nodes: Optional[frozenset] = None
    cache_payloads: bool = False
    payload_rows: int = 1 << 15
    payload_throttle_probes: int = 1 << 15
    payload_throttle_hit_rate: float = 0.01
    payload_probation: int = 16

    def __post_init__(self):
        if self.policy not in POLICIES:
            raise ValueError(f"unknown cache policy {self.policy!r}; "
                             f"expected one of {POLICIES}")
        if self.assoc < 1:
            raise ValueError("assoc must be >= 1")
        if self.cache_payloads and self.payload_rows < 1:
            raise ValueError("cache_payloads needs payload_rows >= 1")

    @property
    def ways(self) -> int:
        return 1 if self.policy == "direct" else int(self.assoc)

    def initial_slots(self) -> int:
        s = int(self.slots)
        if self.budget is not None:
            s = min(s, int(self.budget))
        if s <= 0:
            return 0
        # whole sets only; a positive request below one set rounds UP to a
        # single set rather than silently disabling the cache
        w = self.ways
        return max(w, (s // w) * w)


# ---------------------------------------------------------------------------
# Jitted table ops.  Tables are (S, W) arrays: S sets, W ways.
# ---------------------------------------------------------------------------

@jax.jit
def _probe(tkeys, tvals, tused, tstamp, keys, active, tick):
    """Batched lookup; returns (hit, vals, stamp') — stamp' records the LRU
    touch of every hit way (scatter-max, so duplicate rows are harmless)."""
    n_sets = tkeys.shape[0]
    sets = _hash_sets(keys, n_sets)
    match = tused[sets] & (tkeys[sets] == keys[:, None]) & active[:, None]
    hit = match.any(axis=1)
    way = jnp.argmax(match, axis=1)
    vals = jnp.where(hit, tvals[sets, way], 0)
    stamp = tstamp.at[sets, way].max(jnp.where(hit, tick, -1))
    return hit, vals, stamp


@functools.partial(jax.jit, static_argnames=("policy", "rounds"))
def _insert(tkeys, tvals, tused, tstamp, tcost,
            keys, vals, costs, active, tick, *, policy: str,
            rounds: int = 1, pay=None):
    """Batched fill.  Victim selection per policy.

    Each round elects exactly one writer per set (scatter-max of the row
    index — duplicate-index scatters must not carry the write mask, or a
    masked row's "keep old value" no-op can land after a real admit and
    clobber it) and writes through per-set *unique* indices.  ``rounds``
    (≈ the way count) re-reads the updated table so batch collisions retry
    into the remaining ways instead of being dropped — without it an N-way
    table admits N× fewer entries per launch than a direct-mapped one of
    equal size.

    ``pay`` (``None`` or ``(tpoff, tplen, poff, plen)``, resolved at trace
    time) carries the payload metadata planes through the same election.
    Two payload-specific rules:

    * every admitted write also writes ``(poff, plen)`` — count-mode
      inserts pass the ``plen = -1`` sentinel, so an eviction can never
      leave the victim's block reachable under the new key;
    * a resident key only blocks re-admission when it already carries a
      payload (or the candidate has none): a payload-bearing candidate
      refreshes its resident way in place, so evaluation mode can attach
      blocks to keys first seen by ``count()``.
    """
    n_sets = tkeys.shape[0]
    C = keys.shape[0]
    rows = jnp.arange(C, dtype=jnp.int32)
    sets = jnp.where(active, _hash_sets(keys, n_sets), 0)
    remaining = active
    if pay is not None:
        tpoff, tplen, poff, plen = pay
        cand_pay = plen >= 0
    n_admit = jnp.int32(0)
    n_evict = jnp.int32(0)
    for _ in range(max(1, rounds)):
        way_used = tused[sets]                       # (C, W)
        resident = way_used & (tkeys[sets] == keys[:, None])
        if pay is not None:
            blocking = resident & ((tplen[sets] >= 0) | ~cand_pay[:, None])
        else:
            blocking = resident                      # dup already admitted
        rem = remaining & ~blocking.any(axis=1)
        any_free = ~way_used.all(axis=1)
        free_way = jnp.argmin(way_used, axis=1)      # first invalid way
        if policy == "costaware":
            contested = jnp.argmin(jnp.where(way_used, tcost[sets],
                                             jnp.int64(2 ** 62)), axis=1)
        else:  # direct (W=1 → way 0) and setassoc both take the LRU way
            contested = jnp.argmin(jnp.where(way_used, tstamp[sets],
                                             jnp.int32(2 ** 31 - 1)), axis=1)
        victim = jnp.where(any_free, free_way, contested)
        has_res = jnp.zeros((C,), bool)
        if pay is not None:
            # a payload-less resident is refreshed in its own way
            has_res = resident.any(axis=1)
            victim = jnp.where(has_res, jnp.argmax(resident, axis=1),
                               victim)
        admit = rem
        if policy == "costaware":
            incumbent = tcost[sets, victim]
            admit = admit & (has_res | any_free | (costs >= incumbent))
        # elect one admitted writer per set (highest row index)
        winner = jnp.full((n_sets,), -1, jnp.int32).at[sets].max(
            jnp.where(admit, rows, -1))
        src = jnp.clip(winner, 0, C - 1)             # (S,) winning row
        do_w = winner >= 0
        sel = (jnp.arange(n_sets), victim[src])      # unique per set
        tkeys = tkeys.at[sel].set(jnp.where(do_w, keys[src], tkeys[sel]))
        tvals = tvals.at[sel].set(jnp.where(do_w, vals[src], tvals[sel]))
        tcost = tcost.at[sel].set(jnp.where(do_w, costs[src], tcost[sel]))
        tstamp = tstamp.at[sel].set(jnp.where(do_w, tick, tstamp[sel]))
        if pay is not None:
            tpoff = tpoff.at[sel].set(jnp.where(do_w, poff[src],
                                                tpoff[sel]))
            tplen = tplen.at[sel].set(jnp.where(do_w, plen[src],
                                                tplen[sel]))
        tused = tused.at[sel].set(tused[sel] | do_w)
        won = admit & (winner[sets] == rows)
        n_admit = n_admit + jnp.sum(won.astype(jnp.int32))
        n_evict = n_evict + jnp.sum(
            (won & ~any_free & ~has_res).astype(jnp.int32))
        remaining = rem & ~won
    if pay is not None:
        return (tkeys, tvals, tused, tstamp, tcost, tpoff, tplen,
                n_admit, n_evict)
    return tkeys, tvals, tused, tstamp, tcost, n_admit, n_evict


@jax.jit
def _probe_payload(tkeys, tused, tstamp, tpoff, tplen, keys, active, tick):
    """Evaluation-mode lookup: a hit additionally requires a resident row
    block (``pay_len >= 0``) — entries inserted count-only are misses here.
    Returns (hit, poff, plen, stamp')."""
    n_sets = tkeys.shape[0]
    sets = _hash_sets(keys, n_sets)
    match = (tused[sets] & (tkeys[sets] == keys[:, None])
             & (tplen[sets] >= 0) & active[:, None])
    hit = match.any(axis=1)
    way = jnp.argmax(match, axis=1)
    poff = jnp.where(hit, tpoff[sets, way], 0)
    plen = jnp.where(hit, tplen[sets, way], 0)
    stamp = tstamp.at[sets, way].max(jnp.where(hit, tick, -1))
    return hit, poff, plen, stamp


# ---------------------------------------------------------------------------

@dataclass
class DeviceCache:
    """One node's table: functional arrays + deferred stats/controller.

    Stats are accumulated *on device* (the ``_acc_*`` fields hold lazy
    scalars) so probing/inserting never forces a host sync on the hot
    path; ``hits``/``misses``/... properties and :meth:`stats` fetch them
    once, through the :mod:`hostsync` funnel, when actually read."""

    config: CacheConfig
    keys: jnp.ndarray    # (S, W) int64
    vals: jnp.ndarray    # (S, W) int64
    used: jnp.ndarray    # (S, W) bool
    stamp: jnp.ndarray   # (S, W) int32  — LRU clock (ticks)
    cost: jnp.ndarray    # (S, W) int64  — recomputation-cost proxy
    # payload region (None unless config.cache_payloads) — DESIGN.md §2.6
    pay_off: Optional[jnp.ndarray] = None  # (S, W) int32 — slab offset
    pay_len: Optional[jnp.ndarray] = None  # (S, W) int32 — block rows; -1=none
    slab: Optional[jnp.ndarray] = None     # (payload_rows+1, width) int32;
    #                                        last row = masked-write scratch
    slab_bump: int = 0                     # host-side arena bump pointer
    payload_flushes: int = 0
    payload_skips: int = 0                 # eligible blocks not stored
    payload_throttled: int = 0             # folds skipped by the throttle
    # host-visible evaluation-probe counters feeding the store throttle
    # (maintained by the executor from its per-fold planning fetch — no
    # extra device sync)
    eval_probes_h: int = 0
    eval_hits_h: int = 0
    tick: int = 0
    resizes: int = 0
    window_launches: int = 0
    # device-side accumulators (int until the first op touches them)
    _acc_hits: object = 0
    _acc_misses: object = 0
    _acc_probes: object = 0
    _acc_inserts: object = 0
    _acc_evictions: object = 0
    _acc_payload_hits: object = 0
    # sliding window consumed by the sizing controller
    _acc_window_hits: object = 0
    _acc_window_probes: object = 0

    @property
    def hits(self) -> int:
        return int(device_get(self._acc_hits, "cache-stat"))

    @property
    def misses(self) -> int:
        return int(device_get(self._acc_misses, "cache-stat"))

    @property
    def probes(self) -> int:
        return int(device_get(self._acc_probes, "cache-stat"))

    @property
    def inserts(self) -> int:
        return int(device_get(self._acc_inserts, "cache-stat"))

    @property
    def evictions(self) -> int:
        return int(device_get(self._acc_evictions, "cache-stat"))

    @property
    def payload_hits(self) -> int:
        return int(device_get(self._acc_payload_hits, "cache-stat"))

    @staticmethod
    def create(config: CacheConfig,
               slots: Optional[int] = None) -> "DeviceCache":
        n = config.initial_slots() if slots is None else int(slots)
        w = config.ways
        s = max(1, n // w)
        pay_off = pay_len = None
        if config.cache_payloads:
            pay_off = jnp.zeros((s, w), jnp.int32)
            pay_len = jnp.full((s, w), -1, jnp.int32)
        return DeviceCache(
            config=config,
            keys=jnp.zeros((s, w), jnp.int64),
            vals=jnp.zeros((s, w), jnp.int64),
            used=jnp.zeros((s, w), bool),
            stamp=jnp.zeros((s, w), jnp.int32),
            cost=jnp.zeros((s, w), jnp.int64),
            pay_off=pay_off, pay_len=pay_len)

    # -- capacity ------------------------------------------------------
    @property
    def n_slots(self) -> int:
        return int(self.keys.shape[0] * self.keys.shape[1])

    def occupancy(self) -> int:
        return int(device_get(jnp.sum(self.used), "cache-occupancy"))

    # -- ops -----------------------------------------------------------
    def probe(self, qkeys: jnp.ndarray,
              active: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        self.tick += 1
        hit, vals, stamp = _probe(self.keys, self.vals, self.used,
                                  self.stamp, qkeys, active,
                                  jnp.int32(self.tick))
        self.stamp = stamp
        # device-side accounting: no host sync on the probe path
        n_active = jnp.sum(active.astype(jnp.int64))
        n_hit = jnp.sum(hit.astype(jnp.int64))
        self._acc_probes = self._acc_probes + n_active
        self._acc_hits = self._acc_hits + n_hit
        self._acc_misses = self._acc_misses + (n_active - n_hit)
        self._acc_window_probes = self._acc_window_probes + n_active
        self._acc_window_hits = self._acc_window_hits + n_hit
        return hit, vals

    def probe_payload(self, qkeys: jnp.ndarray, active: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Evaluation-mode lookup: hit only on entries with a resident row
        block; returns (hit, slab offset, block length)."""
        assert self.pay_off is not None, "cache_payloads is off"
        self.tick += 1
        hit, poff, plen, stamp = _probe_payload(
            self.keys, self.used, self.stamp, self.pay_off, self.pay_len,
            qkeys, active, jnp.int32(self.tick))
        self.stamp = stamp
        n_active = jnp.sum(active.astype(jnp.int64))
        n_hit = jnp.sum(hit.astype(jnp.int64))
        self._acc_probes = self._acc_probes + n_active
        self._acc_hits = self._acc_hits + n_hit
        self._acc_misses = self._acc_misses + (n_active - n_hit)
        self._acc_payload_hits = self._acc_payload_hits + n_hit
        self._acc_window_probes = self._acc_window_probes + n_active
        self._acc_window_hits = self._acc_window_hits + n_hit
        return hit, poff, plen

    def insert(self, qkeys: jnp.ndarray, vals: jnp.ndarray,
               active: jnp.ndarray,
               costs: Optional[jnp.ndarray] = None,
               poff: Optional[jnp.ndarray] = None,
               plen: Optional[jnp.ndarray] = None) -> None:
        self.tick += 1
        if costs is None:  # default proxy: the count itself (clipped >= 1)
            costs = jnp.maximum(vals, 1)
        if self.pay_off is not None:
            # payload tables carry the metadata planes through EVERY
            # insert so evicting writes always overwrite them (count
            # inserts carry the -1 sentinel — never a stale block)
            C = qkeys.shape[0]
            if poff is None:
                poff = jnp.zeros((C,), jnp.int32)
                plen = jnp.full((C,), -1, jnp.int32)
            out = _insert(
                self.keys, self.vals, self.used, self.stamp, self.cost,
                qkeys, vals, costs.astype(jnp.int64), active,
                jnp.int32(self.tick), policy=self.config.policy,
                rounds=min(self.config.ways, 8),
                pay=(self.pay_off, self.pay_len, poff, plen))
            (self.keys, self.vals, self.used, self.stamp, self.cost,
             self.pay_off, self.pay_len, n_ins, n_evict) = out
        else:
            out = _insert(self.keys, self.vals, self.used, self.stamp,
                          self.cost, qkeys, vals, costs.astype(jnp.int64),
                          active, jnp.int32(self.tick),
                          policy=self.config.policy,
                          rounds=min(self.config.ways, 8))
            (self.keys, self.vals, self.used, self.stamp, self.cost,
             n_ins, n_evict) = out
        self._acc_inserts = self._acc_inserts + n_ins
        self._acc_evictions = self._acc_evictions + n_evict
        self.window_launches += 1

    # -- payload slab arena (DESIGN.md §2.6) ---------------------------
    def ensure_slab(self, width: int) -> None:
        """Lazily allocate the block arena: ``payload_rows`` rows of the
        node's subtree width, plus one scratch row for masked writes."""
        if self.slab is None:
            self.slab = jnp.zeros((int(self.config.payload_rows) + 1, width),
                                  jnp.int32)
        elif self.slab.shape[1] != width:
            raise ValueError(
                f"slab width {self.slab.shape[1]} != subtree width {width}")

    def alloc_blocks(self, lens: np.ndarray, active: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side bump allocation of one batch of variable-length blocks.

        ``lens[i]`` rows are requested for candidate row ``i`` (``active``
        masks real candidates).  Blocks larger than the whole arena are
        refused outright (they could never fit, and must not trigger a
        pointless flush or veto later candidates).  If the rest of the
        batch does not fit the remaining arena, the arena is *flushed*
        first (every payload invalidated — keys/counts stay resident;
        after a flush at least the first candidate is guaranteed to
        admit); candidates still beyond capacity are refused prefix-wise.
        Returns ``(offsets, admitted)`` (numpy, host) — refusals only
        cost future recomputation.
        """
        cap = int(self.config.payload_rows)
        lens = np.where(active, np.asarray(lens, np.int64), 0)
        lens = np.where(lens <= cap, lens, 0)  # can never fit: refuse
        total = int(lens.sum())
        if total > cap - self.slab_bump and self.slab_bump > 0 and total:
            self.flush_payloads()
        cum = np.cumsum(lens)
        admit = (lens > 0) & (cum <= cap - self.slab_bump)
        offs = np.where(admit, self.slab_bump + cum - lens, 0).astype(
            np.int32)
        if admit.any():
            self.slab_bump += int(lens[admit].sum())
        return offs, admit

    def note_eval_probes(self, probes: int, hits: int) -> None:
        """Feed the store throttle (host counters, no device sync).  The
        counters decay exponentially past 4× the probe floor — a sliding
        window, so a miss-heavy prefix cannot latch the throttle against
        a workload that later starts recurring."""
        self.eval_probes_h += int(probes)
        self.eval_hits_h += int(hits)
        if self.eval_probes_h > 4 * self.config.payload_throttle_probes:
            self.eval_probes_h //= 2
            self.eval_hits_h //= 2

    def store_throttled(self) -> bool:
        """Admission throttle: True once this table has seen many
        evaluation probes at a negligible payload hit rate — storing more
        blocks is then pure overhead (keys don't recur here).  The rate
        is re-checked every call over the decayed window, and the
        executor still stores on an occasional probation fold, so a
        workload shift re-opens storage."""
        cfg = self.config
        return (self.eval_probes_h >= cfg.payload_throttle_probes
                and self.eval_hits_h
                < cfg.payload_throttle_hit_rate * self.eval_probes_h)

    def flush_payloads(self) -> None:
        """Epoch reset of the arena: every payload pointer is invalidated
        (keys and counts stay — count-mode hits are unaffected) and the
        bump pointer rewinds.  Reclaims blocks orphaned by key eviction."""
        if self.pay_len is not None:
            self.pay_len = jnp.full_like(self.pay_len, -1)
        self.slab_bump = 0
        self.payload_flushes += 1

    # -- dynamic sizing (the paper's flexible-cache knob) --------------
    def maybe_resize(self, headroom: Optional[int] = None) -> int:
        """Controller step; returns the slot delta (0 = no change).

        Grow ×2 when the window hit-rate is low *and* the table is mostly
        full (conflict pressure — more slots can actually help); shrink ÷2
        when occupancy stays below the configured floor (memory handed
        back).  ``headroom`` caps growth (global budget minus slots already
        spent elsewhere)."""
        cfg = self.config
        if not cfg.dynamic or self.window_launches < cfg.resize_interval:
            return 0
        probes, hits = (int(x) for x in device_get(
            (self._acc_window_probes, self._acc_window_hits),
            "cache-resize-window"))
        self._acc_window_hits = self._acc_window_probes = 0
        self.window_launches = 0
        if probes == 0:
            return 0
        hit_rate = hits / probes
        occ = self.occupancy() / max(1, self.n_slots)
        old = self.n_slots
        new = old
        if (hit_rate < cfg.grow_below_hit_rate and occ > 0.5
                and old * 2 <= cfg.max_slots):
            new = old * 2
            if headroom is not None:
                new = min(new, old + max(0, headroom))
        elif occ < cfg.shrink_below_occupancy and old // 2 >= cfg.min_slots:
            new = old // 2
        new = (new // cfg.ways) * cfg.ways
        if new <= 0 or new == old:
            return 0
        self._rehash(new)
        self.resizes += 1
        return self.n_slots - old

    def _rehash(self, new_slots: int) -> None:
        old_keys = self.keys.reshape(-1)
        old_vals = self.vals.reshape(-1)
        old_cost = self.cost.reshape(-1)
        old_used = self.used.reshape(-1)
        has_pay = self.pay_off is not None
        if has_pay:
            old_poff = self.pay_off.reshape(-1)
            old_plen = self.pay_len.reshape(-1)
        fresh = DeviceCache.create(self.config, new_slots)
        self.keys, self.vals, self.used, self.stamp, self.cost = (
            fresh.keys, fresh.vals, fresh.used, fresh.stamp, fresh.cost)
        self.pay_off, self.pay_len = fresh.pay_off, fresh.pay_len
        # the slab and its bump pointer survive a resize: offsets stored in
        # the re-inserted metadata still point at live arena rows
        if not bool(device_get(old_used.any(), "cache-rehash")):
            return
        # re-insert resident entries in one batched op; rehash collisions
        # drop entries, which only costs future recomputation (optionality)
        self.tick += 1
        if has_pay:
            out = _insert(
                self.keys, self.vals, self.used, self.stamp, self.cost,
                old_keys, old_vals, old_cost, old_used,
                jnp.int32(self.tick), policy=self.config.policy,
                rounds=min(self.config.ways, 8),
                pay=(self.pay_off, self.pay_len, old_poff, old_plen))
            (self.keys, self.vals, self.used, self.stamp, self.cost,
             self.pay_off, self.pay_len) = out[:7]
        else:
            out = _insert(self.keys, self.vals, self.used, self.stamp,
                          self.cost, old_keys, old_vals, old_cost, old_used,
                          jnp.int32(self.tick), policy=self.config.policy,
                          rounds=min(self.config.ways, 8))
            self.keys, self.vals, self.used, self.stamp, self.cost = out[:5]

    def accumulators(self) -> Dict[str, jnp.ndarray]:
        """The device-side counters that :meth:`stats` reports."""
        return {"hits": self._acc_hits, "misses": self._acc_misses,
                "probes": self._acc_probes, "inserts": self._acc_inserts,
                "evictions": self._acc_evictions,
                "payload_hits": self._acc_payload_hits,
                "occupancy": jnp.sum(self.used)}

    def stats(self, acc: Optional[Dict[str, Any]] = None
              ) -> Dict[str, int]:
        """Counters of this table.  ``acc`` holds the host values of
        :meth:`accumulators` where a caller fetched them with another
        sync; without it they are fetched here (``cache-stats``)."""
        if acc is None:
            acc = device_get(self.accumulators(), "cache-stats")
        out = {k: int(v) for k, v in acc.items()}
        out["resizes"] = self.resizes
        out["slots"] = self.n_slots
        out["payload_flushes"] = self.payload_flushes
        out["payload_skips"] = self.payload_skips
        out["payload_throttled"] = self.payload_throttled
        out["slab_rows"] = self.slab_bump
        return out

    # -- cross-process state (repro/serve snapshots; DESIGN.md §2.9) ---
    def export_state(self) -> Dict[str, object]:
        """Host copy of everything a fresh process needs to serve hits
        from this table: the key/count planes, the payload metadata +
        slab arena, and the host-side slab epoch (``slab_bump`` and
        ``payload_flushes``).  The epoch scalars are the part a naive
        array-only snapshot loses — without them a loader's allocator
        restarts at row 0 and overwrites resident blocks whose
        ``pay_off``/``pay_len`` still claim those rows (stale splices)."""
        arrays = {"keys": self.keys, "vals": self.vals, "used": self.used,
                  "stamp": self.stamp, "cost": self.cost}
        if self.pay_off is not None:
            arrays["pay_off"] = self.pay_off
            arrays["pay_len"] = self.pay_len
            if self.slab is not None:
                arrays["slab"] = self.slab
        host = device_get(arrays, "cache-export")
        state: Dict[str, object] = {k: np.asarray(v)
                                    for k, v in host.items()}
        state["slab_bump"] = int(self.slab_bump)
        state["payload_flushes"] = int(self.payload_flushes)
        state["tick"] = int(self.tick)
        return state

    def import_state(self, state: Dict[str, object]) -> str:
        """Adopt a previously exported table state.  Returns:

        * ``"ok"``      — keys/counts and (if configured) payloads resident;
        * ``"flushed"`` — keys/counts adopted but the payload region was
          cold-started because the snapshot's slab epoch is unusable
          (missing/mis-shaped slab, or a resident block outside
          ``[0, slab_bump]`` — the stale-splice hazard this method exists
          to close);
        * ``"rejected"`` — state malformed for this config; table unchanged.

        The loaded slot count may differ from ``config.slots`` (the writer
        may have resized); table ops derive their geometry from the array
        shapes, so the arrays are adopted wholesale."""
        try:
            keys = np.asarray(state["keys"], np.int64)
            vals = np.asarray(state["vals"], np.int64)
            used = np.asarray(state["used"], bool)
            stamp = np.asarray(state["stamp"], np.int32)
            cost = np.asarray(state["cost"], np.int64)
        except (KeyError, TypeError, ValueError):
            return "rejected"
        shape = keys.shape
        if (keys.ndim != 2 or shape[1] != self.config.ways
                or any(a.shape != shape
                       for a in (vals, used, stamp, cost))):
            return "rejected"
        # adoption must run under x64 or the int64 key/count planes are
        # silently truncated to int32 (packed adhesion keys would corrupt)
        with jax.enable_x64(True):
            self.keys = jnp.asarray(keys)
            self.vals = jnp.asarray(vals)
            self.used = jnp.asarray(used)
            self.stamp = jnp.asarray(stamp)
            self.cost = jnp.asarray(cost)
        self.tick = max(self.tick, int(state.get("tick", 0)))
        if not self.config.cache_payloads:
            return "ok"
        status = "ok"
        cap = int(self.config.payload_rows)
        try:
            pay_off = np.asarray(state["pay_off"], np.int32)
            pay_len = np.asarray(state["pay_len"], np.int32)
            bump = int(state["slab_bump"])
            if pay_off.shape != shape or pay_len.shape != shape:
                raise ValueError("payload plane shape mismatch")
            resident = used & (pay_len >= 0)
            if not (0 <= bump <= cap):
                raise ValueError("slab_bump outside the arena")
            if "slab" in state:
                slab = np.asarray(state["slab"], np.int32)
                if slab.ndim != 2 or slab.shape[0] != cap + 1:
                    raise ValueError("slab arena shape mismatch")
            else:
                # writer never materialized an arena — legal only if no
                # entry claims a block
                if resident.any() or bump != 0:
                    raise ValueError("resident blocks but no slab arena")
                slab = None
            if resident.any():
                off = pay_off[resident].astype(np.int64)
                ln = pay_len[resident].astype(np.int64)
                # the slab-epoch invariant: every resident block must lie
                # inside the allocated prefix, else a future alloc would
                # overwrite rows a key still points at (stale splice)
                if (off < 0).any() or ((off + ln) > bump).any():
                    raise ValueError("resident block outside slab epoch")
            with jax.enable_x64(True):
                self.pay_off = jnp.asarray(pay_off)
                self.pay_len = jnp.asarray(pay_len)
                self.slab = None if slab is None else jnp.asarray(slab)
            self.slab_bump = bump
            self.payload_flushes = int(state.get("payload_flushes", 0))
        except (KeyError, TypeError, ValueError):
            # cold-start the payload region only: keys/counts stay warm
            # (count-mode hits unaffected), blocks re-fill on use
            s, w = shape
            self.pay_off = jnp.zeros((s, w), jnp.int32)
            self.pay_len = jnp.full((s, w), -1, jnp.int32)
            self.slab = None
            self.slab_bump = 0
            self.payload_flushes += 1
            status = "flushed"
        return status


class CacheManager:
    """Per-TD-node DeviceCaches under one global slot budget."""

    def __init__(self, config: CacheConfig):
        self.config = config
        self.tables: Dict[int, DeviceCache] = {}
        # engine hint: how many node tables will eventually exist, so the
        # controller reserves their initial allocations out of the budget
        # instead of letting the first-created table grow into all of it
        self.expected_tables: Optional[int] = None

    @property
    def enabled(self) -> bool:
        return self.config.initial_slots() > 0

    def node_enabled(self, v: int) -> bool:
        en = self.config.enabled_nodes
        return self.enabled and (en is None or v in en)

    def get(self, v: int) -> DeviceCache:
        t = self.tables.get(v)
        if t is None:
            slots = self.config.initial_slots()
            if self.config.budget is not None:
                # node tables are created lazily: cap a newcomer by the
                # remaining headroom so earlier growth cannot spend the
                # whole budget (floor: one set, so the node still caches)
                headroom = self.config.budget - self.total_slots()
                slots = min(slots, max(self.config.ways, headroom))
            t = DeviceCache.create(self.config, slots)
            self.tables[v] = t
        return t

    def total_slots(self) -> int:
        return sum(t.n_slots for t in self.tables.values())

    def maybe_resize(self, v: int) -> int:
        t = self.tables.get(v)
        if t is None:
            return 0
        headroom = None
        if self.config.budget is not None:
            headroom = self.config.budget - self.total_slots()
            if self.expected_tables is not None:
                missing = max(0, self.expected_tables - len(self.tables))
                headroom -= missing * self.config.initial_slots()
        return t.maybe_resize(headroom)

    def stats(self, accs: Optional[Dict[int, Dict[str, Any]]] = None
              ) -> Dict[str, int]:
        """Counters summed over the tables; ``accs`` maps a node to its
        table's fetched accumulators (see :meth:`DeviceCache.stats`)."""
        agg = {"hits": 0, "misses": 0, "probes": 0, "inserts": 0,
               "evictions": 0, "resizes": 0, "slots": 0, "occupancy": 0,
               "payload_hits": 0, "payload_flushes": 0, "payload_skips": 0,
               "payload_throttled": 0, "slab_rows": 0}
        for v, t in self.tables.items():
            for k, val in t.stats((accs or {}).get(v)).items():
                agg[k] = agg.get(k, 0) + val
        return agg

    # -- cross-process state (repro/serve snapshots) -------------------
    def export_state(self) -> Dict[int, Dict[str, object]]:
        """Per-node table states (see :meth:`DeviceCache.export_state`)."""
        return {int(v): t.export_state() for v, t in self.tables.items()}

    def import_state(self, states: Dict[int, Dict[str, object]]
                     ) -> Dict[int, str]:
        """Adopt exported per-node states; nodes disabled under this
        config are skipped.  Returns each node's import status
        (``"ok"``/``"flushed"``/``"rejected"`` — see
        :meth:`DeviceCache.import_state`)."""
        out: Dict[int, str] = {}
        with jax.enable_x64(True):  # table creation allocates int64 planes
            for v, st in states.items():
                v = int(v)
                if not self.node_enabled(v):
                    out[v] = "skipped"
                    continue
                out[v] = self.get(v).import_state(st)
        return out
