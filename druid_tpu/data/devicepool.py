"""Process-wide byte-budgeted pool of device-resident segment data.

Reference analog: the historicals keeping segments mmapped and page-cached
under one OS-level memory budget (SegmentLoaderLocalCacheManager + the page
cache), rather than each segment bounding its own little cache. TPU-first
translation: staged DeviceBlocks and derived padded device arrays pin HBM;
the pool LRU-evicts by ACTUAL array bytes against one configurable budget,
so cache pressure is a single observable number instead of per-segment
entry counts (the old count-capped Segment._device_cache).

Entries are owned by a Segment (via an opaque owner token); a segment being
garbage-collected purges its entries through a weakref finalizer, so dropped
segment generations release HBM without any explicit unload call.

Stats (hits/misses/evictions/evictedBytes/residentBytes) feed the
`segment/devicePool/*` emitter metrics (DevicePoolMonitor below, wired by
cluster/dataserver.py).
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import weakref
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Set, Tuple

from druid_tpu.obs.trace import span as trace_span
from druid_tpu.utils.emitter import Monitor

#: key[0] marker for stacked sharded-execution blocks
#: (parallel/distributed.py stack owner) — entries so marked feed the
#: PoolStats.stacked_* accounting alongside the shared byte budget
STACKED_KIND = "shardStack"


#: entries built (pool misses) per thread — see thread_builds()
_BUILDS = threading.local()


def thread_builds() -> int:
    """How many pool entries THIS thread has built so far (misses of
    get_or_build on any pool). A caller brackets a phase with two reads to
    learn how many arrays it built rather than found resident — the
    `built` attribute of the `engine/filter/words` span."""
    return getattr(_BUILDS, "n", 0)


def _default_budget() -> int:
    # capacity bound only: the budget sizes the pool and its eviction,
    # it never reaches a traced program (catalog: live, no key_member)
    env = os.environ.get("DRUID_TPU_DEVICE_POOL_BYTES")  # druidlint: disable=env-flag-latch
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    # lazy: importing the engine package at module-import time would cycle
    # (engine -> data.segment -> devicepool); at first-use time the engine
    # is importable and its x64 side effect is the intended global anyway
    from druid_tpu.engine.contracts import DEVICE_POOL_BUDGET_BYTES
    return DEVICE_POOL_BUDGET_BYTES


def _fold_entry(value, measure) -> int:
    """THE one recursive walker over a pool entry's structure —
    DeviceBlocks (their array dict), dicts, tuples/lists — summing
    `measure(leaf)`; `measure` returns None to recurse into a node, and
    unmeasurable leaves count 0. Every accounting view (actual bytes,
    decoded-equivalent bytes, cascade bytes) folds through here, so a new
    container shape added once covers all of them."""
    if value is None:
        return 0
    got = measure(value)
    if got is not None:
        return int(got)
    arrays = getattr(value, "arrays", None)
    if isinstance(arrays, dict):
        value = arrays
    if isinstance(value, dict):
        return sum(_fold_entry(v, measure) for v in value.values())
    if isinstance(value, (tuple, list)):
        return sum(_fold_entry(v, measure) for v in value)
    return 0


def _measure_nbytes(v):
    # containers have no nbytes; anything that does is a leaf
    if isinstance(v, (dict, tuple, list)) or hasattr(v, "arrays"):
        return None
    return getattr(v, "nbytes", None)


class LogicalBytes:
    """Accounting-only leaf: contributes `logical_nbytes` to the
    decoded-equivalent accounting and zero actual bytes. Builders of
    BATCHED entries ride one in their value: a stacked sharded block's
    column objects carry per-SEGMENT aux (rows=R — the vmapped decode
    needs it), so their logical_nbytes describes one segment while their
    leaves hold K; this leaf restores the missing (K-1) share so
    packed/stacked ratios stay honest."""

    __slots__ = ("logical_nbytes",)
    nbytes = 0

    def __init__(self, logical_nbytes: int):
        self.logical_nbytes = int(logical_nbytes)


def entry_bytes(value) -> int:
    """Actual device bytes a pool entry pins: DeviceBlocks count their
    array dict, containers count their leaves, arrays their nbytes.
    PackedColumn/cascade entries (and any pytree mixing compressed words
    with aux arrays) count their COMPRESSED bytes — the pool budgets what
    HBM actually holds, so effective capacity multiplies by the ratio."""
    return _fold_entry(value, _measure_nbytes)


def entry_logical_bytes(value) -> int:
    """Decoded-equivalent bytes of a pool entry: what the same data would
    pin if staged fully decoded. Equals entry_bytes for plain arrays;
    packed/cascade columns report rows × element width. logical / actual
    is the pool's packedRatio — the effective-capacity multiplier."""
    def measure(v):
        logical = getattr(v, "logical_nbytes", None)
        if logical is not None:
            return logical
        return _measure_nbytes(v)
    return _fold_entry(value, measure)


def entry_cascade_bytes(value) -> Tuple[int, int]:
    """(actual, decoded-equivalent) bytes of the CASCADE-encoded leaves of
    a pool entry (data/cascade.py RLE/delta/FOR/LZ4 columns, marked by
    `cascade_kind`). Their ratio is the pool's cascadeRatio — the
    capacity multiplier the cascade rungs specifically add on top of
    bit-packing."""
    def cascade_leaf(attr):
        def measure(v):
            if getattr(v, "cascade_kind", None) is not None:
                return getattr(v, attr, 0)
            return None if isinstance(v, (dict, tuple, list)) \
                or hasattr(v, "arrays") else 0
        return measure
    return (_fold_entry(value, cascade_leaf("nbytes")),
            _fold_entry(value, cascade_leaf("logical_nbytes")))


@dataclass
class PoolStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    evicted_bytes: int = 0
    resident_bytes: int = 0
    logical_bytes: int = 0
    cascade_bytes: int = 0
    cascade_logical_bytes: int = 0
    stacked_bytes: int = 0
    stacked_logical_bytes: int = 0
    stacked_entries: int = 0
    entries: int = 0
    budget_bytes: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @property
    def packed_ratio(self) -> float:
        """Decoded-equivalent bytes / actual resident bytes: 1.0 when
        nothing is packed, the effective-capacity multiplier otherwise."""
        return self.logical_bytes / self.resident_bytes \
            if self.resident_bytes else 1.0

    @property
    def cascade_ratio(self) -> float:
        """Decoded-equivalent / actual bytes over CASCADE-encoded entries
        only (1.0 when nothing cascade-encoded is resident)."""
        return self.cascade_logical_bytes / self.cascade_bytes \
            if self.cascade_bytes else 1.0

    @property
    def stacked_ratio(self) -> float:
        """Decoded-equivalent / actual bytes over the STACKED sharded
        blocks only (query/sharded/packedRatio — 1.0 when nothing is
        stacked): how much HBM the compressed-resident stacking saves a
        pod versus the old decoded host-stack."""
        return self.stacked_logical_bytes / self.stacked_bytes \
            if self.stacked_bytes else 1.0


class DeviceSegmentPool:
    """Byte-budgeted LRU over (owner, key) -> device value."""

    def __init__(self, budget_bytes: Optional[int] = None):
        self._budget = budget_bytes            # None -> resolve lazily
        self._lock = threading.Lock()
        # key -> (value, actual_bytes, logical_bytes,
        #         cascade_actual_bytes, cascade_logical_bytes)
        self._entries: "collections.OrderedDict[Tuple, Tuple]" \
            = collections.OrderedDict()
        self._owner_keys: Dict[int, Set[Tuple]] = {}
        self._owner_seq = itertools.count(1)
        # weakref finalizers ONLY append here (deque.append is atomic and
        # takes no lock): a finalizer can fire at any allocation point —
        # including while this thread already holds self._lock — so a
        # finalizer that acquired the lock would self-deadlock. Dead owners
        # are drained under the lock at the next pool operation.
        self._dead_owners: "collections.deque[int]" = collections.deque()
        self._resident = 0
        self._logical = 0
        self._cascade = 0
        self._cascade_logical = 0
        self._stacked = 0
        self._stacked_logical = 0
        self._stacked_entries = 0
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._evicted_bytes = 0

    # ---- configuration --------------------------------------------------
    @property
    def budget_bytes(self) -> int:
        """Resolved budget; <= 0 means unbounded (no eviction)."""
        if self._budget is None:
            self._budget = _default_budget()
        return self._budget

    def configure(self, budget_bytes: Optional[int]) -> None:
        """Set the byte budget (None re-resolves env/contract default;
        <= 0 disables eviction) and trims immediately."""
        with self._lock:
            self._drain_dead_locked()
            self._budget = budget_bytes
            budget = self.budget_bytes
            if budget > 0:
                self._evict_to(budget, keep=None)

    # ---- owner registry -------------------------------------------------
    def register_owner(self, obj) -> int:
        """Opaque token for `obj`'s entries; a weakref finalizer marks it
        dead when `obj` is collected (dropped segments release HBM at the
        next pool touch). The token's presence in the owner registry IS the
        liveness bit get_or_build checks before caching."""
        with self._lock:
            self._drain_dead_locked()
            token = next(self._owner_seq)
            self._owner_keys.setdefault(token, set())
        weakref.finalize(obj, self._note_dead, token)
        return token

    def _note_dead(self, owner: int) -> None:
        """Finalizer target. MUST NOT touch self._lock: finalizers run at
        arbitrary allocation points, including under this very lock."""
        # the lock-free write is the point: deque.append is atomic, and a
        # finalizer taking self._lock would self-deadlock when GC fires
        # inside a locked region
        self._dead_owners.append(owner)  # druidlint: disable=unguarded-shared-write

    def _drain_dead_locked(self) -> int:
        """Caller holds the lock. Purge every finalizer-reported owner."""
        freed = 0
        while True:
            try:
                owner = self._dead_owners.popleft()
            except IndexError:
                break
            freed += self._purge_locked(owner)
        return freed

    @staticmethod
    def _is_stacked(full_key: Tuple) -> bool:
        # full_key = (owner,) + key; stacked blocks lead their key with
        # STACKED_KIND (the distributed.py stack owner's convention)
        return len(full_key) > 1 and full_key[1] == STACKED_KIND

    def _forget_stacked(self, full_key: Tuple, entry: Tuple) -> None:
        """Caller holds the lock and just removed `entry` under
        `full_key` — every removal path (purge, take, evict, replace)
        funnels here so the stacked counters cannot drift."""
        if self._is_stacked(full_key):
            self._stacked -= entry[1]
            self._stacked_logical -= entry[2]
            self._stacked_entries -= 1

    def _purge_locked(self, owner: int) -> int:
        freed = 0
        for key in self._owner_keys.pop(owner, ()):
            value = self._entries.pop(key, None)
            if value is not None:
                freed += value[1]
                self._logical -= value[2]
                self._cascade -= value[3]
                self._cascade_logical -= value[4]
                self._forget_stacked(key, value)
        self._resident -= freed
        return freed

    def purge_owner(self, owner: int) -> int:
        """Drop every entry owned by `owner` NOW; returns bytes released.
        Purges are bookkeeping, not cache pressure: they do not count as
        evictions. Removing the owner's registry slot also marks it dead,
        so an in-flight get_or_build cannot resurrect its entries (a late
        insert after the owner died would pin HBM forever)."""
        with self._lock:
            return self._purge_locked(owner)

    # ---- cache surface --------------------------------------------------
    def peek(self, owner: int, key: Tuple) -> bool:
        """Residency probe WITHOUT touching LRU order or hit/miss stats —
        callers keeping their own cache metrics (the filter-bitmap cache's
        query/filter/* counters) ask this before get_or_build so the pool's
        segment/devicePool/* accounting is not double-counted."""
        with self._lock:
            return ((owner,) + tuple(key)) in self._entries

    def get_or_build(self, owner: int, key: Tuple, build: Callable[[], object]):
        """LRU get; on miss, `build()` runs OUTSIDE the lock (staging does
        device_put) — a concurrent duplicate build wastes work but cannot
        corrupt the accounting (the replaced entry's bytes are subtracted)."""
        full_key = (owner,) + tuple(key)
        with self._lock:
            self._drain_dead_locked()
            hit = self._entries.get(full_key)
            if hit is not None:
                self._entries.move_to_end(full_key)
                self._hits += 1
                return hit[0]
            self._misses += 1
        _BUILDS.n = getattr(_BUILDS, "n", 0) + 1
        # cold miss: the H2D staging cost a warm pool hides. The span times
        # the whole build (host prep + device_put) at its existing boundary
        with trace_span("pool/h2d",
                        kind=str(key[0]) if key else "") as sp:
            value = build()
            nbytes = entry_bytes(value)
            logical = entry_logical_bytes(value)
            casc, casc_logical = entry_cascade_bytes(value)
            if sp is not None:
                # "bytes" is what actually crossed the bus (compressed for
                # packed entries); logicalBytes the decoded-equivalent size
                sp.attrs["bytes"] = nbytes
                sp.attrs["logicalBytes"] = logical
        with self._lock:
            self._drain_dead_locked()
            keys = self._owner_keys.get(owner)
            if keys is None:
                # owner purged while build() ran (segment GC'd mid-query):
                # hand the value back WITHOUT caching — its finalizer will
                # never run again, so a cached entry would leak HBM
                return value
            old = self._entries.pop(full_key, None)
            if old is not None:
                self._resident -= old[1]
                self._logical -= old[2]
                self._cascade -= old[3]
                self._cascade_logical -= old[4]
                self._forget_stacked(full_key, old)
            self._entries[full_key] = (value, nbytes, logical, casc,
                                       casc_logical)
            keys.add(full_key)
            self._resident += nbytes
            self._logical += logical
            self._cascade += casc
            self._cascade_logical += casc_logical
            if self._is_stacked(full_key):
                self._stacked += nbytes
                self._stacked_logical += logical
                self._stacked_entries += 1
            budget = self.budget_bytes
            if budget > 0:
                self._evict_to(budget, keep=full_key)
        return value

    def take(self, owner: int, key: Tuple):
        """Remove and return an entry's value (None when absent). The
        megakernel's donated-carry handoff: the previous execution's
        partial buffers pop out so they can be DONATED back into the next
        program — on accelerator backends donation invalidates the
        buffers, so they must leave the pool before the call. Stats-free
        like peek(): carry probes are handoff mechanics, not staging-cache
        outcomes, and must not skew segment/devicePool hit/miss series.
        Never counts as an eviction either.

        Ownership contract (donorguard): a successful take POPS ownership
        to the caller, who owes a re-park (get_or_build/device_cached), a
        return, or an explicit discard on every path — the static
        take-without-repark rule and the DRUID_TPU_DONOR_WITNESS=1
        dynamic witness (tools/druidlint/donorwitness.py) both enforce
        it, the witness by tracking the popped leaves' identity."""
        full_key = (owner,) + tuple(key)
        with self._lock:
            self._drain_dead_locked()
            entry = self._entries.pop(full_key, None)
            if entry is None:
                return None
            self._owner_keys.get(owner, set()).discard(full_key)
            self._resident -= entry[1]
            self._logical -= entry[2]
            self._cascade -= entry[3]
            self._cascade_logical -= entry[4]
            self._forget_stacked(full_key, entry)
            return entry[0]

    def _evict_to(self, budget: int, keep: Optional[Tuple]) -> None:
        """Caller holds the lock. `keep` (the just-inserted entry) survives
        even when it alone exceeds the budget — the query running right now
        must not have its own block evicted from under it."""
        while self._resident > budget and self._entries:
            key = next(iter(self._entries))
            if key == keep:
                if len(self._entries) == 1:
                    return
                self._entries.move_to_end(key)
                continue
            entry = self._entries.pop(key)
            _, nbytes, logical, casc, casc_logical = entry
            # key[0] is the owner token (get_or_build prefixes it)
            self._owner_keys.get(key[0], set()).discard(key)
            self._resident -= nbytes
            self._logical -= logical
            self._cascade -= casc
            self._cascade_logical -= casc_logical
            self._forget_stacked(key, entry)
            self._evictions += 1
            self._evicted_bytes += nbytes

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            # keep owner slots (liveness bits) — only their key sets drop;
            # clearing slots would permanently refuse live segments' inserts
            for keys in self._owner_keys.values():
                keys.clear()
            self._resident = 0
            self._logical = 0
            self._cascade = 0
            self._cascade_logical = 0
            self._stacked = 0
            self._stacked_logical = 0
            self._stacked_entries = 0

    # ---- observability --------------------------------------------------
    def snapshot(self) -> PoolStats:
        with self._lock:
            self._drain_dead_locked()
            return PoolStats(hits=self._hits, misses=self._misses,
                             evictions=self._evictions,
                             evicted_bytes=self._evicted_bytes,
                             resident_bytes=self._resident,
                             logical_bytes=self._logical,
                             cascade_bytes=self._cascade,
                             cascade_logical_bytes=self._cascade_logical,
                             stacked_bytes=self._stacked,
                             stacked_logical_bytes=self._stacked_logical,
                             stacked_entries=self._stacked_entries,
                             entries=len(self._entries),
                             budget_bytes=self.budget_bytes)


_POOL = DeviceSegmentPool()


def device_pool() -> DeviceSegmentPool:
    """The process-wide pool every Segment stages through."""
    return _POOL


class DevicePoolMonitor(Monitor):
    """Emits `segment/devicePool/*` metrics per tick: the hit RATE over the
    tick window (only when there was traffic — an idle pool emits no rate),
    delta hit/miss/evicted counters, and resident gauges."""

    def __init__(self, pool: Optional[DeviceSegmentPool] = None):
        self.pool = pool or device_pool()
        self._last = PoolStats()

    def do_monitor(self, emitter):
        s = self.pool.snapshot()
        last, self._last = self._last, s
        d_hits = s.hits - last.hits
        d_misses = s.misses - last.misses
        if d_hits + d_misses > 0:
            emitter.metric("segment/devicePool/hitRate",
                           d_hits / (d_hits + d_misses))
        emitter.metric("segment/devicePool/hits", d_hits)
        emitter.metric("segment/devicePool/misses", d_misses)
        emitter.metric("segment/devicePool/evictedBytes",
                       s.evicted_bytes - last.evicted_bytes)
        emitter.metric("segment/devicePool/residentBytes", s.resident_bytes)
        emitter.metric("segment/devicePool/entries", s.entries)
        emitter.metric("segment/devicePool/packedRatio", s.packed_ratio)
        emitter.metric("segment/devicePool/cascadeRatio", s.cascade_ratio)
