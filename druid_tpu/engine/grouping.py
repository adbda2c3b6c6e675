"""The unified grouped-aggregate device program.

One XLA program implements all three aggregating engines of the reference:
  * timeseries  — key = time bucket                (TimeseriesQueryEngine.java:87)
  * topN        — key = bucket × cardinality + id  (PooledTopNAlgorithm.java:111)
  * groupBy     — key = fused dim ids              (GroupByQueryEngineV2.java:413)

The program is: mask = valid ∧ time-in-intervals ∧ filter; key = fused
(bucket, dim ids); for each aggregator a segmented reduction over key. The
per-(structure) jitted callable is cached — XLA recompiles only when shapes
change, playing the role of the reference's SpecializationService bytecode
cache and of GroupBy's ByteBufferHashTable (dense keys replace open-addressing
hashing, the BufferArrayGrouper insight generalized).

Two key modes:
  * dense   — group space B × ∏cardinalities small enough for a dense grid;
    dim id columns fuse on device (optionally through remap tables, which
    implement extraction fns, listFiltered, and cross-segment dictionary
    unification).
  * host    — high-cardinality fallback: the fused key column is compacted
    host-side with np.unique (cached per segment, the analog of the
    reference's per-segment dictionaries) and the device reduces over compact
    ids. Plays the role of GroupBy's SpillingGrouper for cardinalities that
    would not fit a dense grid.

Reduction strategies (chosen per (segment, query) by `select_strategy`,
measured rates on a v5e chip at 12.5M rows):
  * "mm"       — one-hot MXU matmul (engine/mmagg.py), G ≤ 4096, all
    aggregators sum-decomposable. ~790M rows/s at G=1024.
  * "windowed" — big-G local-dense path for dimension-sorted segments (the
    reference's rollup sort order): each 1k-row block's keys span < W, so a
    [block, W] local grid reduces on the VPU and the per-block grids scatter
    into the full grid at block granularity (#blocks×W ≪ N elements).
    ~300M rows/s at G=131072 vs ~77M for scatter.
  * "blocked"  — scanned [block, G] masked broadcast-reduce, G ≤ 2048.
  * "mixed"    — per-kernel blocked where supported, else scatter
    (segment_sum/min/max); the fully general fallback.
"""
from __future__ import annotations

import collections
import functools
import logging
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from druid_tpu.data import cascade as cascade_mod
from druid_tpu.data import packed as packed_mod
from druid_tpu.data.segment import DEFAULT_ROW_ALIGN, Segment
from druid_tpu.data.devicepool import entry_bytes
from druid_tpu.engine import filters as filters_mod
from druid_tpu.engine import megakernel, pallas_agg
from druid_tpu.engine.contracts import (PENDING_FETCH_BYTES, named_program,
                                        program_name)
from druid_tpu.engine.filters import (ConstNode, FilterNode, plan_filter,
                                      simplify_node)
from druid_tpu.obs import dispatch as dispatch_mod
from druid_tpu.obs.trace import span as trace_span
from druid_tpu.obs.trace import span_when as trace_span_when
from druid_tpu.engine.kernels import AggKernel, make_kernel
from druid_tpu.query.aggregators import AggregatorSpec
from druid_tpu.utils.granularity import Granularity
from druid_tpu.utils.intervals import Interval

DENSE_GROUP_LIMIT = 1 << 21  # max dense key space per (bucket × groups) grid


def pad_pow2(n: int, floor: int = 8) -> int:
    return max(floor, 1 << max(0, math.ceil(math.log2(max(n, 1)))))


@dataclass
class KeyDim:
    """One grouping dimension: ids column (+ optional remap) with cardinality.

    column=None means the dimension is absent from the segment — it
    contributes a constant id 0 (value "" at decode time), matching the
    reference's treatment of missing columns as null.

    host_ids set means the ids come from a derived host array rather than a
    segment dim column (numeric dimension handlers: a query-time dictionary
    over a metric column's values — DoubleDimensionHandler capability);
    `column` is then a synthetic name the executor stages the array under,
    and ids_key its cache identity for the padded device copy.
    """
    column: Optional[str]
    cardinality: int             # output cardinality (after remap)
    remap: Optional[np.ndarray]  # int32[input_card] -> output id or -1
    host_ids: Optional[np.ndarray] = None
    ids_key: Optional[Tuple] = None


@dataclass
class GroupSpec:
    """Bucketing + grouping config for one segment execution."""
    bucket_starts: np.ndarray          # int64 [B] bucket start timestamps
    bucket_mode: str                   # "all" | "uniform" | "host"
    uniform_period: int = 0
    uniform_first_offset: int = 0      # first bucket start - segment time0
    host_bucket_ids: Optional[np.ndarray] = None  # int32 [padded]
    key_mode: str = "dense"            # "dense" | "host"
    dims: Tuple[KeyDim, ...] = ()
    host_keys: Optional[np.ndarray] = None        # int32 [padded] compact ids
    host_unique: Optional[np.ndarray] = None      # raw fused keys per compact id
    num_total: int = 1                 # padded dense key-space size
    strategy: str = "mixed"            # reduction strategy (select_strategy)
    window: int = 0                    # local window W for "windowed"
    # stable cache identities for host_keys / host_bucket_ids so their padded
    # device copies persist in the segment cache across query executions
    # (re-device_put of a 100M-row key column costs ~400MB of H2D per query)
    host_keys_cache: Optional[Tuple] = None
    host_bucket_cache: Optional[Tuple] = None

    @property
    def num_buckets(self) -> int:
        return int(len(self.bucket_starts))


@dataclass
class SegmentPartial:
    """Partial aggregation result (host-side): one segment's as the engine
    produces it, many segments' once merged (`merge.merge_to_partial` on a
    data node's host, the sharded program on a mesh) — `segment` is then
    the first's."""
    segment: Segment
    spec: GroupSpec
    counts: np.ndarray                    # int64 [num_total]
    states: Dict[str, object]             # agg name -> host state
    kernels: List[AggKernel]


# ---------------------------------------------------------------------------
# Plan construction helpers
# ---------------------------------------------------------------------------

def _fused_raw_keys(segment: Segment, bucket_mode: str, bucket_starts,
                    period: int, B: int, host_bucket,
                    dims: Tuple[KeyDim, ...]) -> np.ndarray:
    """Host: int64 fused (bucket, dim ids) key per row; -1 = invalid row
    (out of bucket range or remapped-away dim value)."""
    if bucket_mode == "all":
        b = np.zeros(segment.n_rows, dtype=np.int64)
    elif bucket_mode == "uniform":
        b = (segment.time_ms - int(bucket_starts[0])) // period
        b = np.where((b < 0) | (b >= B), -1, b)
    else:
        b = host_bucket.astype(np.int64)
    key = b
    valid = b >= 0
    for d in dims:
        if d.column is None:
            continue
        ids = d.host_ids if d.host_ids is not None \
            else segment.dims[d.column].ids
        if d.remap is not None:
            ids = d.remap[ids]
            valid &= ids >= 0
        key = key * d.cardinality + ids
    return np.where(valid, key, -1)


@dataclass
class Projection:
    """A sorted, key-compacted view of one segment for one key structure —
    the query-time analog of the reference's rollup sort order + dictionary
    (IndexMergerV9 row ordering; Druid 31 'projections'). Built once per
    (segment, granularity, intervals, dims) and cached on the segment; the
    row permutation clusters equal group keys so big-G aggregations reduce
    over a small local window instead of scattering across the full grid."""
    order: np.ndarray       # int32 [n] row permutation (invalid rows first)
    keys: np.ndarray        # int32 [n] sorted compact ids (-1 = invalid)
    unique: np.ndarray      # int64 [G] raw fused key per compact id
    max_span: int           # max key span over WINDOW_BLOCK-row blocks


def _key_structure(kind: str, granularity: Granularity,
                   intervals: Sequence[Interval],
                   dims: Sequence[KeyDim]) -> Tuple:
    """Cache identity of what one (granularity, intervals, key dims) derives
    from a segment: its fused keys, its projection, its window span."""
    return (kind, str(granularity),
            tuple((iv.start, iv.end) for iv in intervals),
            tuple((d.column, d.cardinality,
                   None if d.remap is None else d.remap.tobytes())
                  for d in dims))


def _max_block_span(keys: np.ndarray) -> int:
    """Widest span of valid keys (-1 = invalid row) over the
    WINDOW_BLOCK-row blocks of `keys`; 1 when no row is valid."""
    n, blk = keys.shape[0], WINDOW_BLOCK
    top = np.iinfo(np.int64).max
    kp = np.full(max(-(-n // blk), 1) * blk, top, dtype=np.int64)
    kp[:n] = np.where(keys >= 0, keys, top)
    lo = kp.reshape(-1, blk).min(axis=1)
    hi = np.where(kp == top, -1, kp).reshape(-1, blk).max(axis=1)
    live = hi >= 0
    return int((hi[live] - lo[live] + 1).max()) if live.any() else 1


def build_projection(segment: Segment, intervals: Sequence[Interval],
                     granularity: Granularity,
                     spec: "GroupSpec") -> Projection:
    cache_key = _key_structure("projection", granularity, intervals,
                               spec.dims)

    def _compute():
        # runs only when the projection is BUILT (a miss of the segment's
        # aux cache): the sort that dominates a cold process's set-up
        with trace_span("engine/projection/build", rows=segment.n_rows):
            return _sort_projection()

    def _sort_projection():
        raw = _fused_raw_keys(segment, spec.bucket_mode, spec.bucket_starts,
                              spec.uniform_period, spec.num_buckets,
                              spec.host_bucket_ids, spec.dims)
        n = raw.shape[0]
        order = np.argsort(raw, kind="stable")
        sr = raw[order]
        n_invalid = int(np.searchsorted(sr, 0))  # -1 rows sort first
        valid_sorted = sr[n_invalid:]
        keys = np.full(n, -1, dtype=np.int32)
        if valid_sorted.size:
            newgrp = np.empty(valid_sorted.shape, dtype=bool)
            newgrp[0] = True
            np.not_equal(valid_sorted[1:], valid_sorted[:-1], out=newgrp[1:])
            unique = valid_sorted[newgrp]
            keys[n_invalid:] = np.cumsum(newgrp) - 1
        else:
            unique = np.zeros(0, dtype=np.int64)
        # the sorted layout keeps the span near the per-block
        # distinct-group count
        return Projection(order=order.astype(np.int32), keys=keys,
                          unique=unique, max_span=_max_block_span(keys))

    return segment.aux_cached(cache_key, _compute)


def make_group_spec(segment: Segment, intervals: Sequence[Interval],
                    granularity: Granularity,
                    dims: Sequence[KeyDim]) -> GroupSpec:
    """Choose bucket mode + key mode for this (segment, query) pair."""
    if granularity.is_all:
        # one global bucket across all query intervals (AllGranularity)
        first = min((iv.start for iv in intervals), default=0)
        bucket_starts_list = [np.asarray([first], dtype=np.int64)]
        bucket_starts = bucket_starts_list[0]
    else:
        bucket_starts_list = [granularity.bucket_starts(iv) for iv in intervals]
        bucket_starts = (np.concatenate(bucket_starts_list)
                         if bucket_starts_list else np.zeros(0, dtype=np.int64))
    B = max(int(len(bucket_starts)), 1)

    host_bucket_cache = None
    if granularity.is_all:
        bucket_mode, period, first_off, host_bucket = "all", 0, 0, None
    elif (granularity.is_uniform and len(intervals) == 1):
        bucket_mode = "uniform"
        period = granularity.period_ms
        first_off = int(bucket_starts[0] - segment.interval.start)
        host_bucket = None
    else:
        bucket_mode, period, first_off = "host", 0, 0
        key = ("bucket_ids", str(granularity),
               tuple((iv.start, iv.end) for iv in intervals))

        def _compute():
            ids_parts = []
            offset = 0
            out = np.full(segment.n_rows, -1, dtype=np.int32)
            for iv, starts in zip(intervals, bucket_starts_list):
                ids = granularity.bucket_ids(segment.time_ms, iv)
                sel = ids >= 0
                out[sel] = ids[sel] + offset
                offset += len(starts)
            return out
        host_bucket = segment.aux_cached(key, _compute)
        host_bucket_cache = key

    dims = tuple(dims)
    group_card = 1
    for d in dims:
        group_card *= max(d.cardinality, 1)
    dense_total = B * group_card

    spec = GroupSpec(bucket_starts=bucket_starts, bucket_mode=bucket_mode,
                     uniform_period=period, uniform_first_offset=first_off,
                     host_bucket_ids=host_bucket, key_mode="dense", dims=dims,
                     num_total=pad_pow2(dense_total),
                     host_bucket_cache=host_bucket_cache)
    if not dims or dense_total <= DENSE_GROUP_LIMIT:
        return spec

    # host-compacted key path: fuse (bucket, dim ids) host-side and np.unique
    cache_key = _key_structure("fused_keys", granularity, intervals, dims)

    def _compute_keys():
        key = _fused_raw_keys(segment, bucket_mode, bucket_starts, period, B,
                              host_bucket, dims)
        uniq, compact = np.unique(key, return_inverse=True)
        # drop the -1 group if present by remapping it to an unused slot
        if len(uniq) and uniq[0] == -1:
            compact = compact - 1  # -1 rows get id -1
            uniq = uniq[1:]
        return uniq, compact.astype(np.int32)

    spec.host_unique, spec.host_keys = segment.aux_cached(cache_key,
                                                          _compute_keys)
    spec.key_mode, spec.host_keys_cache = "host", cache_key
    spec.num_total = pad_pow2(max(len(spec.host_unique), 1))
    return spec


# ---------------------------------------------------------------------------
# Device program assembly + jit cache
# ---------------------------------------------------------------------------

# Compiled per-segment programs keyed on the structure signature, LRU-bounded:
# closures capture only plan structure (segment constants arrive via aux at
# call time), but dropped query shapes should still release their executables.
# The lock covers the whole get-or-build sequence: the broker fans segments
# out over a thread pool, and an unsynchronized evict could race a
# move_to_end into KeyError (jit() construction is lazy, so building under
# the lock costs nothing — tracing happens at first call).
_JIT_CACHE: "collections.OrderedDict[str, object]" = collections.OrderedDict()
_JIT_CACHE_CAP = 128
_JIT_CACHE_LOCK = threading.Lock()


def plan_virtual_columns(segment: Segment, virtual_columns: Sequence
                         ) -> Tuple[Tuple, List[np.ndarray]]:
    """Per-(segment, query) virtual-column plan: parse each expression and
    rewrite string-dimension comparisons into per-dictionary-id LUT gathers
    (utils.expression.rewrite_string_sites) — the device never sees string
    semantics, only an aux bool LUT indexed by dictionary ids.

    Returns (vc_plans, luts): vc_plans = ((name, rewritten_expr, out_type,
    n_luts), ...) — structural, shareable across segments with equal
    signatures — and the flat per-segment LUT list for the aux stream."""
    from druid_tpu.utils.expression import (lut_for_site, parse_expression,
                                            rewrite_string_sites)
    plans = []
    luts: List[np.ndarray] = []
    string_dims = frozenset(segment.dims)
    for v in virtual_columns:
        expr, sites = rewrite_string_sites(
            parse_expression(v.expression), string_dims)
        for site in sites:
            luts.append(lut_for_site(
                site, segment.dims[site[0]].dictionary.values))
        plans.append((v.name, expr, v.output_type, len(sites)))
    return tuple(plans), luts


def eval_virtual_columns(arrays: Dict, t_abs, vc_plans, it=None) -> Dict:
    """Traced: evaluate planned expression virtual columns over staged
    columns (reference: ExpressionVirtualColumn) into fused XLA elementwise
    ops; string-comparison LUTs stream in from the aux iterator `it`.
    Shared by the per-segment and sharded program builders."""
    import jax
    import jax.numpy as jnp

    # x64 gate: under JAX's default x64-disabled mode an astype(jnp.int64)
    # silently produces int32 — request the wide dtypes only when the flag
    # is actually on (engine/__init__ enables it), and name the narrow
    # dtypes explicitly otherwise so the truncation is a stated contract,
    # not an accident.
    if jax.config.jax_enable_x64:
        long_dt, double_dt = jnp.int64, jnp.float64
    else:
        long_dt, double_dt = jnp.int32, jnp.float32
    bindings = dict(arrays)
    bindings["__time"] = t_abs
    arrays = dict(arrays)
    for name, expr, out_type, n_luts in vc_plans:
        bindings["__luts"] = [next(it) for _ in range(n_luts)]
        val = expr.evaluate(bindings)
        dt = {"long": long_dt, "double": double_dt,
              "float": jnp.float32}.get(out_type, double_dt)
        arrays[name] = jnp.asarray(val).astype(dt)
        bindings[name] = arrays[name]
    return arrays


def fuse_filter_update(arrays: Dict, mask, key, it, dims: Sequence[KeyDim],
                       filter_node: Optional[FilterNode],
                       kernels: Sequence[AggKernel], num_total: int,
                       strategy: str = "mixed", window: int = 0,
                       packed_cols: Optional[Dict] = None):
    """Traced: the tail of the grouped-aggregate body (traced_segment) —
    fuse the ids of `dims` into the key (through optional remap tables),
    apply the filter mask, and run every kernel's segmented reduction via
    the selected strategy.

    `arrays` is the DENSE view (the program top already decoded any
    bit-packed columns — data/packed.py); `packed_cols` carries the
    original PackedColumns so the pallas strategy can consume the words
    directly and unpack per VMEM tile. XLA dead-code-eliminates whichever
    representation a strategy leaves unused."""
    import jax
    import jax.numpy as jnp

    for d in dims:
        if d.column is None:
            continue
        ids = arrays[d.column]
        if d.remap is not None:
            remap = next(it)
            ids = remap[ids]
            mask = mask & (ids >= 0)
        card = next(it)
        key = key * card + jnp.maximum(ids, 0)

    if strategy == "megakernel":
        # the fused one-dispatch variant (engine/megakernel.py): top-level
        # AND-conjunct mega nodes stay in the WORD domain all the way into
        # the pallas kernel; only the residual (row-domain) part of the
        # tree expands here. Masked rows read the key sentinel in-kernel,
        # so results are bit-identical to the staged pallas path.
        mega_nodes, residual = megakernel.split_for_kernel(filter_node)
        if residual is not None:
            mask = mask & residual.build(arrays, it)
        key = jnp.clip(key, 0, num_total - 1).astype(jnp.int32)
        return megakernel.mega_reduce(arrays, mask, key, mega_nodes,
                                      kernels, num_total, window,
                                      packed_cols=packed_cols)

    if filter_node is not None:
        mask = mask & filter_node.build(arrays, it)

    key = jnp.clip(key, 0, num_total - 1).astype(jnp.int32)

    if strategy == "mm":
        from druid_tpu.engine.mmagg import mm_reduce
        col_dtypes = {c: a.dtype for c, a in arrays.items()}
        plans = [k.mm_plan(col_dtypes, mask.shape[0]) for k in kernels]
        # select_strategy validated eligibility against plan-time dtypes; a
        # divergence here (row padding, virtual-column dtype) must fail
        # loudly at plan time, not as an opaque trace error
        missing = [k.signature() for k, p in zip(kernels, plans) if p is None]
        if missing:
            raise AssertionError(
                f"mm strategy selected but kernels have no mm plan at trace "
                f"time: {missing}")
        return mm_reduce(arrays, mask, key, kernels, plans, num_total)

    if strategy == "pallas":
        return pallas_agg.pallas_reduce(arrays, mask, key, kernels,
                                        num_total, window,
                                        packed_cols=packed_cols)

    if strategy == "windowed":
        return _windowed_reduce(arrays, mask, key, kernels, num_total, window)

    blocked_idx = []
    if strategy in ("blocked", "mixed") and num_total <= BLOCKED_GROUP_LIMIT:
        col_dtypes = {c: a.dtype for c, a in arrays.items()}
        blocked_idx = [i for i, k in enumerate(kernels)
                       if k.blocked_supported(col_dtypes)]
    blocked_states = {}
    counts = None
    if blocked_idx:
        bk = [kernels[i] for i in blocked_idx]
        counts, bstates = _blocked_reduce(arrays, mask, key, bk, num_total)
        blocked_states = dict(zip(blocked_idx, bstates))
    if counts is None:
        counts = jax.ops.segment_sum(mask.astype(jnp.int32), key,
                                     num_segments=num_total)
    # positional states: the jit cache is shared across queries whose
    # aggregators differ only by output name
    states = tuple(blocked_states[i] if i in blocked_states
                   else k.update(arrays, mask, key, num_total, it)
                   for i, k in enumerate(kernels))
    return counts, states


BLOCKED_GROUP_LIMIT = 2048
BLOCK_ROWS = 2048

# ---------------------------------------------------------------------------
# Windowed local-dense reduction (dimension-sorted segments)
# ---------------------------------------------------------------------------

WINDOW_BLOCK = 1024          # rows per local-window block
WINDOW_SUB = 8               # blocks per scan step
WINDOW_CHOICES = (128, 256, 512)


def _windowed_reduce(arrays: Dict, mask, key, kernels: Sequence[AggKernel],
                     num_total: int, W: int):
    """Big-G reduction for segments whose rows are clustered by the grouping
    key (the reference's rollup sort order, IndexMergerV9 row ordering): each
    WINDOW_BLOCK-row block's valid keys span < W, so the block reduces into a
    local [W] grid on the VPU and the per-block grids combine into the full
    [num_total] grid with a scatter over only (#blocks × W) elements."""
    import jax
    import jax.numpy as jnp

    fields = sorted({k.spec.field for k in kernels
                     if getattr(k.spec, "field", None) in arrays})
    n = mask.shape[0]
    step = WINDOW_BLOCK * WINDOW_SUB
    pad = (-n) % step

    def padded(a):
        if not pad:
            return a
        return jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])

    nstep = (n + pad) // step
    keyb = padded(key).reshape(nstep, WINDOW_SUB, WINDOW_BLOCK)
    maskb = padded(mask).reshape(nstep, WINDOW_SUB, WINDOW_BLOCK)
    colsb = {f: padded(arrays[f]).reshape(nstep, WINDOW_SUB, WINDOW_BLOCK)
             for f in fields}
    iota = jnp.arange(W, dtype=keyb.dtype)
    big = jnp.asarray(np.iinfo(np.int32).max, keyb.dtype)
    col_tmpl = {f: arrays[f] for f in fields}

    vary0 = (key[0] * 0) + (mask[0] * 0).astype(key.dtype)

    def body(carry, xs):
        kb, mb = xs[0], xs[1]                    # [WINDOW_SUB, WINDOW_BLOCK]
        cols = dict(zip(fields, xs[2:]))
        base = jnp.min(jnp.where(mb, kb, big), axis=1)
        base = jnp.where(base == big, 0, base)   # fully-masked block
        local = kb - base[:, None]
        valid = (local[:, :, None] == iota[None, None, :]) \
            & mb[:, :, None]                     # [SUB, BLOCK, W]
        cnt = valid.astype(jnp.int32).sum(axis=1, dtype=jnp.int32)
        grids = []
        for k in kernels:
            init0 = k.blocked_init(W, col_tmpl)
            grids.append(jax.vmap(
                lambda c, v, k=k, i0=init0: k.blocked_step(
                    i0, c, v, W))({f: cols[f] for f in fields}, valid))
        return carry, (base, cnt, tuple(grids))

    xs = (keyb, maskb) + tuple(colsb[f] for f in fields)
    _, (bases, cnts, grids) = jax.lax.scan(body, vary0, xs)

    # L2: per-block [W] grids scatter at block granularity. Slots past
    # num_total hold identity values by construction (keys were clipped), so
    # clipping their targets cannot corrupt real groups.
    flat_keys = jnp.clip(
        bases.reshape(-1)[:, None] + iota[None, :], 0, num_total - 1).ravel()
    counts = jax.ops.segment_sum(cnts.reshape(-1), flat_keys,
                                 num_segments=num_total)
    states = []
    for k, g in zip(kernels, grids):
        flat = g.reshape(-1, W).ravel() if g.ndim == 3 else g.reshape(-1)
        if k.reduce_kind == "max":
            st = jax.ops.segment_max(flat, flat_keys, num_segments=num_total)
        elif k.reduce_kind == "min":
            st = jax.ops.segment_min(flat, flat_keys, num_segments=num_total)
        else:
            st = jax.ops.segment_sum(flat, flat_keys, num_segments=num_total)
        states.append(k.blocked_finish(st))
    return counts, tuple(states)


def windowed_window(segment: Segment, intervals: Sequence[Interval],
                    granularity: Granularity, spec: GroupSpec) -> int:
    """Host-side eligibility for the windowed strategy: the smallest W in
    WINDOW_CHOICES covering every WINDOW_BLOCK-row block's fused-key span, or
    0. Conservative: spans are measured over ALL interval-valid rows; any
    query filter only shrinks the row set, so a sub-mask can never widen a
    block's span. Cached per (segment, key structure)."""
    key = _key_structure("windowed_span", granularity, intervals, spec.dims)

    def _compute():
        return _max_block_span(_fused_raw_keys(
            segment, spec.bucket_mode, spec.bucket_starts,
            spec.uniform_period, spec.num_buckets, spec.host_bucket_ids,
            spec.dims))

    span = segment.aux_cached(key, _compute)
    for w in WINDOW_CHOICES:
        if span <= w:
            return w
    return 0


#: test override (tests set the module attribute): force an ELIGIBLE
#: strategy; an ineligible force falls through to normal selection.
FORCE_STRATEGY: Optional[str] = None


def select_strategy(spec: GroupSpec, kernels: Sequence[AggKernel],
                    col_dtypes: Dict, padded_rows: int,
                    windowed_w) -> Tuple[str, int]:
    """Pick the reduction strategy for one (segment, query) plan.

    windowed_w: the host span check over every participating segment (0 or
    W), called only when the windowed path is actually a candidate."""
    from druid_tpu.engine.mmagg import MM_GROUP_LIMIT
    num = spec.num_total
    plans = [k.mm_plan(col_dtypes, padded_rows) for k in kernels]
    mm_ok = all(p is not None for p in plans)
    blocked_ok = all(k.blocked_supported(col_dtypes) for k in kernels)
    if FORCE_STRATEGY:
        f = FORCE_STRATEGY
        if f == "mixed":
            return "mixed", 0
        if f == "mm" and mm_ok and num <= MM_GROUP_LIMIT:
            return "mm", 0
        if f == "blocked" and blocked_ok and num <= BLOCKED_GROUP_LIMIT:
            # beyond the limit fuse_filter_update would silently scatter —
            # mislabeled timings are worse than a fallthrough
            return "blocked", 0
        if f == "windowed" and blocked_ok:
            w = windowed_w()
            if w:
                return "windowed", w
        if f == "projection" and blocked_ok:
            return "projection", 0
    if blocked_ok and num <= 64:
        return "blocked", 0      # near-streaming; scan step scales with 1/G
    if mm_ok and num <= 2048:
        return "mm", 0
    if num > BLOCKED_GROUP_LIMIT and blocked_ok and spec.key_mode == "dense":
        w = windowed_w()
        if w:
            return "windowed", w
    if blocked_ok and num <= BLOCKED_GROUP_LIMIT:
        return "blocked", 0
    if mm_ok and num <= MM_GROUP_LIMIT:
        return "mm", 0
    if blocked_ok and num > MM_GROUP_LIMIT \
            and padded_rows >= PROJECTION_MIN_ROWS:
        # big group space over a big segment: build/reuse the sorted
        # key-compacted projection and reduce over a local window (pallas on
        # TPU, the XLA windowed path elsewhere) instead of scattering
        return "projection", 0
    return "mixed", 0


PROJECTION_MIN_ROWS = 1 << 20   # below this the one-time sort outweighs wins


def _projection_strategy(proj: Projection, kernels: Sequence[AggKernel],
                         col_dtypes: Dict, num_total: int) -> Tuple[str, int]:
    """Inner reduction over the sorted compacted layout: the fused pallas
    kernel on TPU, the XLA windowed path elsewhere, scatter as last resort."""
    span = proj.max_span
    if pallas_agg.usable(kernels, col_dtypes, span, num_total):
        return "pallas", pallas_agg.canonical_span(span)
    for w in WINDOW_CHOICES:
        if span <= w:
            return "windowed", w
    return "mixed", 0


def _blocked_reduce(arrays: Dict, mask, key, kernels: Sequence[AggKernel],
                    num_total: int):
    """Scanned masked broadcast-reduce over row blocks. Returns (counts,
    per-kernel states) shaped exactly like the scatter path's."""
    import jax
    import jax.numpy as jnp

    n = mask.shape[0]
    fields = sorted({k.spec.field for k in kernels
                     if getattr(k.spec, "field", None) in arrays})
    # rows per scan step scale inversely with the group space so the [rows,
    # G] working set stays ~4M cells; tiny G (timeseries) streams in big
    # steps instead of paying scan overhead every 2048 rows
    block_rows = min(65536, max(BLOCK_ROWS, (1 << 22) // max(num_total, 1)))
    c = max(1, -(-n // block_rows))
    padded = c * block_rows

    def pad(a, fill=0):
        if padded == n:
            return a
        return jnp.concatenate(
            [a, jnp.full((padded - n,), fill, a.dtype)])

    keyb = pad(key).reshape(c, block_rows)
    maskb = pad(mask, False).reshape(c, block_rows)
    colsb = {f: pad(arrays[f]).reshape(c, block_rows) for f in fields}
    iota = jnp.arange(num_total, dtype=key.dtype)

    # data-derived zero so carries inherit the varying-axis type under
    # shard_map (a plain zeros init trips the scan vma check); derive from
    # both key and mask — the key can be shard-invariant (all-granularity)
    # while the row mask is sharded
    vary0 = (key[0] * 0) + (mask[0] * 0).astype(key.dtype)
    inits = [jax.tree.map(lambda x: x + vary0.astype(x.dtype),
                          k.blocked_init(num_total, arrays))
             for k in kernels]
    count0 = jnp.zeros(num_total, jnp.int32) + vary0.astype(jnp.int32)

    def body(carry, xs):
        cnt, states = carry
        kb, mb = xs[0], xs[1]
        cblk = dict(zip(fields, xs[2:]))
        valid = (kb[:, None] == iota[None, :]) & mb[:, None]
        # pin the accumulation dtype: under x64 an int32 sum promotes to
        # int64 and the scan carry dtype check fails
        cnt = cnt + valid.astype(jnp.int32).sum(axis=0, dtype=jnp.int32)
        states = tuple(k.blocked_step(s, cblk, valid, num_total)
                       for k, s in zip(kernels, states))
        return (cnt, states), None

    xs = (keyb, maskb) + tuple(colsb[f] for f in fields)
    (counts, states), _ = jax.lax.scan(body, (count0, tuple(inits)), xs)
    return counts, tuple(k.blocked_finish(s)
                         for k, s in zip(kernels, states))


def _structure_sig(spec: GroupSpec, n_intervals: int, filter_node, kernels,
                   vc_plans, packs: Tuple = (), cascades: Tuple = ()) -> str:
    dims_sig = ",".join(
        f"{d.column}:{'remap' if d.remap is not None else 'raw'}" for d in spec.dims)
    # repr(expr) is the rewritten AST structure — two segments share a
    # jitted program only when their LUT sites line up
    vc_sig = ";".join(f"{name}={expr!r}:{out_type}:l{n_luts}"
                      for name, expr, out_type, n_luts in vc_plans)
    return "|".join([
        f"bucket={spec.bucket_mode}",
        f"key={spec.key_mode}",
        f"dims={dims_sig}",
        f"iv={n_intervals}",
        f"vc={vc_sig}",
        f"filt={filter_node.signature() if filter_node else 'none'}",
        f"aggs={';'.join(k.signature() for k in kernels)}",
        f"total={spec.num_total}",
        f"strat={spec.strategy}:{spec.window}",
        # the pack descriptor (data/packed.plan_columns) is program
        # structure: packed inputs have different treedefs/shapes, so two
        # executions share a jitted program only when their packing agrees
        f"packs={packs}",
        # the cascade descriptor (data/cascade.plan_columns) likewise:
        # RLE/delta/FOR/LZ4 inputs are distinct treedefs per descriptor
        f"casc={cascades}",
    ])


def _build_device_fn(spec: GroupSpec, n_intervals: int,
                     filter_node: Optional[FilterNode],
                     kernels: List[AggKernel], vc_plans: Tuple = ()):
    """Build the per-segment program: the shared body (traced_segment) with
    the segment's origins taken off the head of `aux` (_assemble_aux).
    Structure-only closure: every segment-specific constant arrives via
    `aux` (device arrays), so one jitted callable serves every segment with
    the same structure.

    The "megakernel" strategy's callable takes a third `carries` argument —
    the previous execution's raw accumulator grids, donated
    (donate_argnums) when the backend supports donation so repeated/
    standing executions reuse the same HBM buffers (the kernel
    re-initializes them at grid step 0, so donated reuse is bit-identical
    to fresh zeros). `keep_unused` holds the carries in the signature:
    they exist purely as donatable buffers, never as data."""
    import jax

    def fn(arrays: Dict[str, object], aux: Tuple, carries: Tuple = ()):
        time0, iv_rel, bucket_off = aux[:3]
        return traced_segment(spec, filter_node, kernels, vc_plans, arrays,
                              time0, iv_rel, bucket_off, aux[3:])

    # the program's stable name: the profiler's modules and the dispatch
    # spans' `program` read `seg_agg_<strategy>`, never a shape
    named_program(fn, program_name("seg_agg", spec.strategy))
    if spec.strategy == "megakernel":
        if megakernel.donation_enabled():
            return jax.jit(fn, keep_unused=True, donate_argnums=(2,))
        return jax.jit(fn, keep_unused=True)
    return jax.jit(fn)


def _assemble_aux(spec: GroupSpec, segment: Segment,
                  intervals: Sequence[Interval],
                  filter_node: Optional[FilterNode], kernels: List[AggKernel],
                  vc_plans: Tuple = (),
                  vc_luts: Sequence[np.ndarray] = ()) -> Tuple:
    """The per-segment program's aux: the segment's own origins (the head
    `_build_device_fn.fn` takes off) and then the stacked layout. `vc_plans`
    rides for the call shape it shares with `_build_device_fn`."""
    time0s, iv_rel, bucket_off = stacked_origins([segment], [intervals],
                                                 [spec])
    return (time0s[0], iv_rel[0], bucket_off[0]) + assemble_stacked_aux(
        spec, filter_node.aux_arrays() if filter_node is not None else (),
        [a for k in kernels for a in k.aux_arrays()], vc_luts)


# ---------------------------------------------------------------------------
# The one traced body and its inputs. The per-segment program, the batched
# program (engine/batching.py, the body UNROLLED inside one jit) and the
# sharded shard_map program (parallel/distributed.py, vmapped within each
# shard) all run traced_segment over assemble_stacked_aux's layout, with
# stacked_origins' per-segment arguments.
# ---------------------------------------------------------------------------

def traced_segment(spec: GroupSpec, filter_node: Optional[FilterNode],
                   kernels: Sequence[AggKernel], vc_plans: Tuple,
                   arrays, time0, iv_rel, bucket_off, aux):
    """THE traced per-segment body. The plan's structure comes first (four
    arguments the stacked programs close over, never traced); a segment's
    origins (time0, relative interval bounds, bucket origin) arrive as
    arguments — mapped over the stack, or off the head of the per-segment
    program's aux — so one body serves every segment of a structure.
    Returns RAW (counts, states): callers apply device_post/host_post as
    their merge discipline requires. Only the per-segment program stages
    `__key` (host key mode) or `__bucket` (host bucket mode)."""
    import jax.numpy as jnp

    it = iter(aux)
    # decode compressed columns at the program top: HBM keeps the
    # packed/RLE/delta/LZ4 representation (pooled blocks and stacked slots
    # alike), XLA fuses the decode into every consumer; the pallas strategy
    # additionally receives the raw packed words (packed_cols, FOR
    # included) and unpacks per tile inside the kernel instead
    # (data/cascade.split_resident is the ONE decode entry point)
    packed_cols, arrays = cascade_mod.split_resident(arrays)
    t = arrays["__time_offset"]
    mask = arrays["__valid"]

    if vc_plans:
        # expressions may reference absolute __time — the one consumer of
        # 64-bit per-row time (epoch millis overflow int32; x64 is globally
        # on via engine/__init__)
        arrays = eval_virtual_columns(
            arrays, t.astype(jnp.int64) + time0, vc_plans, it)  # druidlint: disable=x64-dtype

    # int32 relative bounds — no 64-bit elementwise time math
    within = (t[:, None] >= iv_rel[None, :, 0]) \
        & (t[:, None] < iv_rel[None, :, 1])
    mask = mask & jnp.any(within, axis=1)

    dims = spec.dims
    if spec.key_mode == "host":
        # the host-fused key already holds bucket and dims
        key = arrays["__key"]
        mask = mask & (key >= 0)
        dims = ()
    elif spec.bucket_mode == "all":
        key = jnp.zeros(t.shape, dtype=jnp.int32)
    elif spec.bucket_mode == "uniform":
        # int32 bucket math: offsets are int32 by construction and uniform
        # periods (≤ week) fit int32; 64-bit div would be limb-emulated on
        # TPU. The origin is (offset within a period, whole periods), so
        # neither term leaves int32 however far back the first bucket lies
        period = next(it)
        nb = next(it)  # num buckets as device scalar
        b = (t - bucket_off[0]) // period - bucket_off[1]
        mask = mask & (b >= 0) & (b < nb)
        key = b.astype(jnp.int32)
    else:
        key = arrays["__bucket"]
        mask = mask & (key >= 0)

    return fuse_filter_update(arrays, mask, key, it, dims, filter_node,
                              kernels, spec.num_total,
                              strategy=spec.strategy, window=spec.window,
                              packed_cols=packed_cols or None)


def assemble_stacked_aux(spec: GroupSpec, f_aux: Sequence[np.ndarray],
                         k_aux: Sequence[np.ndarray],
                         vc_luts: Sequence[np.ndarray] = ()) -> Tuple:
    """Aux stream in the order of traced_segment's reads: interval
    bounds and bucket origins are per-segment arguments (NOT aux); only
    plan constants live here. vc string-LUTs lead (consumed inside
    eval_virtual_columns first); a host-fused key reads no bucket or dim
    constants."""
    aux: List[np.ndarray] = list(vc_luts)
    if spec.key_mode == "dense":
        if spec.bucket_mode == "uniform":
            aux.append(np.asarray(spec.uniform_period, dtype=np.int32))
            aux.append(np.asarray(spec.num_buckets, dtype=np.int32))
        for d in spec.dims:
            if d.column is None:
                continue
            if d.remap is not None:
                aux.append(d.remap.astype(np.int32))
            aux.append(np.asarray(d.cardinality, dtype=np.int32))
    aux.extend(f_aux)
    aux.extend(k_aux)
    return tuple(aux)


def stacked_origins(segments: Sequence[Segment],
                    intervals_per_segment: Sequence[Sequence[Interval]],
                    specs: Sequence[GroupSpec], K: Optional[int] = None
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-segment origins of the traced body, `[K]`-leading (K pads past
    the segments with zeros: no interval, so no row): each segment's start
    (`time0s`, int64), the query intervals RELATIVE to it (`iv_rel`, int32
    `[K, n, 2]`) and its uniform bucket origin (`bucket_off`, int32
    `[K, 2]`). The device program stays in int32 offset space (64-bit
    elementwise time math is limb-emulated on TPU), so everything relative
    is brought into int32 HERE, once. An interval bound clips: beyond ±24.8
    days of the segment's start it lies outside every row offset anyway. A
    bucket origin does not clip — a query's first bucket may start months
    before a segment whose rows it still counts — it SPLITS into (offset
    within a period, whole periods): row offset `t` falls in bucket
    `(t - rest) // period - whole`, exact at any distance. Only `whole`
    clips, and only where no row can pass the interval bounds."""
    K = len(segments) if K is None else K
    clip_lo, clip_hi = -(2**31) + 1, 2**31 - 1
    n_iv = max((len(ivs) for ivs in intervals_per_segment), default=0)
    time0s = np.zeros((K,), dtype=np.int64)
    iv_rel = np.zeros((K, max(n_iv, 1), 2), dtype=np.int32)
    bucket_off = np.zeros((K, 2), dtype=np.int32)
    for i, (s, ivs, spec) in enumerate(zip(segments, intervals_per_segment,
                                           specs)):
        t0 = time0s[i] = s.interval.start
        for j, ivl in enumerate(ivs):
            iv_rel[i, j, 0] = min(max(ivl.start - t0, clip_lo), clip_hi)
            iv_rel[i, j, 1] = min(max(ivl.end - t0, clip_lo), clip_hi)
        if spec.bucket_mode == "uniform":
            whole, rest = divmod(int(spec.bucket_starts[0]) - t0,
                                 int(spec.uniform_period))
            bucket_off[i] = rest, min(max(whole, clip_lo), clip_hi)
    return time0s, iv_rel, bucket_off


def common_window(windows) -> int:
    """The window every stacked segment agrees on: the widest of the
    segments' own (`windowed_window`, given lazily), 0 as soon as one
    segment has none."""
    w_all = 0
    for w in windows:
        if not w:
            return 0
        w_all = max(w_all, w)
    return w_all


def aux_equal(a: Sequence[np.ndarray], b: Sequence[np.ndarray]) -> bool:
    """Plan-constant equality across segments (stacked-eligibility checks)."""
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape or not np.array_equal(x, y):
            return False
    return True


def keydims_equal(a: Sequence[KeyDim], b: Sequence[KeyDim]) -> bool:
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if x.column != y.column or x.cardinality != y.cardinality:
            return False
        if (x.remap is None) != (y.remap is None):
            return False
        if x.remap is not None and not np.array_equal(x.remap, y.remap):
            return False
    return True


def needed_columns(segment: Segment, aggs: Sequence[AggregatorSpec],
                   virtual_columns: Sequence, filter_node,
                   kernels: Sequence[AggKernel],
                   extra_columns: Sequence[str] = ()):
    """Returns (all real-column names the filter, aggregators and virtual
    columns reference, the subset present in `segment` — i.e. the columns
    to stage whatever fuses the key). Needs come from the PLANNED filter
    tree and kernels, not the raw filter's: subtrees compiled to device
    bitmaps (filters.DeviceBitmapNode) read resident words, so filter-only
    dimensions stop staging; a kernel without planned needs (None) falls
    back to its aggregator's."""
    from druid_tpu.utils.expression import parse_expression
    needed = set(extra_columns)
    if filter_node is not None:
        needed |= filter_node.required_device_columns()
    for a, k in zip(aggs, kernels):
        kc = k.required_device_columns()
        needed |= a.required_columns() if kc is None else kc
    for v in virtual_columns:
        needed |= parse_expression(v.expression).required_columns()
    needed -= {v.name for v in virtual_columns}
    needed -= {"__time", "__time_offset", "__valid"}
    present = tuple(sorted(c for c in needed
                           if c in segment.dims or c in segment.metrics))
    return needed, present


@dataclass
class GroupPlan:
    """The host-side planning product for one segment's grouped aggregation
    — everything derived BEFORE staging. Built by plan_grouped_aggregate,
    the ONE planner of all three builders: the per-segment path runs it,
    the batched path (engine/batching.py) groups plans into shape buckets
    and hands a straggler's plan back so nothing is planned twice, the mesh
    (parallel/distributed.py) compares the plans of a query's segments.

    Single-use per execution: run_grouped_aggregate mutates it (strategy
    selection, the projection rewrite, megakernel conversion) — do not
    share one plan across runs."""
    spec: "GroupSpec"
    filter_node: object
    kernels: List[AggKernel]
    vc_plans: Tuple
    vc_luts: List[np.ndarray]
    n_slots: int                        # `__fbmpN` bitmap slots assigned
    f_aux: Sequence[np.ndarray]         # the PLANNED filter's aux constants
    k_aux: List[np.ndarray]             # the kernels' aux constants
    needed: set                         # referenced real columns, held or not
    base_columns: Tuple[str, ...]       # staged whatever fuses the key
    columns: Tuple[str, ...]            # + the dims a dense key fuses on device
    col_dtypes: Dict[str, np.dtype]     # staged dtype of every program input
    perm: Optional[np.ndarray] = None   # a sorted projection's row order
    perm_key: Optional[Tuple] = None


def _staged_col_dtypes(segment: Segment, spec: "GroupSpec",
                       columns: Sequence[str]) -> Dict[str, np.dtype]:
    """Dtype each input of the program stages as — what select_strategy is
    asked with BEFORE anything stages (a projection stages a permuted
    layout, so there is no block to read dtypes from)."""
    i32 = np.dtype(np.int32)
    out = {"__time_offset": i32, "__valid": np.dtype(bool)}
    for c in columns:
        out[c] = i32 if c in segment.dims \
            else np.dtype(segment.staged_dtype(c))
    if spec.key_mode == "host":
        out["__key"] = i32
    else:
        out.update((d.column, i32) for d in spec.dims
                   if d.host_ids is not None)
        if spec.bucket_mode == "host":
            out["__bucket"] = i32
    return out


def plan_grouped_aggregate(segment: Segment, intervals: Sequence[Interval],
                           granularity: Granularity,
                           dims: Sequence[KeyDim],
                           aggs: Sequence[AggregatorSpec], flt,
                           virtual_columns: Sequence = (),
                           extra_columns: Sequence[str] = ()) -> GroupPlan:
    """Host-side planning for one segment (no staging, no device work)."""
    vc_plans, vc_luts = plan_virtual_columns(segment, virtual_columns)
    filter_node = simplify_node(plan_filter(flt, segment, virtual_columns))
    kernels = [make_kernel(a, segment) for a in aggs]
    # globally unique bitmap slots across the query filter AND the
    # filtered-aggregator trees — their staged word arrays share one
    # `__fbmpN` namespace in the arrays dict
    n_slots = filters_mod.assign_bitmap_slots(filter_node, kernels)
    spec = make_group_spec(segment, intervals, granularity, dims)
    needed, base_columns = needed_columns(
        segment, aggs, virtual_columns, filter_node, kernels, extra_columns)
    # a dense key fuses its dims on the device: their id columns stage too
    # (a derived id column, `host_ids`, stages from its host array)
    key_columns = {d.column for d in spec.dims
                   if spec.key_mode == "dense" and d.column is not None}
    columns = tuple(sorted(key_columns.intersection(segment.dims)
                           .union(base_columns)))
    return GroupPlan(
        spec=spec, filter_node=filter_node, kernels=kernels,
        vc_plans=vc_plans, vc_luts=vc_luts, n_slots=n_slots,
        f_aux=filter_node.aux_arrays() if filter_node is not None else [],
        k_aux=[a for k in kernels for a in k.aux_arrays()],
        needed=needed | key_columns, base_columns=base_columns,
        columns=columns,
        col_dtypes=_staged_col_dtypes(segment, spec, columns))


def _host_post(kernel: AggKernel, state, segment: Segment):
    return kernel.host_post(state, segment)


class StackedSlot(NamedTuple):
    """Slot `index` of a stacked program's ONE output tree `out`, (counts,
    states) with every leaf `[K, ...]`, as a chunk's entries name it
    (batching._enqueue_batch). The K entries of a chunk share `out` by
    identity: it is started, waited for, fetched and released once, and
    the fetch hands each entry row `index` of the one host copy."""
    out: Tuple
    index: int


def _distinct_outputs(outs: Sequence) -> List:
    """Each distinct output tree of `outs` once, by identity, in order: a
    plain (counts, states) is its own tree, a chunk's K StackedSlots name
    one."""
    trees = (o.out if isinstance(o, StackedSlot) else o for o in outs)
    return list({id(t): t for t in trees}.values())


def fetch_partials(targets: Sequence[Tuple], outs: Sequence,
                   post=_host_post, programs: int = 1,
                   **attrs) -> List[SegmentPartial]:
    """Device results → host partials, under the ONE `engine/fetch`.
    `targets` are (segment, spec, kernels) and `outs` their (counts,
    states), or a StackedSlot of a stacked program's one output — of one
    program or of every program a request enqueued;
    `post(kernel, state, segment)` is the one thing that differs between
    the builders: a per-segment result takes the kernel's host_post (the
    default), a mesh result that the collectives already merged its
    host_from_device. This is where the host blocks for the device, and
    the span's three children say for what: `engine/fetch/wait` is the
    sync — `jax.block_until_ready` over each distinct output tree once,
    the wait the `device_get` behind it would have made for the same
    programs (host arrays, as the run-domain route leaves them, pass
    through both) — `engine/fetch/d2h` the ONE `jax.device_get` (what is
    left of the copies each enqueue started, and the numpy
    materialisation), `engine/fetch/post` the partials' construction
    (`post` over host arrays; a stacked slot's leaves are row views of its
    tree's one host copy). Three spans a fetch, none a segment, and
    nothing of the fetch outside them. `bytes` is what came back (counted
    under `d2h`, a stacked tree once), `programs` how many enqueued
    programs' outputs these are."""
    import jax
    with trace_span("engine/fetch", programs=programs, **attrs) as sp:
        with trace_span("engine/fetch/wait"):
            trees = _distinct_outputs(outs)
            jax.block_until_ready(trees)
        with trace_span("engine/fetch/d2h"):
            host_trees = jax.device_get(trees)
            if sp is not None:
                sp.attrs["bytes"] = entry_bytes(host_trees)
        with trace_span("engine/fetch/post"):
            host = {id(t): h for t, h in zip(trees, host_trees)}

            def host_of(out):
                if isinstance(out, StackedSlot):
                    return jax.tree.map(lambda x: x[out.index],
                                        host[id(out.out)])
                return host[id(out)]
            partials = []
            for (segment, spec, kernels), out in zip(targets, outs):
                counts, states = host_of(out)
                partials.append(SegmentPartial(
                    segment=segment, spec=spec,
                    counts=np.asarray(counts, dtype=np.int64),
                    states={k.name: post(k, st, segment)
                            for k, st in zip(kernels, states)},
                    kernels=kernels))
            return partials


def run_grouped_aggregates(work: Sequence, check=None
                           ) -> List[SegmentPartial]:
    """Run a request's device programs: ENQUEUE every one, then fetch ONCE.

    `work` is a sequence of zero-argument enqueues, run in order. Each
    returns what it left pending, a segment an entry: one entry
    (`enqueue_grouped_aggregate`) or a list of them (a stacked chunk,
    batching._enqueue_batch). An entry is a finished host partial
    (`constFalse`) or `((segment, spec, kernels), out)`, what
    `fetch_partials` takes: `out` a segment's own `(counts, states)` or a
    StackedSlot of its chunk's one output. Returns the partials in the
    order of the entries. `check` (cancel/timeout probe) runs between
    enqueues and once before the fetch.

    Nothing waits for the device between two enqueues: the device runs
    program k while the host plans, stages and enqueues program k + 1, and
    the queued programs run on while another request's thread holds the
    interpreter lock. The outputs' copies to the host start at the enqueue
    (`copy_to_host_async`, where an output has it; the walk over a
    program's distinct output leaves that starts them is
    `engine/fetch/start`, one span a program enqueued, with `leaves` and
    `bytes`) and the request's results come back under ONE
    `engine/fetch` whose `programs` says how
    many enqueued programs it collected. What is enqueued and not fetched
    is bounded by contracts.PENDING_FETCH_BYTES of outputs: at the bound
    the pending programs are fetched (one more `engine/fetch`) and the
    enqueues go on. After each fetch the device outputs are RELEASED under
    `engine/fetch/release` (segments, and traced `arrays`: the distinct
    device arrays dropped): this function's `pending` is their one owner,
    so that is where their destructors run — one a distinct leaf, which
    for a stacked chunk is one a leaf of the chunk, not of each segment.
    An enqueue that raises surfaces its error; the outputs of the programs
    enqueued before it are dropped."""
    results: List[Optional[SegmentPartial]] = []
    pending: List[Tuple] = []   # (slot in results, target, out), un-fetched
    programs = pending_bytes = 0

    def enqueue_next(enqueue) -> Optional[int]:
        """One enqueue's entries into `results` / `pending`; returns the
        bytes of the outputs it left pending, None where no program ran.
        A function of its own: none of its locals keeps an output alive
        past the fetch, so `pending` is the outputs' one owner."""
        entries = enqueue()
        first = len(pending)
        for entry in entries if isinstance(entries, list) else [entries]:
            if isinstance(entry, SegmentPartial):
                results.append(entry)
            else:
                pending.append((len(results),) + entry)
                results.append(None)
        if len(pending) == first:
            return None
        return _start_host_copies([out for _, _, out in pending[first:]])

    def fetch(programs):
        if pending:
            slots, targets, outs = zip(*pending)
            pending.clear()
            for slot, partial in zip(slots, fetch_partials(
                    targets, outs, programs=programs)):
                results[slot] = partial
            # the device outputs' last reference goes HERE, under a name:
            # one destructor a distinct leaf, each of which hands the
            # interpreter lock over — with eight request threads ~1 ms of
            # wall a leaf (PERF.md §5, PR 36); a stacked chunk's outputs
            # are one array a leaf, not K (PR 37)
            with trace_span("engine/fetch/release",
                            segments=len(slots)) as sp:
                if sp is not None:
                    sp.attrs["arrays"] = _device_arrays(outs)
                del outs

    for n, enqueue in enumerate(work):
        if check is not None and n:
            check()
        started = enqueue_next(enqueue)
        if started is not None:
            programs += 1
            pending_bytes += started
            if pending_bytes >= PENDING_FETCH_BYTES:
                fetch(programs)
                programs = pending_bytes = 0
    if check is not None:
        check()
    fetch(programs)
    return results


def _start_host_copies(outs: Sequence) -> int:
    """Start the copies to the host of one enqueued program's outputs
    (`copy_to_host_async`, where a leaf has it) under `engine/fetch/start`
    (leaves, bytes: of the distinct output trees, so a chunk's stacked
    leaves count once); returns the bytes."""
    import jax
    with trace_span("engine/fetch/start") as sp:
        leaves = jax.tree_util.tree_leaves(_distinct_outputs(outs))
        nbytes = 0
        for leaf in leaves:
            nbytes += getattr(leaf, "nbytes", 0)
            start_copy = getattr(leaf, "copy_to_host_async", None)
            if start_copy is not None:
                start_copy()
        if sp is not None:
            sp.attrs.update(leaves=len(leaves), bytes=nbytes)
    return nbytes


def _device_arrays(outs: Sequence) -> int:
    """How many distinct device arrays `outs` hold."""
    import jax
    return sum(isinstance(leaf, jax.Array) for leaf in
               jax.tree_util.tree_leaves(_distinct_outputs(outs)))


def run_grouped_aggregate(segment: Segment, intervals: Sequence[Interval],
                          granularity: Granularity, dims: Sequence[KeyDim],
                          aggs: Sequence[AggregatorSpec],
                          flt, extra_columns: Sequence[str] = (),
                          virtual_columns: Sequence = (),
                          plan: Optional[GroupPlan] = None) -> SegmentPartial:
    """Execute the grouped aggregation for one segment; returns host
    partials: `enqueue_grouped_aggregate` and the fetch of that one
    program (`run_grouped_aggregates` over one enqueue). A request of many
    segments hands all its enqueues to `run_grouped_aggregates` instead,
    which fetches once."""
    partial, = run_grouped_aggregates([functools.partial(
        enqueue_grouped_aggregate, segment, intervals, granularity, dims,
        aggs, flt, extra_columns=extra_columns,
        virtual_columns=virtual_columns, plan=plan)])
    return partial


def enqueue_grouped_aggregate(segment: Segment,
                              intervals: Sequence[Interval],
                              granularity: Granularity,
                              dims: Sequence[KeyDim],
                              aggs: Sequence[AggregatorSpec],
                              flt, extra_columns: Sequence[str] = (),
                              virtual_columns: Sequence = (),
                              plan: Optional[GroupPlan] = None):
    """Plan, stage and ENQUEUE one segment's grouped aggregation; nothing
    waits for the device. Returns an entry of `run_grouped_aggregates`: a
    finished host partial when no program runs (`constFalse`), else
    `((segment, spec, kernels), (counts, states))` with the outputs still
    on their way. `plan` (a GroupPlan from plan_grouped_aggregate over the
    SAME arguments) skips re-planning — the batched path's stragglers pass
    the plan it already built for bucket grouping.

    The phases run one after another, each a function: `_plan_segment`,
    `_stage_segment`, `_dispatch_segment`. Traced, the segment's host time
    lies under one `engine/segment` span whose children are consecutive
    phases (at most 7 spans a warm segment):
    `engine/plan` (group spec, the code-domain probe, strategy, projection
    lookup — with an `engine/projection/build` child when the projection
    is built),
    `engine/filter/words` (megakernel conversion, then the staging of
    filter words; `built` = arrays built rather than found resident),
    `engine/stage` (columns and derived keys; `pool/h2d` nests here),
    `engine/build` (aux, signature, program cache, the kernel build) and
    `engine/dispatch` (the ENQUEUE: dispatch is asynchronous). The wait
    for the device is the request's `engine/fetch`, beside the segments
    and not under one (see `fetch_partials`)."""
    with trace_span("engine/segment", rows=segment.n_rows) as seg_span:
        plan, route = _plan_segment(segment, intervals, granularity, dims,
                                    aggs, flt, extra_columns,
                                    virtual_columns, plan)
        spec, kernels = plan.spec, plan.kernels
        if route == "constFalse":
            # nothing matches — skip the device
            entry = SegmentPartial(
                segment=segment, spec=spec,
                counts=np.zeros(spec.num_total, dtype=np.int64),
                states={k.name: k.empty_state(spec.num_total)
                        for k in kernels},
                kernels=kernels)
        else:
            if route == "runDomain":
                out = cascade_mod.try_run_domain(
                    segment, intervals, granularity, spec, kernels, flt,
                    virtual_columns)
            else:
                staged = _stage_segment(segment, plan)
                out = _dispatch_segment(segment, intervals, plan, *staged)
                route = spec.strategy
            entry = (segment, spec, kernels), out
        if seg_span is not None:
            # formatted only when traced: untraced segments pay no str()
            seg_span.attrs.update(segment=str(segment.id), strategy=route)
        return entry


def _plan_segment(segment, intervals, granularity, dims, aggs, flt,
                  extra_columns, virtual_columns,
                  plan: Optional[GroupPlan]) -> Tuple[GroupPlan, Optional[str]]:
    """`engine/plan`: the plan complete before anything stages — strategy
    and projection included — and the route when no row program runs:
    `constFalse` (the filter folded away every row) or `runDomain`."""
    with trace_span("engine/plan") as plan_span:
        if plan is None:
            plan = plan_grouped_aggregate(segment, intervals, granularity,
                                          dims, aggs, flt, virtual_columns,
                                          extra_columns)
        spec, filter_node, kernels = plan.spec, plan.filter_node, plan.kernels

        if isinstance(filter_node, ConstNode) and not filter_node.value:
            return plan, "constFalse"

        # code-domain fast path (data/cascade.py): when every referenced
        # column is constant within one shared run partition and the query
        # shape allows it, the whole aggregation executes over run metadata
        # — no row-width column stages, nothing decodes, and the results
        # are bit-identical to the row program (exact int arithmetic,
        # identical identities). batching._plan_for routes eligible
        # segments here. The probe plans it (memoized on the spec); on a
        # cold segment it computes the columns' run tables, seconds of
        # set-up a 5M-row segment: the span says how long (`runDomainMs`)
        if cascade_mod.enabled():
            t0 = time.monotonic()
            run_domain = cascade_mod.run_domain_probe(
                segment, intervals, granularity, spec, kernels, flt,
                virtual_columns)
            if plan_span is not None:
                plan_span.attrs["runDomainMs"] = round(
                    (time.monotonic() - t0) * 1000.0, 3)
            if run_domain:
                return plan, "runDomain"

        spec.strategy, spec.window = select_strategy(
            spec, kernels, plan.col_dtypes, _padded_rows(segment),
            lambda: windowed_window(segment, intervals, granularity, spec))

        if spec.strategy == "projection":
            proj = build_projection(segment, intervals, granularity, spec)
            spec.key_mode = "host"
            spec.host_keys = proj.keys
            spec.host_unique = proj.unique
            spec.num_total = pad_pow2(max(len(proj.unique), 1))
            # key prefused: dim columns stay host-side, `__key` stages
            plan.columns = plan.base_columns
            plan.col_dtypes = _staged_col_dtypes(segment, spec, plan.columns)
            spec.strategy, spec.window = _projection_strategy(
                proj, kernels, plan.col_dtypes, spec.num_total)
            plan.perm = proj.order
            plan.perm_key = _key_structure("projection", granularity,
                                           intervals, spec.dims)
            spec.host_keys_cache = plan.perm_key
            # bitmap subtrees STAY on the words path: the projection's
            # permuted row layout stages its own words under a
            # permutation-digest pool key (filters.bitmap_pool_key), so the
            # bit test aligns with the permuted columns instead of forcing
            # a column-path re-plan
    return plan, None


def _padded_rows(segment: Segment) -> int:
    """Rows a segment's block stages at (Segment.device_block's default
    alignment), known before it stages."""
    return max(DEFAULT_ROW_ALIGN,
               -(-segment.n_rows // DEFAULT_ROW_ALIGN) * DEFAULT_ROW_ALIGN)


def _stage_segment(segment: Segment, plan: GroupPlan) -> Tuple:
    """`engine/filter/words` and `engine/stage`: what the program reads,
    resident — (arrays, packs, cascades)."""
    spec, kernels = plan.spec, plan.kernels
    perm, perm_key = plan.perm, plan.perm_key
    padded_rows = _padded_rows(segment)
    # megakernel conversion (engine/megakernel.py): bitmap subtrees whose
    # combined words are not already resident fuse INLINE — per-leaf words
    # stay resident, the algebra evaluates inside the ONE aggregation
    # program, and the separate fill dispatch disappears. Resident subtrees
    # keep the cached bit-test path (also one dispatch). Opt-out:
    # DRUID_TPU_MEGAKERNEL=0.
    pdg = filters_mod.perm_digest(perm_key)
    with filters_mod.words_span():
        # conversion only: nothing stages here, the words stage below
        if megakernel.enabled():
            plan.filter_node = megakernel.megaize(plan.filter_node, segment,
                                                  padded_rows, pdg)
            megakernel.megaize_kernels(kernels, segment, padded_rows, pdg)
        else:
            megakernel.record_disabled_fallback(plan.filter_node, kernels)
    filter_node = plan.filter_node

    with trace_span("engine/stage"):
        # cascade + pack descriptors of the staged column set: must be
        # derived IDENTICALLY to device_block's own planning
        # (cascade.plan_pair, the one shared derivation), and both join the
        # jit-cache signature — a cascade-encoded, packed, and decoded
        # staging of the same structure are different programs
        columns = list(plan.columns)
        cascades, packs = cascade_mod.plan_pair(segment, columns,
                                                permuted=perm is not None)
        block = segment.device_block(columns, perm=perm, perm_key=perm_key)

        arrays = dict(block.arrays)
        if spec.key_mode == "dense":
            for d in spec.dims:
                if d.host_ids is not None:
                    # derived id column (numeric dimension): staged via the
                    # bounded device cache like any other derived key column
                    arrays[d.column] = _pad_device_cached(
                        segment, d.ids_key, d.host_ids, block.padded_rows, 0)
        if spec.key_mode == "host":
            # derived projection keys ride the cascade FOR rung: their
            # value range [-1, num_total) is known exactly, so they
            # range-pack at plan-determined width
            # (data/cascade.for_encode_derived)
            arrays["__key"] = _pad_device_cached(
                segment, spec.host_keys_cache, spec.host_keys,
                block.padded_rows, -1, value_range=(-1, spec.num_total - 1))
        elif spec.bucket_mode == "host":
            arrays["__bucket"] = _pad_device_cached(
                segment, spec.host_bucket_cache, spec.host_bucket_ids,
                block.padded_rows, -1,
                value_range=(-1, spec.num_buckets - 1))
    with filters_mod.words_span():
        # resident filter-bitmap words (engine/filters.py device-bitmap
        # path): cached per (segment, filter structure, aux digest,
        # permutation digest) in the same pool; filtered-aggregator trees
        # stage alongside the query filter's, and the projection path
        # stages PERMUTED words
        arrays.update(filters_mod.stage_device_bitmaps(
            segment, filter_node, block.padded_rows, kernels=kernels,
            perm=perm, perm_key=perm_key))
        # per-leaf mask words for inline-fused (mega) subtrees
        arrays.update(megakernel.stage_mega_leaves(
            segment, filter_node, kernels, block.padded_rows,
            perm=perm, perm_key=perm_key))

    # the fused pallas variant: when the projection strategy landed on the
    # pallas kernel AND the tree carries top-level AND-conjunct mega nodes,
    # the mask rides into the kernel as words (the 32x mask-VMEM cut) and
    # the partial grids become donatable carries
    if spec.strategy == "pallas" \
            and megakernel.split_for_kernel(filter_node)[0]:
        spec.strategy = "megakernel"
    return arrays, packs, cascades


def _dispatch_segment(segment: Segment, intervals: Sequence[Interval],
                      plan: GroupPlan, arrays: Dict, packs: Tuple,
                      cascades: Tuple) -> Tuple:
    """`engine/build` and `engine/dispatch`: the program found or built and
    ENQUEUED — returns its device (counts, states), which the request's
    `fetch_partials` waits for."""
    spec, filter_node, kernels = plan.spec, plan.filter_node, plan.kernels
    vc_plans, vc_luts = plan.vc_plans, plan.vc_luts
    col_dtypes = plan.col_dtypes
    aux = None
    while True:
        # pallas-class programs are BUILT before they run (the latch below
        # catches exactly the build's failures); XLA strategies compile
        # inside their first call
        kernel_class = spec.strategy in ("pallas", "megakernel")
        program = program_name("seg_agg", spec.strategy)
        carried, donated, donated_nbytes = None, False, 0
        try:
            with trace_span("engine/build", program=program) as build_span:
                if aux is None:
                    aux = _assemble_aux(spec, segment, intervals,
                                        filter_node, kernels, vc_plans,
                                        vc_luts)
                sig = _structure_sig(spec, len(intervals), filter_node,
                                     kernels, vc_plans, packs, cascades)
                if spec.strategy == "megakernel":
                    # donation changes the jit construction
                    # (donate_argnums) and the carry handoff changes the
                    # carries treedef (empty vs full tuple), so both key
                    # the program cache; carry buffers key off the same sig
                    sig += f"|mk={int(megakernel.donation_enabled())}" \
                        f"{int(megakernel.carry_enabled())}"
                with _JIT_CACHE_LOCK:
                    fn = _JIT_CACHE.get(sig)
                    # the builder-idiom miss IS the compile event: a
                    # pallas-class program traces, lowers and compiles in
                    # _build_kernel_program (here, under engine/build), an
                    # XLA-strategy one inside its first call (under
                    # engine/dispatch). The engine/compile span nests
                    # where the compile happens — no extra syncs
                    compiled = fn is None
                    if fn is None:
                        fn = _build_device_fn(spec, len(intervals),
                                              filter_node, kernels, vc_plans)
                        _JIT_CACHE[sig] = fn
                        while len(_JIT_CACHE) > _JIT_CACHE_CAP:
                            _JIT_CACHE.popitem(last=False)
                    else:
                        _JIT_CACHE.move_to_end(sig)
                if build_span is not None:
                    build_span.attrs["compile"] = compiled
                if spec.strategy == "megakernel" \
                        and megakernel.carry_enabled():
                    # donated-carry handoff: the previous execution's raw
                    # accumulator grids pop out of the pool and ride back
                    # in as the donated third argument; the new grids park
                    # under the same key for the next tick. Content is
                    # never read (the kernel re-inits at step 0) — the
                    # carry is purely the reusable HBM allocation, so
                    # repeated scheduler-tick execution has zero per-tick
                    # pool growth. A carry popped before a failed call is
                    # deliberately dropped: donation may have invalidated
                    # its buffers mid-flight, so the next tick rebuilds
                    # fresh zeros.
                    cdefs = megakernel.carry_defs(
                        kernels, col_dtypes, spec.num_total, spec.window)
                    carried = segment.device_take(("megacarry", sig))
                    if carried is None:
                        # standing-query bridge: a live sink's fresh
                        # snapshot adopts its predecessor's parked grids
                        # (data/segment.py adopt_carries_from) — carries
                        # are content-free, so cross-generation reuse is
                        # exactly as bit-safe as same-segment reuse
                        donor = segment.carry_donor()
                        if donor is not None:
                            carried = donor.device_take(("megacarry", sig))
                    donated = carried is not None \
                        and len(carried) == len(cdefs) \
                        and megakernel.donation_enabled()
                    if carried is None or len(carried) != len(cdefs):
                        carried = megakernel.fresh_carries(cdefs)
                    # byte accounting BEFORE the dispatch: once the call
                    # returns the carries are donated — invalidated on
                    # accelerator backends — and must not be read again
                    # (donorguard read-after-donate)
                    donated_nbytes = sum(
                        int(getattr(a, "nbytes", 0))
                        for a in carried) if donated else 0
                if kernel_class:
                    with trace_span_when(compiled, "engine/compile",
                                         kind="segment",
                                         strategy=spec.strategy):
                        if carried is not None:
                            try:
                                _build_kernel_program(fn, arrays, aux,
                                                      tuple(carried))
                            except BaseException:
                                # a failed build latches the program off,
                                # so its carries are dead: discharge the
                                # ownership the take popped
                                megakernel.discard_carries(carried)
                                raise
                        elif spec.strategy == "megakernel":
                            # no donation support: parking grids in the
                            # budgeted pool would only evict useful
                            # entries — run carryless
                            _build_kernel_program(fn, arrays, aux, ())
                        else:
                            _build_kernel_program(fn, arrays, aux)
            # the ENQUEUE: dispatch is asynchronous, so this span closes
            # when the program is queued; the request's engine/fetch times
            # its finish, where the host blocks for the results anyway
            with trace_span("engine/dispatch", strategy=spec.strategy,
                            rows=segment.n_rows, compile=compiled,
                            program=program), \
                    trace_span_when(compiled and not kernel_class,
                                    "engine/compile", kind="segment",
                                    strategy=spec.strategy):
                if carried is not None:
                    try:
                        counts, states, raw = fn(arrays, aux,
                                                 tuple(carried))
                    except BaseException:
                        # the take popped ownership; a failed dispatch may
                        # have already invalidated the donated buffers
                        # mid-flight, so discharge them explicitly — the
                        # pool's resident bytes stay truthful and the next
                        # tick rebuilds fresh zeros (donorguard
                        # take-without-repark)
                        megakernel.discard_carries(carried)
                        raise
                    segment.device_cached(("megacarry", sig),
                                          lambda: raw)
                    if donated:
                        megakernel.stats().record_donated(donated_nbytes)
                elif spec.strategy == "megakernel":
                    counts, states, _raw = fn(arrays, aux, ())
                else:
                    counts, states = fn(arrays, aux)
            # count the SUCCESSFUL program only (a Mosaic-failure retry
            # must not double-bill the query's dispatch scoreboard)
            dispatch_mod.record("segment")
            break
        except pallas_agg.KernelBuildError as e:
            # the kernel did not BUILD (trace → Pallas lowering → Mosaic
            # compile): latch pallas off for the process and retry on the
            # XLA windowed/mixed path — a shape the compiler refuses must
            # not fail user queries (reference queries never depend on
            # which engine strategy runs). Nothing that fails while the
            # program RUNS is caught here. The latch is loud: the reason
            # is pallas_agg.broken_reason(), and chip_smoke.py and the
            # benchmark treat a latched process as failed. A megakernel tree keeps
            # working: its mega nodes expand to row masks in XLA
            # (MegaBitmapNode.build).
            pallas_agg.mark_broken(e.__cause__ or e)
            logging.getLogger(__name__).warning(
                "pallas %s kernel failed to build; falling back to the "
                "XLA path: %s", spec.strategy, e)
            spec.strategy, spec.window = next(
                (("windowed", w) for w in WINDOW_CHOICES
                 if spec.window and spec.window <= w),
                ("mixed", 0))

    return counts, states


def _build_kernel_program(fn, *args) -> None:
    """Build a pallas-class jitted program for `args` WITHOUT running it:
    trace, Pallas lowering and the Mosaic compile happen here, so the
    latch in _dispatch_segment catches exactly the failures of the
    BUILD (pallas_agg.KernelBuildError) and never one of the run. The jit
    call that follows reuses the executable this leaves in jit's own
    cache (nothing compiles twice); on a warm cache the call costs a
    signature lookup."""
    try:
        fn.lower(*args).compile()
    except Exception as e:
        raise pallas_agg.KernelBuildError(
            f"{type(e).__name__}: {e}") from e


def _pad_host(arr: np.ndarray, padded: int, fill) -> np.ndarray:
    out = np.full((padded,), fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def _pad_device(arr: np.ndarray, padded: int, fill) -> object:
    import jax
    return jax.device_put(_pad_host(arr, padded, fill))


def _pad_device_cached(segment: Segment, cache_key: Optional[Tuple],
                       arr: np.ndarray, padded: int, fill,
                       value_range: Optional[Tuple[int, int]] = None
                       ) -> object:
    """Padded device copy of a derived host column, cached on the segment so
    repeated queries reuse the HBM-resident array exactly like staged data
    columns (data/segment.py device cache, LRU-bounded).

    `value_range=(lo, hi)` marks an int32 column whose exact range is a
    plan constant (`__key`/`__bucket`): when the cascade FOR rung covers
    it, the column stages as base-biased range-packed words instead of
    dense int32 — decoded at the program top like any cascade column."""
    plan = cascade_mod.for_encode_derived(*value_range) \
        if value_range is not None and arr.dtype == np.int32 else None
    if plan is not None:
        w, base = plan

        def _build_for():
            import jax
            words = packed_mod.pack_padded(_pad_host(arr, padded, fill), w,
                                           base)
            return cascade_mod.ForColumn(jax.device_put(words), w, base,
                                         padded, str(arr.dtype))
        if cache_key is None:
            return _build_for()
        return segment.device_cached(
            ("devpadfor", cache_key, padded, fill, w, base), _build_for)
    if cache_key is None:
        return _pad_device(arr, padded, fill)
    return segment.device_cached(("devpad", cache_key, padded, fill),
                                 lambda: _pad_device(arr, padded, fill))
