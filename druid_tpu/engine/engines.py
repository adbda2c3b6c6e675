"""Per-query-type engines over the unified grouped-aggregate program.

Reference analogs:
  timeseries — query/timeseries/TimeseriesQueryEngine.java:40
  topN       — query/topn/TopNQueryEngine.java:48 (+PooledTopNAlgorithm)
  groupBy    — query/groupby/epinephelinae/GroupByQueryEngineV2.java:91
  scan       — query/scan/ScanQueryEngine.java:55
  select     — query/select/SelectQueryEngine.java
  search     — query/search/SearchQueryRunnerFactory.java (UseIndexesStrategy)
  timeBoundary / segmentMetadata / dataSourceMetadata —
      query/timeboundary/, query/metadata/SegmentAnalyzer.java,
      query/datasourcemetadata/

Result row shapes mirror the reference's JSON wire format (timestamps kept as
epoch millis ints; the HTTP layer renders ISO strings).
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from druid_tpu.data.segment import Segment, ValueType
from druid_tpu.engine import batching
from druid_tpu.engine.filters import host_mask
from druid_tpu.engine.grouping import (KeyDim, enqueue_grouped_aggregate,
                                       run_grouped_aggregates)
from druid_tpu.engine.merge import merge_partials, merge_to_partial
from druid_tpu.parallel import distributed
from druid_tpu.query.model import (DefaultLimitSpec, DimensionSpec, GroupByQuery,
                                   ListFilteredDimensionSpec, ScanQuery,
                                   SearchQuery, SegmentMetadataQuery, SelectQuery,
                                   TimeBoundaryQuery, TimeseriesQuery, TopNQuery,
                                   DataSourceMetadataQuery)
from druid_tpu.query.postaggs import compute_postaggs
from druid_tpu.utils.granularity import Granularity
from druid_tpu.utils.intervals import Interval, condense


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def _segments_for(segments: Sequence[Segment],
                  intervals: Sequence[Interval]) -> List[Segment]:
    return [s for s in segments
            if any(s.interval.overlaps(iv) for iv in intervals)]


def _clamp_to_data(intervals: Sequence[Interval],
                   segs: Sequence[Segment]) -> List[Interval]:
    """Intersect query intervals with the extent of the matched segments.
    The reference never materializes buckets outside segment data (cursors
    exist per granularity bucket *within* segments —
    QueryableIndexStorageAdapter.makeCursors); clamping keeps eternity-
    interval queries from enumerating unbounded bucket ranges."""
    if not segs:
        return list(intervals)
    lo = min(s.min_time for s in segs)
    hi = max(s.max_time for s in segs) + 1
    data = Interval(lo, hi)
    out = []
    for iv in intervals:
        x = iv.intersect(data)
        if x is not None and x.width > 0:
            out.append(x)
    return out


def _keydim_for(segment: Segment, spec: DimensionSpec) -> Tuple[KeyDim, List[str]]:
    """Build a KeyDim (+ local id -> output value list) for one dimension spec.

    Extraction fns and listFiltered run host-side over the dictionary,
    producing an id remap table (cached per segment) — the analog of the
    reference applying ExtractionFn per row, at O(cardinality) instead of
    O(rows)."""
    from druid_tpu.query.model import ExpressionDimensionSpec
    if isinstance(spec, ExpressionDimensionSpec):
        return _expr_keydim(segment, spec)
    col = segment.dims.get(spec.dimension)
    num_ids = None
    num_key = None
    dim_col = spec.dimension
    if col is None:
        m = segment.metrics.get(spec.dimension)
        if m is None or np.asarray(m.values).ndim != 1:
            return KeyDim(None, 1, None), [""]
        # numeric dimension handler (reference: Double/Long/Float
        # DimensionHandler + GroupByQueryEngineV2 numeric grouping): build a
        # query-time dictionary over the column's values — the device groups
        # by compact int32 ids exactly like a string dim, decode emits the
        # numeric values
        num_key = ("numdim", spec.dimension)

        def _compute_num():
            uniq, inv = np.unique(m.values, return_inverse=True)
            return inv.astype(np.int32), [v.item() for v in uniq]
        num_ids, num_vals = segment.aux_cached(num_key, _compute_num)
        dim_col = f"__numdim_{spec.dimension}"

    fn = spec.extraction_fn
    whitelist = None
    is_white = True
    if isinstance(spec, ListFilteredDimensionSpec):
        whitelist = set(spec.values)
        is_white = spec.is_whitelist

    if fn is None and whitelist is None:
        if col is None:
            return KeyDim(dim_col, max(len(num_vals), 1), None,
                          host_ids=num_ids,
                          ids_key=("numdim_ids", spec.dimension)), \
                (num_vals or [""])
        return KeyDim(spec.dimension, col.cardinality, None), col.dictionary.values

    cache_key = ("keydim", spec.dimension,
                 json.dumps(fn.cache_key(), sort_keys=True) if fn else None,
                 tuple(sorted(whitelist)) if whitelist is not None else None,
                 is_white)

    def _compute():
        # extraction fns see the STRING form of numeric values (reference
        # ExtractionFn contract)
        vals = [str(v) for v in num_vals] if col is None \
            else col.dictionary.values
        raw = fn.apply_all(vals) if fn else vals
        outs = ["" if o is None else str(o) for o in raw]
        keep = [True] * len(outs)
        if whitelist is not None:
            for i, o in enumerate(outs):
                inside = o in whitelist
                keep[i] = inside if is_white else not inside
        uniq = sorted({o for o, k in zip(outs, keep) if k})
        index = {v: i for i, v in enumerate(uniq)}
        remap = np.asarray(
            [index[o] if k else -1 for o, k in zip(outs, keep)], dtype=np.int32)
        return remap, uniq

    remap, uniq = segment.aux_cached(cache_key, _compute)
    return KeyDim(dim_col, max(len(uniq), 1), remap, host_ids=num_ids,
                  ids_key=("numdim_ids", spec.dimension)
                  if num_ids is not None else None), (uniq or [""])


def _expr_keydim(segment: Segment, spec) -> Tuple[KeyDim, List]:
    """Host-evaluate an expression dimension into a per-segment value
    dictionary (numeric dims generalized to computed values; string dims
    bind decoded so string comparisons/CASE work)."""
    from druid_tpu.engine.filters import _bind_string_dims
    from druid_tpu.utils.expression import parse_expression

    cache_key = ("exprdim", spec.expression, spec.output_type)

    def _compute():
        expr = parse_expression(spec.expression)
        bindings: Dict[str, np.ndarray] = {"__time": segment.time_ms}
        for name, m in segment.metrics.items():
            if np.asarray(m.values).ndim == 1:
                bindings[name] = m.values
        _bind_string_dims(expr, segment, bindings)
        vals = np.broadcast_to(np.asarray(expr.evaluate(bindings)),
                               (segment.n_rows,))
        uniq, inv = np.unique(vals, return_inverse=True)
        out = [v.item() if hasattr(v, "item") else v for v in uniq]
        if spec.output_type == "string":
            out = [str(v) for v in out]
        return inv.astype(np.int32), out

    ids, vals = segment.aux_cached(cache_key, _compute)
    return KeyDim(f"__exprdim_{spec.output_name}", max(len(vals), 1), None,
                  host_ids=ids,
                  ids_key=("exprdim_ids", spec.expression,
                           spec.output_type)), (vals or [""])


def _bucket_starts(granularity: Granularity,
                   intervals: Sequence[Interval]) -> np.ndarray:
    if granularity.is_all:
        # single global bucket (matches grouping.make_group_spec)
        first = min((iv.start for iv in intervals), default=0)
        return np.asarray([first], dtype=np.int64) if intervals \
            else np.zeros(0, dtype=np.int64)
    parts = [granularity.bucket_starts(iv) for iv in intervals]
    return np.concatenate(parts) if parts else np.zeros(0, dtype=np.int64)


def _covered_buckets(granularity: Granularity, starts: np.ndarray,
                     data_spans: Sequence[Tuple[int, int]],
                     intervals: Sequence[Interval]) -> np.ndarray:
    """Buckets whose span intersects actual segment data (mirrors the
    reference emitting one row per cursor bucket). `data_spans` are
    (min_time, max_time) extents of the contributing segments."""
    if len(starts) == 0:
        return np.zeros(0, dtype=bool)
    spans = []
    for mn, mx in data_spans:
        for iv in intervals:
            lo = max(mn, iv.start)
            hi = min(mx + 1, iv.end)
            if lo < hi:
                spans.append((lo, hi))
    if not spans:
        return np.zeros(len(starts), dtype=bool)
    if granularity.is_all:
        return np.ones(len(starts), dtype=bool)
    if granularity.is_uniform:
        ends = starts + granularity.period_ms
    else:
        ends = np.asarray([granularity.next_bucket(int(st)) for st in starts],
                          dtype=np.int64)
    los = np.asarray([lo for lo, _ in spans], dtype=np.int64)
    his = np.asarray([hi for _, hi in spans], dtype=np.int64)
    # bucket i covered iff any span overlaps [starts[i], ends[i])
    return ((starts[:, None] < his[None, :])
            & (ends[:, None] > los[None, :])).any(axis=1)


def _vectorized_postaggs(postaggs, value_arrays: Dict[str, np.ndarray]):
    out = dict(value_arrays)
    for pa in postaggs:
        out[pa.name] = pa.compute(out)
    return out


def _make_partials(segs, intervals, query, kds_per_seg, vals_per_seg,
                   check=None):
    """Produce (partials, dim_values): ONE sharded device program when a mesh
    is active and the segments agree on plan constants; else batched
    multi-segment dispatches over shape-compatible segments (one jitted
    program per shape bucket with the per-segment body unrolled inside it —
    deliberately NOT vmapped, see engine/batching.py); else the per-segment
    path. The sharded program merges on the device into one partial; the
    others return a partial a segment, which the data node merges on its
    host before it answers (`AggregatePartials.merged`) and a local run
    merges in its finish step.

    A request ENQUEUES every program it needs and fetches once
    (grouping.run_grouped_aggregates): its spans are one `engine/segment`
    (or `engine/batch/dispatch`) a program, all host work, then ONE
    `engine/fetch` under `engine/partials` whose `programs` counts them.

    `check` (cancel/timeout probe) runs at every dispatch boundary: between
    per-segment programs, between batched shape-bucket dispatches, before
    the single sharded program, and once before the fetch. Nothing recalls
    an enqueued program: a request cancelled after its last enqueue raises
    at the check before the fetch, and at most its own queued kernels run
    out on the device behind it (234 ms for twenty 5M-row segments —
    PERF.md §5)."""
    from druid_tpu.obs.trace import span as trace_span
    if check is not None:
        check()
    with trace_span("engine/partials", segments=len(segs)):
        merged = distributed.try_sharded(segs, intervals, query.granularity,
                                         kds_per_seg, query.aggregations,
                                         query.filter, query.virtual_columns)
        if merged is not None:
            return [merged], [vals_per_seg[0]]
        partials = batching.run_with_batching(
            segs, intervals, query.granularity, kds_per_seg,
            query.aggregations, query.filter, query.virtual_columns,
            context=query.context_map, check=check)
        if partials is None:
            partials = run_grouped_aggregates(
                [functools.partial(
                    enqueue_grouped_aggregate, s, intervals,
                    query.granularity, kds, query.aggregations, query.filter,
                    virtual_columns=query.virtual_columns)
                 for s, kds in zip(segs, kds_per_seg)], check)
        return partials, list(vals_per_seg)


# ---------------------------------------------------------------------------
# Partial production / finish split (the broker's scatter-gather seam)
# ---------------------------------------------------------------------------

class AggregatePartials:
    """Partial aggregation states from one producer (data node / local run).

    The unit shipped from data nodes to the broker: states are plain
    host arrays, dim_values are merged-dictionary string lists, spans are
    (min_time, max_time) data extents for bucket-coverage accounting.
    One partial stands for one segment as the engine produces them, for
    many once merged (`merged()`, the sharded program): a data node answers
    with ONE, and `spans` keeps an entry for every segment behind it.
    Reference analog: the non-finalized sequence a historical streams back
    after ServerManager merged its per-segment runners with the tool
    chest's mergeResults, before the broker's own mergeResults."""

    def __init__(self, partials, dim_values, spans, intervals):
        self.partials = partials          # List[SegmentPartial]
        self.dim_values = dim_values      # parallel: List[List[List[str]]]
        self.spans = spans                # List[(min_ms, max_ms)]
        self.intervals = intervals        # intervals partials were built with

    def merged(self) -> "AggregatePartials":
        """These partials as ONE (`merge.merge_to_partial`) with its one
        entry of dim_values, every span and the intervals kept: what a data
        node sends instead of a partial a segment. `finish_*` of the result
        is `finish_*` of `self`, bit for bit. Zero or one partial is `self`
        — nothing to merge, as for a mesh node's sharded partial."""
        if len(self.partials) < 2:
            return self
        partial, values = merge_to_partial(self.partials, self.dim_values)
        return AggregatePartials([partial], [values], self.spans,
                                 self.intervals)

    @staticmethod
    def concat(parts: Sequence["AggregatePartials"]) -> "AggregatePartials":
        parts = [p for p in parts if p is not None]
        out = AggregatePartials([], [], [], None)
        for p in parts:
            out.partials += list(p.partials)
            out.dim_values += list(p.dim_values)
            out.spans += list(p.spans)
            if out.intervals is None:
                out.intervals = p.intervals
        return out


def make_aggregate_partials(query, segments: Sequence[Segment],
                            clamp: bool = True,
                            check=None) -> AggregatePartials:
    """Produce partial states for a timeseries/topN/groupBy query over local
    segments. `clamp=False` is used by the broker path: it pre-bounds the
    query intervals globally so bucket index spaces align across nodes.
    `check` (optional cancel/timeout probe) fires at dispatch boundaries."""
    return _make_aggregate_partials_with_segs(query, segments, clamp,
                                              check)[0]


def make_partials_by_segment(query, segments: Sequence[Segment],
                             clamp: bool = False,
                             check=None) -> List[AggregatePartials]:
    """One single-segment AggregatePartials PER INPUT SEGMENT (parallel to
    `segments`; a segment outside the query intervals yields an EMPTY
    partials object). The data node's segment-cache miss path runs its
    whole miss set through here — ONE call, so shape-compatible misses
    batch into shared dispatches (engine/batching.py) — and splits the
    results back into per-segment cache entries."""
    ap, segs = _make_aggregate_partials_with_segs(query, segments, clamp,
                                                  check)
    if len(ap.partials) != len(segs):
        # the sharded path fused the set into one merged partial (mesh
        # active) — per-segment states no longer exist, so compute each
        # segment singly; callers needing the split semantics (the cache
        # population path) get correct entries at per-segment cost. The
        # cancel probe keeps firing at every dispatch boundary.
        out = []
        for i, s in enumerate(segments):
            if check is not None and i:
                check()
            out.append(make_aggregate_partials(query, [s], clamp=clamp))
        return out
    return _split_by_segment(ap, segs, segments)


def _split_by_segment(ap: AggregatePartials, segs: Sequence[Segment],
                      segments: Sequence[Segment]
                      ) -> List[AggregatePartials]:
    """Split a per-segment AggregatePartials (partials parallel to `segs`)
    into one entry per input segment; a segment absent from `segs` (outside
    the query intervals) yields an EMPTY partials object — exactly what the
    per-miss cache loop would have stored for it."""
    remaining: Dict[int, List[int]] = {}
    for i, s in enumerate(segs):
        remaining.setdefault(id(s), []).append(i)
    out = []
    for s in segments:
        idxs = remaining.get(id(s))
        if idxs:
            i = idxs.pop(0)
            out.append(AggregatePartials([ap.partials[i]],
                                         [ap.dim_values[i]],
                                         [ap.spans[i]], ap.intervals))
        else:
            out.append(AggregatePartials([], [], [], ap.intervals))
    return out


def split_partials_by_segment(ap: AggregatePartials,
                              segments: Sequence[Segment]
                              ) -> List[AggregatePartials]:
    """Public splitter for per-segment partial sets produced WITHOUT mesh
    fusion (make_aggregate_partials_multi items): `ap.partials` is parallel
    to `_segments_for(segments, ap.intervals)` by construction, so the
    per-input-segment split is exact. The data node's scheduler-fused
    segment-cache path uses this to turn one fused wave's results back
    into per-segment cache entries identical to the serial path's."""
    segs = _segments_for(segments, ap.intervals or [])
    assert len(ap.partials) == len(segs), \
        "split_partials_by_segment needs unfused per-segment partials"
    return _split_by_segment(ap, segs, segments)


#: TTL for cached union-remap id columns: a rolling ingest window retires
#: segments' union digests, and the per-(segment, dim) aux slot would pin
#: its last n_rows×4B remap forever (the aux cache has no eviction). The
#: sweeper below clears any slot idle past this, so stale remaps stop
#: pinning host memory while hot dashboards (re-touched every query) never
#: expire. Override via DRUID_TPU_UNIDIM_TTL_S; <= 0 disables expiry.
_UNIDIM_TTL_S = float(os.environ.get("DRUID_TPU_UNIDIM_TTL_S", "900"))
_UNIDIM_LOCK = threading.Lock()


class _UnidimSlot(dict):
    """Weakref-able remap slot ({union digest: remapped ids}) with a
    last-touch stamp; the registry holds weak references only, so a
    collected segment's slots vanish without bookkeeping. Identity
    hash/eq: dict is unhashable and content-equality would collide
    distinct (empty) slots inside the WeakSet registry."""
    __slots__ = ("__weakref__", "touched")
    __hash__ = object.__hash__

    def __eq__(self, other):
        return self is other

    def __ne__(self, other):
        return self is not other


_UNIDIM_SLOTS: "weakref.WeakSet[_UnidimSlot]" = weakref.WeakSet()


def set_unidim_ttl(seconds: float) -> float:
    """Set the union-remap TTL; returns the previous value (test hook)."""
    global _UNIDIM_TTL_S
    with _UNIDIM_LOCK:
        prev = _UNIDIM_TTL_S
        _UNIDIM_TTL_S = float(seconds)
        return prev


def _sweep_unidim(now: float) -> int:
    """Clear every union-remap slot idle past the TTL; returns the number
    of slots cleared. Runs at each unify_query_dims entry — eviction needs
    no background thread because the only growth source is this path."""
    cleared = 0
    with _UNIDIM_LOCK:
        ttl = _UNIDIM_TTL_S
        if ttl <= 0:
            return 0
        for slot in list(_UNIDIM_SLOTS):
            if slot and now - getattr(slot, "touched", now) > ttl:
                slot.clear()
                cleared += 1
    return cleared


def unify_query_dims(segs: Sequence[Segment], kds_per_seg,
                     vals_per_seg) -> None:
    """Unify per-segment QUERY-TIME dictionaries (numeric/expression
    dimension handlers: KeyDim.host_ids) into ONE id space across the
    query's segments, in place. Each segment's local ids remap host-side
    into the sorted union of every segment's values (cached per (segment,
    union digest)), so plan constants — cardinality, decode list — stop
    being segment-local and shape-compatible segments batch
    (engine/batching.py; the host-mask era excluded these). Results are
    unchanged: ids decode to exactly the same values, the space is merely
    shared."""
    import hashlib
    if len(segs) < 2 or not kds_per_seg or not kds_per_seg[0]:
        return
    now = time.monotonic()
    _sweep_unidim(now)
    for j in range(len(kds_per_seg[0])):
        col = [kds[j] for kds in kds_per_seg]
        if not all(kd.host_ids is not None and kd.remap is None
                   and kd.ids_key is not None for kd in col):
            continue
        lists = [vals[j] for vals in vals_per_seg]
        if all(l == lists[0] for l in lists[1:]):
            continue                  # already one id space
        try:
            union = sorted(set().union(*map(set, lists)))
        except TypeError:
            continue                  # unorderable mixed types: per-segment
        udig = hashlib.sha1(repr(union).encode()).hexdigest()[:16]
        index = {v: i for i, v in enumerate(union)}
        for s, kds, vals in zip(segs, kds_per_seg, vals_per_seg):
            kd = kds[j]
            # ONE resident remapped id column per (segment, dim), replaced
            # when the union digest changes, and TTL-swept when idle
            # (_sweep_unidim): a rolling segment set would otherwise grow
            # a fresh n_rows×4B aux entry per distinct window this segment
            # ever appeared in AND pin the last one forever. Repeated
            # dashboards over a stable set still hit.
            slot = s.aux_cached(("unidim",) + tuple(kd.ids_key),
                                _UnidimSlot)
            with _UNIDIM_LOCK:
                _UNIDIM_SLOTS.add(slot)
            slot.touched = now
            new_ids = slot.get(udig)
            if new_ids is None:
                remap = np.asarray([index[v] for v in vals[j]],
                                   dtype=np.int32)
                new_ids = remap[kd.host_ids]
                slot.clear()
                # the slot was fetched per (segment, kd.ids_key) via
                # aux_cached, so segment/kd state is pinned per slot;
                # udig keys the one free variable (the window's union)
                slot[udig] = new_ids  # druidlint: disable=unkeyed-trace-input
            kds[j] = KeyDim(kd.column, max(len(union), 1), None,
                            host_ids=new_ids,
                            ids_key=("unidim",) + tuple(kd.ids_key)
                            + (udig,))
            vals[j] = list(union)


def _keydims_for_query(query, segs: Sequence[Segment]):
    """Per-segment KeyDims + decode value lists for an aggregate query —
    the one derivation every partial-producing path (single-query, multi-
    query scheduler, by-segment split) shares."""
    if isinstance(query, TimeseriesQuery):
        return [[] for _ in segs], [[] for _ in segs]
    if isinstance(query, TopNQuery):
        keydims = [_keydim_for(s, query.dimension) for s in segs]
        kds_per_seg = [[kd] for kd, _ in keydims]
        vals_per_seg = [[values] for _, values in keydims]
        unify_query_dims(segs, kds_per_seg, vals_per_seg)
        return kds_per_seg, vals_per_seg
    if isinstance(query, GroupByQuery):
        kds_per_seg, vals_per_seg = [], []
        for s in segs:
            kds, vals = [], []
            for d in query.dimensions:
                kd, v = _keydim_for(s, d)
                kds.append(kd)
                vals.append(v)
            kds_per_seg.append(kds)
            vals_per_seg.append(vals)
        unify_query_dims(segs, kds_per_seg, vals_per_seg)
        return kds_per_seg, vals_per_seg
    raise TypeError(f"not an aggregate query: {type(query).__name__}")


def _make_aggregate_partials_with_segs(query, segments: Sequence[Segment],
                                       clamp: bool, check
                                       ) -> Tuple[AggregatePartials,
                                                  List[Segment]]:
    intervals = condense(query.intervals)
    segs = _segments_for(segments, intervals)
    if clamp and not query.granularity.is_all:
        intervals = _clamp_to_data(intervals, segs)
    if not segs:
        return AggregatePartials([], [], [], intervals), segs
    kds_per_seg, vals_per_seg = _keydims_for_query(query, segs)
    partials, dim_values = _make_partials(segs, intervals, query,
                                          kds_per_seg, vals_per_seg,
                                          check=check)
    spans = [(s.min_time, s.max_time) for s in segs]
    return AggregatePartials(partials, dim_values, spans, intervals), segs


def make_aggregate_partials_multi(items, on_batch=None) -> List[object]:
    """Cross-query partial production: one call for a whole scheduler
    flush. `items` is a sequence of (query, segments, check) triples —
    aggregate queries over LOCAL segments, meshless (the scheduler routes
    mesh/cached/row work individually). Returns one entry per item: an
    AggregatePartials, or the Exception that item's cancel/timeout probe
    raised.

    Per-item planning (interval condensing, keydim derivation) is exactly
    the serial path's; only the device dispatches fuse — results are
    bit-identical to calling make_aggregate_partials per item.
    `on_batch(n_queries, n_segments, fill)` observes each fused dispatch
    (the scheduler's query/crossBatch/* hook)."""
    from druid_tpu.engine.batching import BatchWork, run_multi_with_batching
    from druid_tpu.obs.trace import span as trace_span

    work: List[BatchWork] = []
    meta: List[object] = []   # per item: (intervals, segs, vals) | result
    for query, segments, check in items:
        try:
            intervals = condense(query.intervals)
            segs = _segments_for(segments, intervals)
            if not segs:
                meta.append(AggregatePartials([], [], [], intervals))
                continue
            kds_per_seg, vals_per_seg = _keydims_for_query(query, segs)
        except Exception as e:
            meta.append(e)
            continue
        meta.append((intervals, segs, vals_per_seg))
        work.append(BatchWork(
            segs=segs, intervals=intervals, granularity=query.granularity,
            kds_per_seg=kds_per_seg, aggs=query.aggregations,
            flt=query.filter, virtual_columns=query.virtual_columns,
            context=query.context_map, check=check))

    with trace_span("engine/partials", queries=len(work),
                    segments=sum(len(w.segs) for w in work)):
        multi = run_multi_with_batching(work, on_batch=on_batch)

    out: List[object] = []
    it = iter(multi)
    for m in meta:
        if not isinstance(m, tuple):
            out.append(m)            # precomputed empty result / error
            continue
        intervals, segs, vals_per_seg = m
        got = next(it)
        if isinstance(got, BaseException):
            out.append(got)
            continue
        spans = [(s.min_time, s.max_time) for s in segs]
        out.append(AggregatePartials(got, list(vals_per_seg), spans,
                                     intervals))
    return out


# ---------------------------------------------------------------------------
# Timeseries
# ---------------------------------------------------------------------------

def run_by_segment(query, segments: Sequence[Segment]) -> List[dict]:
    """context.bySegment=true: per-segment UNMERGED results, each wrapped
    with its segment identity (reference: BySegmentQueryRunner.java — the
    caching/debug surface where the broker sees exactly what every segment
    contributed)."""
    from dataclasses import replace
    inner = replace(query, context=tuple(
        (k, v) for k, v in query.context_map.items() if k != "bySegment"))
    out: List[dict] = []
    intervals = condense(query.intervals)
    for s in _segments_for(segments, intervals):
        if isinstance(query, TimeseriesQuery):
            rows = finish_timeseries(
                inner, make_aggregate_partials(inner, [s]))
        elif isinstance(query, TopNQuery):
            rows = finish_topn(inner, make_aggregate_partials(inner, [s]))
        else:
            rows = finish_groupby(inner, make_aggregate_partials(inner, [s]))
        out.append({
            "timestamp": rows[0]["timestamp"] if rows else None,
            "result": {"results": rows, "segment": str(s.id),
                       "interval": str(s.interval)},
            "bySegment": True,
        })
    return out


def run_timeseries(query: TimeseriesQuery, segments: Sequence[Segment]) -> List[dict]:
    return finish_timeseries(query, make_aggregate_partials(query, segments))


def finish_timeseries(query: TimeseriesQuery,
                      ap: AggregatePartials) -> List[dict]:
    intervals = ap.intervals if ap.intervals is not None \
        else condense(query.intervals)
    starts = _bucket_starts(query.granularity, intervals)
    if not ap.partials or len(starts) == 0:
        return []
    buckets, _, counts, states, kernels = merge_partials(
        ap.partials, [[] for _ in ap.partials])
    finalized = {k.name: k.finalize_array(states[k.name]) for k in kernels}

    covered = _covered_buckets(query.granularity, starts, ap.spans, intervals)
    empty_defaults = {k.name: k.finalize_array(k.empty_state(1))[0]
                      for k in kernels}

    by_bucket = {int(b): i for i, b in enumerate(buckets)}
    rows = []
    for bi, st in enumerate(starts):
        gi = by_bucket.get(bi)
        if gi is None:
            if not covered[bi] or query.skip_empty_buckets:
                continue
            vals = {name: _scalar(v) for name, v in empty_defaults.items()}
        else:
            if query.skip_empty_buckets and counts[gi] == 0:
                continue
            vals = {k.name: _scalar(finalized[k.name][gi]) for k in kernels}
        vals = compute_postaggs(query.post_aggregations, vals)
        rows.append({"timestamp": int(st), "result": vals})
    if query.descending:
        rows.reverse()
    return rows


def _scalar(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.ndarray) and v.ndim == 0:
        return v.item()
    return v


# ---------------------------------------------------------------------------
# TopN
# ---------------------------------------------------------------------------

def run_topn(query: TopNQuery, segments: Sequence[Segment]) -> List[dict]:
    return finish_topn(query, make_aggregate_partials(query, segments))


def finish_topn(query: TopNQuery, ap: AggregatePartials) -> List[dict]:
    intervals = ap.intervals if ap.intervals is not None \
        else condense(query.intervals)
    starts = _bucket_starts(query.granularity, intervals)
    if not ap.partials or len(starts) == 0:
        return []
    buckets, dim_vals, counts, states, kernels = merge_partials(
        ap.partials, ap.dim_values)
    finalized = {k.name: k.finalize_array(states[k.name]) for k in kernels}
    arrays = _vectorized_postaggs(query.post_aggregations, finalized)
    values = dim_vals[0] if dim_vals else np.zeros(0, dtype=object)
    out_name = query.dimension.output_name

    # live groups only
    live = counts > 0
    buckets, values = buckets[live], values[live]
    arrays = {k: np.asarray(v)[live] for k, v in arrays.items()}

    ordering = query.metric_ordering
    rows = []
    covered = _covered_buckets(query.granularity, starts, ap.spans, intervals)
    for bi, st in enumerate(starts):
        sel = buckets == bi
        if not sel.any():
            if covered[bi]:
                rows.append({"timestamp": int(st), "result": []})
            continue
        idx = np.flatnonzero(sel)
        if ordering in ("lexicographic",):
            order = np.argsort(values[idx].astype(str))
        elif ordering == "inverted_lexicographic":
            order = np.argsort(values[idx].astype(str))[::-1]
        elif ordering == "strlen":
            order = np.argsort([len(str(v)) for v in values[idx]])
        else:
            metric_arr = np.asarray(arrays[query.metric], dtype=np.float64)
            order = np.argsort(-metric_arr[idx], kind="stable")
            if ordering == "inverted":
                order = order[::-1]
        top = idx[order[: query.threshold]]
        result = []
        for gi in top:
            entry = {out_name: values[gi]}
            for name, arr in arrays.items():
                entry[name] = _scalar(np.asarray(arr)[gi])
            result.append(entry)
        rows.append({"timestamp": int(st), "result": result})
    return rows


# ---------------------------------------------------------------------------
# GroupBy
# ---------------------------------------------------------------------------

def run_groupby(query: GroupByQuery, segments: Sequence[Segment]) -> List[dict]:
    return finish_groupby(query, make_aggregate_partials(query, segments))


def finish_groupby(query: GroupByQuery, ap: AggregatePartials) -> List[dict]:
    intervals = ap.intervals if ap.intervals is not None \
        else condense(query.intervals)
    starts = _bucket_starts(query.granularity, intervals)
    if not ap.partials or len(starts) == 0:
        return []
    buckets, dim_vals, counts, states, kernels = merge_partials(
        ap.partials, ap.dim_values)
    finalized = {k.name: k.finalize_array(states[k.name]) for k in kernels}
    arrays = _vectorized_postaggs(query.post_aggregations, finalized)

    live = counts > 0
    out_names = [d.output_name for d in query.dimensions]
    rows = _emit_groupby_rows(starts, buckets, dim_vals, arrays, live, out_names,
                              kernels, query)

    if query.subtotals:
        rows = rows + _subtotal_rows(query, starts, buckets, dim_vals, counts,
                                     states, kernels)

    if query.having is not None:
        rows = [r for r in rows if query.having.evaluate(r["event"])]
    rows = _apply_limit_spec(rows, query.limit_spec, out_names)
    return rows


def _emit_groupby_rows(starts, buckets, dim_vals, arrays, live, out_names,
                       kernels, query) -> List[dict]:
    # columnar → row dicts via one .tolist() per column: at 100k+ groups the
    # per-element numpy scalar extraction would dominate the whole query
    idxs = np.flatnonzero(live)
    n = len(idxs)
    if len(starts):
        ts = np.asarray(starts)[np.asarray(buckets)[idxs]].tolist()
    else:
        ts = [0] * n
    agg_names = [k.name for k in kernels] + [p.name for p in query.post_aggregations]
    cols = [(name, np.asarray(vals)[idxs].tolist())
            for name, vals in zip(out_names, dim_vals)]
    cols += [(name, np.asarray(arrays[name])[idxs].tolist())
             for name in agg_names]
    rows = []
    for i in range(n):
        event = {name: lst[i] for name, lst in cols}
        rows.append({"version": "v1", "timestamp": int(ts[i]),
                     "event": event})
    return rows


def _subtotal_rows(query, starts, buckets, dim_vals, counts, states,
                   kernels) -> List[dict]:
    """Re-group merged results for each subtotal spec (reference:
    GroupByStrategyV2.processSubtotalsSpec)."""
    out_names = [d.output_name for d in query.dimensions]
    rows = []
    live = np.flatnonzero(counts > 0)
    for subset in query.subtotals:
        keep = [i for i, n in enumerate(out_names) if n in subset]
        groups: Dict[tuple, dict] = {}
        for gi in live:
            key = (int(buckets[gi]),) + tuple(dim_vals[i][gi] for i in keep)
            g = groups.get(key)
            if g is None:
                g = {"states": {k.name: _state_at(states[k.name], gi)
                                for k in kernels}}
                groups[key] = g
            else:
                for k in kernels:
                    g["states"][k.name] = k.combine(
                        g["states"][k.name], _state_at(states[k.name], gi))
        for key, g in sorted(groups.items(), key=lambda kv: str(kv[0])):
            event = {}
            for j, i in enumerate(keep):
                event[out_names[i]] = key[1 + j]
            vals = {k.name: _scalar(k.finalize_array(g["states"][k.name])[0])
                    for k in kernels}
            event.update(compute_postaggs(query.post_aggregations, vals))
            rows.append({"version": "v1",
                         "timestamp": int(starts[key[0]]) if len(starts) else 0,
                         "event": event})
    return rows


def _state_at(state, gi):
    if isinstance(state, dict):
        return {k: _state_at(v, gi) for k, v in state.items()}
    return np.asarray(state)[gi:gi + 1]


def _apply_limit_spec(rows: List[dict], limit_spec: Optional[DefaultLimitSpec],
                      dim_names: List[str]) -> List[dict]:
    if limit_spec is None:
        return rows
    if limit_spec.columns:
        # stable multi-column sort: apply columns in reverse significance order
        for c in reversed(limit_spec.columns):
            descending = c.direction == "descending"

            def one_key(row, col=c):
                # "__timestamp" orders by the granularity bucket (used by
                # SQL ORDER BY on a FLOOR(__time TO ...) projection)
                v = row["timestamp"] if col.dimension == "__timestamp" \
                    else row["event"].get(col.dimension)
                if col.dimension_order == "numeric" or not isinstance(v, str):
                    try:
                        v = float(v)
                    except (TypeError, ValueError):
                        v = float("-inf")
                return v
            rows = sorted(rows, key=one_key, reverse=descending)
    start = limit_spec.offset
    end = None if limit_spec.limit is None else start + limit_spec.limit
    return rows[start:end]


# ---------------------------------------------------------------------------
# Scan / Select (raw row export, host-side)
# ---------------------------------------------------------------------------

def _masked_row_ids(segment: Segment, query) -> np.ndarray:
    intervals = condense(query.intervals)
    t = segment.time_ms
    m = np.zeros(segment.n_rows, dtype=bool)
    for iv in intervals:
        m |= (t >= iv.start) & (t < iv.end)
    m &= host_mask(query.filter, segment,
                   getattr(query, "virtual_columns", ()))
    return np.flatnonzero(m)


def _decode_rows(segment: Segment, row_ids: np.ndarray,
                 columns: Sequence[str]) -> List[dict]:
    cols: Dict[str, np.ndarray] = {}
    for c in columns:
        if c == "__time":
            cols[c] = segment.time_ms[row_ids]
        elif c in segment.dims:
            col = segment.dims[c]
            vals = np.asarray(col.dictionary.values, dtype=object)
            cols[c] = vals[col.ids[row_ids]] if col.cardinality else \
                np.full(len(row_ids), "", dtype=object)
        elif c in segment.metrics:
            cols[c] = segment.metrics[c].values[row_ids]
    out = []
    for i in range(len(row_ids)):
        out.append({c: _scalar(v[i]) for c, v in cols.items()})
    return out


def iter_scan(query: ScanQuery, segments: Sequence[Segment]):
    """Lazy scan: yields one ScanResultValue batch at a time, a segment is
    only filtered/decoded when its batch is pulled, and `batch_size`
    bounds events per batch — the Sequence-analog streaming surface
    (reference: ScanQueryEngine returning a BaseSequence of batches)."""
    intervals = condense(query.intervals)
    segs = _segments_for(segments, intervals)
    if query.order == "descending":
        segs = sorted(segs, key=lambda s: s.min_time, reverse=True)
    else:
        segs = sorted(segs, key=lambda s: s.min_time)
    remaining = query.limit if query.limit is not None else None
    to_skip = query.offset
    batch = max(int(query.batch_size), 1)
    for s in segs:
        if remaining is not None and remaining <= 0:
            return
        row_ids = _masked_row_ids(s, query)
        if query.order == "descending":
            row_ids = row_ids[::-1]
        if to_skip:
            if to_skip >= len(row_ids):
                to_skip -= len(row_ids)
                continue
            row_ids = row_ids[to_skip:]
            to_skip = 0
        if remaining is not None:
            row_ids = row_ids[:remaining]
            remaining -= len(row_ids)
        columns = list(query.columns) or (
            ["__time"] + list(s.dims.keys()) + list(s.metrics.keys()))
        for i in range(0, len(row_ids), batch):
            events = _decode_rows(s, row_ids[i:i + batch], columns)
            if events:
                yield {"segmentId": str(s.id), "columns": columns,
                       "events": events}


def run_scan(query: ScanQuery, segments: Sequence[Segment]) -> List[dict]:
    return list(iter_scan(query, segments))


def run_select(query: SelectQuery, segments: Sequence[Segment]) -> List[dict]:
    intervals = condense(query.intervals)
    segs = _segments_for(segments, intervals)
    segs = sorted(segs, key=lambda s: s.min_time, reverse=query.descending)
    paging = dict(query.paging_spec)
    threshold = query.threshold
    events = []
    new_paging: Dict[str, int] = {}
    for s in segs:
        if threshold <= 0:
            break
        row_ids = _masked_row_ids(s, query)
        if query.descending:
            row_ids = row_ids[::-1]
        start = paging.get(str(s.id), -1) + 1
        row_ids = row_ids[start:start + threshold]
        threshold -= len(row_ids)
        columns = (["__time"] + (list(query.dimensions) or list(s.dims.keys()))
                   + (list(query.metrics) or list(s.metrics.keys())))
        for off, ev in zip(range(start, start + len(row_ids)),
                           _decode_rows(s, row_ids, columns)):
            events.append({"segmentId": str(s.id), "offset": off, "event": ev})
            new_paging[str(s.id)] = off
    ts = int(min((s.min_time for s in segs), default=0))
    return [{"timestamp": ts,
             "result": {"pagingIdentifiers": new_paging, "events": events}}]


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------

def run_search(query: SearchQuery, segments: Sequence[Segment]) -> List[dict]:
    intervals = condense(query.intervals)
    segs = _segments_for(segments, intervals)
    if not segs:
        return []
    needle = query.value if query.case_sensitive else query.value.lower()

    def matches(v: str) -> bool:
        h = v if query.case_sensitive else v.lower()
        return needle in h

    hits: Dict[Tuple[str, str], int] = {}
    for s in segs:
        row_ids = _masked_row_ids(s, query)
        dims = list(query.search_dimensions) or list(s.dims.keys())
        for d in dims:
            col = s.dims.get(d)
            if col is None:
                continue
            cnt = np.bincount(col.ids[row_ids], minlength=col.cardinality)
            for vid, c in enumerate(cnt):
                if c > 0 and matches(col.dictionary.values[vid]):
                    key = (d, col.dictionary.values[vid])
                    hits[key] = hits.get(key, 0) + int(c)

    entries = [{"dimension": d, "value": v, "count": c}
               for (d, v), c in hits.items()]
    if query.sort == "strlen":
        entries.sort(key=lambda e: (len(e["value"]), e["value"], e["dimension"]))
    else:
        entries.sort(key=lambda e: (e["value"], e["dimension"]))
    entries = entries[: query.limit]
    ts = int(min(iv.start for iv in intervals))
    return [{"timestamp": ts, "result": entries}]


# ---------------------------------------------------------------------------
# TimeBoundary / SegmentMetadata / DataSourceMetadata
# ---------------------------------------------------------------------------

def run_time_boundary(query: TimeBoundaryQuery,
                      segments: Sequence[Segment]) -> List[dict]:
    intervals = condense(query.intervals)
    segs = _segments_for(segments, intervals)
    min_t, max_t = None, None
    for s in segs:
        if query.filter is None and len(intervals) == 1 \
                and intervals[0].contains_interval(Interval(s.min_time, s.max_time + 1)):
            lo, hi = s.min_time, s.max_time
        else:
            row_ids = _masked_row_ids(s, query)
            if len(row_ids) == 0:
                continue
            t = s.time_ms[row_ids]
            lo, hi = int(t.min()), int(t.max())
        min_t = lo if min_t is None else min(min_t, lo)
        max_t = hi if max_t is None else max(max_t, hi)
    if min_t is None:
        return []
    result = {}
    if query.bound in (None, "minTime"):
        result["minTime"] = min_t
    if query.bound in (None, "maxTime"):
        result["maxTime"] = max_t
    ts = min_t if query.bound != "maxTime" else max_t
    return [{"timestamp": ts, "result": result}]


def _analyze_segment(segment: Segment, query: SegmentMetadataQuery) -> dict:
    """reference: query/metadata/SegmentAnalyzer.java"""
    cols = {}
    names = list(query.to_include) or (
        ["__time"] + list(segment.dims.keys()) + list(segment.metrics.keys()))
    want = set(query.analysis_types)
    for c in names:
        info: Dict[str, object] = {"hasMultipleValues": False,
                                   "errorMessage": None}
        if c == "__time":
            info["type"] = "LONG"
            if "size" in want:
                info["size"] = int(segment.time_ms.nbytes)
            if "minmax" in want:
                info["minValue"] = segment.min_time
                info["maxValue"] = segment.max_time
        elif c in segment.dims:
            col = segment.dims[c]
            info["type"] = "STRING"
            if "cardinality" in want:
                info["cardinality"] = col.cardinality
            if "size" in want:
                info["size"] = int(col.ids.nbytes)
            if "minmax" in want and col.cardinality:
                info["minValue"] = col.dictionary.values[0]
                info["maxValue"] = col.dictionary.values[-1]
        elif c in segment.metrics:
            m = segment.metrics[c]
            info["type"] = m.type.value.upper()
            if "size" in want:
                info["size"] = int(m.values.nbytes)
            if "minmax" in want and segment.n_rows:
                info["minValue"] = _scalar(m.values.min())
                info["maxValue"] = _scalar(m.values.max())
        else:
            continue
        cols[c] = info
    return {"id": str(segment.id),
            "intervals": [str(segment.interval)] if "interval" in want else None,
            "columns": cols,
            "size": segment.size_bytes(),
            "numRows": segment.n_rows}


def run_segment_metadata(query: SegmentMetadataQuery,
                         segments: Sequence[Segment]) -> List[dict]:
    intervals = condense(query.intervals)
    segs = _segments_for(segments, intervals)
    analyses = [_analyze_segment(s, query) for s in segs]
    if not query.merge or not analyses:
        return analyses
    merged = analyses[0]
    for a in analyses[1:]:
        merged["size"] += a["size"]
        merged["numRows"] += a["numRows"]
        if merged["intervals"] is not None and a["intervals"]:
            merged["intervals"] = sorted(set(merged["intervals"] + a["intervals"]))
        for c, info in a["columns"].items():
            if c not in merged["columns"]:
                merged["columns"][c] = info
            else:
                tgt = merged["columns"][c]
                for k in ("size",):
                    if k in info and k in tgt:
                        tgt[k] += info[k]
                for k in ("cardinality",):
                    if k in info and k in tgt:
                        tgt[k] = max(tgt[k], info[k])
                if "minValue" in info and "minValue" in tgt:
                    tgt["minValue"] = min(tgt["minValue"], info["minValue"])
                    tgt["maxValue"] = max(tgt["maxValue"], info["maxValue"])
    merged["id"] = "merged"
    return [merged]


def run_datasource_metadata(query: DataSourceMetadataQuery,
                            segments: Sequence[Segment]) -> List[dict]:
    if not segments:
        return []
    mx = max(s.max_time for s in segments)
    return [{"timestamp": mx, "result": {"maxIngestedEventTime": mx}}]
