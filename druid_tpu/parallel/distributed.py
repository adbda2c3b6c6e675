"""Sharded multi-segment execution: one device program for a whole query.

Reference analog, inverted for TPU:
  * ChainedExecutionQueryRunner.java (thread-pool per-segment runners) →
    segments stacked on a leading axis, `jax.vmap` over it;
  * CachingClusteredClient.java:253 scatter-gather + MergeSequence →
    `shard_map` over a mesh axis, partial states merged with
    psum/pmin/pmax/all_gather collectives over ICI;
  * epinephelinae/ParallelCombiner.java combining tree → the XLA collective
    is the combining tree.

The stacked blocks are COMPRESSED-RESIDENT: each shard carries per-segment
packed words (data/packed.py tile-planar layout), cascade columns (RLE run
tables, delta/FOR words — data/cascade.py), resident filter-bitmap words
(engine/filters.py DeviceBitmapNode slots), and each segment's validity as
its row count (cascade.PrefixMaskColumn: `__valid` is `iota < n_rows`, one
compare), and the program decodes at its top through the same
`cascade.split_resident` every other path calls — one decode/filter story
for per-segment, batched and sharded execution. Every PartitionSpec comes from parallel/speclayout.py (the
canonical SpecLayout; lint-enforced single source), and partial grids are
merged ON DEVICE by the collectives — the broker-side host merge for this
path is gone; `host_from_device` below only converts the already-merged
replicated states to their host representation.

Eligibility (else callers fall back to per-segment host-merged execution):
dense key mode, "all"/"uniform" bucketing, and identical plan constants
(filter LUTs, kernel aux, dim remaps) across segments — true whenever
segments share dictionaries, which the ingestion path guarantees per
datasource generation (the analog of DimensionMergerV9's unified dictionary).
"""
from __future__ import annotations

import collections
import functools
import hashlib
import threading
import weakref
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from druid_tpu.data import cascade as cascade_mod
from druid_tpu.data import devicepool
from druid_tpu.data import packed as packed_mod
from druid_tpu.data.segment import Segment
from druid_tpu.engine import filters as filters_mod
from druid_tpu.engine.filters import ConstNode
from druid_tpu.engine import grouping
from druid_tpu.engine.contracts import (named_program, program_name,
                                        sharded_fallback_reason)
from druid_tpu.engine.grouping import (GroupPlan, GroupSpec, KeyDim,
                                       SegmentPartial, assemble_stacked_aux,
                                       aux_equal, common_window,
                                       fetch_partials, keydims_equal,
                                       plan_grouped_aggregate,
                                       stacked_origins, traced_segment,
                                       windowed_window)
from druid_tpu.engine.kernels import AggKernel
from druid_tpu.obs.trace import span as trace_span
from druid_tpu.obs.trace import span_when as trace_span_when
from druid_tpu.parallel import context, speclayout
from druid_tpu.query.aggregators import AggregatorSpec
from druid_tpu.utils.emitter import Monitor
from druid_tpu.utils.granularity import Granularity
from druid_tpu.utils.intervals import Interval

# Jitted sharded programs, LRU-bounded: entries capture kernel aux arrays in
# their closures, so an unbounded cache would pin host memory across segment
# generations. Locked: concurrent queries racing evict vs move_to_end would
# KeyError (shard_map/jit construction is lazy, so building under the lock
# is cheap).
_FN_CACHE: "collections.OrderedDict[Tuple, object]" = collections.OrderedDict()
_FN_CACHE_CAP = 64
_CACHE_LOCK = threading.Lock()


class _StackOwner:
    """Anchor object owning the stacked-shard entries in the device pool.

    Stacked blocks pin whole segment sets in HBM; instead of a private
    count-capped LRU they live in the process-wide DeviceSegmentPool under
    this owner, accounted at actual bytes against DEVICE_POOL_BUDGET_BYTES
    (satellite of the old `_STACK_CACHE`). The anchor is module-lived, so
    entries only leave through LRU pressure or clear_stack_cache()."""


_STACK_ANCHOR: Optional[_StackOwner] = None
_STACK_TOKEN: Optional[int] = None
_STACK_POOL: Optional["weakref.ref"] = None


def _stack_owner_token(pool: "devicepool.DeviceSegmentPool") -> int:
    """Lazily (re-)register the stack owner: purge_owner removes the
    registry slot, so after clear_stack_cache() the next stacking must
    register a fresh token or the pool would refuse its inserts. The
    token is only valid for the pool it was registered on — when the
    process pool is swapped (tests monkeypatch isolated pools), the old
    pool's stacked entries are purged and a fresh token registers on the
    new one, so there is always at most ONE live stack owner."""
    global _STACK_ANCHOR, _STACK_TOKEN, _STACK_POOL
    with _CACHE_LOCK:
        prev = _STACK_POOL() if _STACK_POOL is not None else None
        if _STACK_TOKEN is None or prev is not pool:
            if prev is not None and _STACK_TOKEN is not None:
                # _CACHE_LOCK -> pool lock is the documented order; the
                # pool never takes _CACHE_LOCK
                prev.purge_owner(_STACK_TOKEN)
            _STACK_ANCHOR = _StackOwner()
            _STACK_TOKEN = pool.register_owner(_STACK_ANCHOR)
            _STACK_POOL = weakref.ref(pool)
        return _STACK_TOKEN


class _ShardedPlan(NamedTuple):
    """What `_plan_sharded` hands the run: the per-segment plans of an
    eligible segment set (they agree on every constant; the first speaks
    for the program) and what only a stack derives."""
    plans: List[GroupPlan]
    cascades: Tuple
    packs: Tuple
    selected: str       # select_strategy's choice; the spec's is what runs


def try_sharded(segments: Sequence[Segment], intervals: Sequence[Interval],
                granularity: Granularity,
                kds_per_seg: Sequence[Sequence[KeyDim]],
                aggs: Sequence[AggregatorSpec], flt,
                virtual_columns: Sequence = ()) -> Optional[SegmentPartial]:
    """Run the grouped aggregate for all segments as ONE sharded device
    program; returns a single merged SegmentPartial, or None if ineligible
    (caller falls back to the per-segment path). Past the mesh check every
    exit is under `engine/sharded/plan`: a fall-back says so (`fallback` 1,
    `reason` one of contracts.SHARDED_FALLBACK_REASONS) and is counted, so a
    mesh node that serves a query on one device never does so silently."""
    mesh = context.get_mesh()
    if mesh is None or not segments:
        return None
    with trace_span("engine/sharded/plan", segments=len(segments)) as sp:
        plan = _plan_sharded(mesh, segments, intervals, granularity,
                             kds_per_seg, aggs, flt, virtual_columns)
        fallback = isinstance(plan, str)
        if sp is not None:
            sp.attrs["fallback"] = int(fallback)
            if fallback:
                sp.attrs["reason"] = plan
            elif isinstance(plan, _ShardedPlan):
                sp.attrs["selected"] = plan.selected
                sp.attrs["strategy"] = plan.plans[0].spec.strategy
    if fallback:
        _SHARDED_STATS.record_fallback()
        return None
    if isinstance(plan, SegmentPartial):
        return plan         # a const-false filter's whole-query zero
    return _run_sharded(mesh, plan, segments, intervals)


def _plan_sharded(mesh, segments: Sequence[Segment],
                  intervals: Sequence[Interval], granularity: Granularity,
                  kds_per_seg: Sequence[Sequence[KeyDim]],
                  aggs: Sequence[AggregatorSpec], flt,
                  virtual_columns: Sequence):
    """Eligibility and the per-segment plans of one query: a `_ShardedPlan`,
    the whole-query zero of a const-false filter, or the fall-back's reason
    (contracts.SHARDED_FALLBACK_REASONS, the closed set)."""
    import jax
    if any(d.process_index != jax.process_index()
           for d in mesh.devices.flat):
        # cross-process mesh: the stacked program would need every shard's
        # data process-addressable; host-level combine is the broker's job
        return sharded_fallback_reason("cross_process_mesh")
    kds = list(kds_per_seg[0])
    if any(d.host_ids is not None for d in kds):
        # numeric-dimension ids are per-segment query-time dictionaries —
        # a stacked program cannot share one id space; per-segment path
        # merges them host-side
        return sharded_fallback_reason("numeric_dimension")
    for other in kds_per_seg[1:]:
        if not keydims_equal(kds, other):
            return sharded_fallback_reason("key_dims_differ")
    # raw (remap-free) key dims fuse dictionary ids directly, so the
    # dictionaries themselves must agree across segments — equal cardinality
    # is NOT enough (ids would decode through segments[0]'s values)
    for d in kds:
        if d.column is None:
            continue
        first = segments[0].dims[d.column].dictionary
        for s in segments[1:]:
            other = s.dims.get(d.column)
            if other is None:
                return sharded_fallback_reason("key_dimension_missing")
            if other.dictionary is not first and \
                    list(other.dictionary.values) != list(first.values):
                return sharded_fallback_reason("dictionaries_differ")

    # ONE planner (grouping.plan_grouped_aggregate) for every segment; the
    # first is planned first, and alone until its key mode is known: a
    # host-keyed group spec sorts a segment's rows
    p0 = plan_grouped_aggregate(segments[0], intervals, granularity, kds,
                                aggs, flt, virtual_columns)
    spec0, filter_node, kernels = p0.spec, p0.filter_node, p0.kernels
    if spec0.key_mode != "dense" or spec0.bucket_mode not in ("all", "uniform"):
        return sharded_fallback_reason("key_or_bucket_mode")

    # filter + kernels + virtual columns are planned per segment; constants
    # must agree across segments. Device-bitmap compilation follows the
    # process default (the stacked program reads resident `__fbmpN` word
    # slots, exactly like the per-segment program) — slots are assigned per
    # plan BEFORE signatures are compared, so filtered-aggregator trees
    # cannot collide with the query filter's slot 0.
    f_sig = filter_node.signature() if filter_node else "none"
    plans = [p0]
    for s, kds_s in zip(segments[1:], kds_per_seg[1:]):
        p = plan_grouped_aggregate(s, intervals, granularity, kds_s, aggs,
                                   flt, virtual_columns)
        fn_s = p.filter_node
        if (fn_s.signature() if fn_s else "none") != f_sig:
            return sharded_fallback_reason("filter_plans_differ")
        if not aux_equal(p.f_aux, p0.f_aux):
            return sharded_fallback_reason("filter_constants_differ")
        if [k.signature() for k in p.kernels] \
                != [k.signature() for k in kernels]:
            return sharded_fallback_reason("kernel_plans_differ")
        if not aux_equal(p.k_aux, p0.k_aux):
            return sharded_fallback_reason("kernel_constants_differ")
        if repr(p.vc_plans) != repr(p0.vc_plans) \
                or not aux_equal(p.vc_luts, p0.vc_luts):
            return sharded_fallback_reason("virtual_columns_differ")
        plans.append(p)
    # only after every segment agreed on the plan is a const-false filter a
    # whole-query zero (a column may exist in some segments only)
    if isinstance(filter_node, ConstNode) and not filter_node.value:
        return SegmentPartial(
            segment=segments[0], spec=spec0,
            counts=np.zeros(spec0.num_total, dtype=np.int64),
            states={k.name: k.empty_state(spec0.num_total) for k in kernels},
            kernels=kernels)

    # every needed column must have the same presence, kind AND dtype in all
    # segments: the plain path handles per-segment differences (missing
    # aggregates as zero), but one stacked program cannot — fall back rather
    # than KeyError, silently cast, or crash. Complex (2-D) metric columns
    # also fall back: the stacker allocates [K, R] only. The plans' needs
    # are the PLANNED trees': bitmap-compiled subtrees stage no columns
    # (their data rides in the word slots).
    for c in p0.needed:
        in_dim0 = c in segments[0].dims
        met0 = segments[0].metrics.get(c)
        if met0 is not None and np.asarray(met0.values).ndim != 1:
            return sharded_fallback_reason("complex_metric")
        for s, p in zip(segments[1:], plans[1:]):
            if (c in s.dims) != in_dim0:
                return sharded_fallback_reason("dimension_presence_differs")
            met = s.metrics.get(c)
            if (met is None) != (met0 is None):
                return sharded_fallback_reason("metric_presence_differs")
            if met is not None and (met.type is not met0.type
                                    or met.values.dtype != met0.values.dtype
                                    or p.col_dtypes.get(c)
                                    != p0.col_dtypes.get(c)):
                return sharded_fallback_reason("metric_types_differ")

    # compressed slots: the descriptor pair every segment can agree on
    # (cascade entries + pack entries) — the descriptors join the stack
    # pool key AND _sharded_sig below, so chunk-mates agree and the cached
    # program's treedef is pinned
    cascades, packs = _common_descriptors(segments, p0.columns)
    R, _K = _stack_shape(
        segments, mesh.shape[speclayout.layout_for(mesh).seg_axis])

    # reduction strategy must agree across the whole stacked program; the
    # windowed path needs every segment's host span check to pass.
    # select_strategy via the module so tests forcing a strategy
    # (monkeypatching grouping.select_strategy) also steer the sharded path
    spec0.strategy, spec0.window = grouping.select_strategy(
        spec0, kernels, p0.col_dtypes, R,
        lambda: common_window(
            windowed_window(s, intervals, granularity, p.spec)
            for s, p in zip(segments, plans)))
    selected = spec0.strategy
    if selected == "projection":
        # sorted projections are per-segment layouts the stacked program
        # cannot share, so the stacked program overrides to the XLA scatter
        # (`mixed`); the plan span carries both (`selected`, `strategy`).
        # What the override costs against the meshless per-segment Pallas
        # path is measured, not assumed: PERF.md §5, `mesh4-analyst-groupby`
        # against `analyst-groupby`.
        spec0.strategy, spec0.window = "mixed", 0
    return _ShardedPlan(plans=plans, cascades=cascades, packs=packs,
                        selected=selected)


def _run_sharded(mesh, plan: _ShardedPlan, segments: Sequence[Segment],
                 intervals: Sequence[Interval]) -> SegmentPartial:
    """Stack look-up (or build), per-request H2D, the ONE dispatch and its
    fetch, each under its span."""
    layout = speclayout.layout_for(mesh)
    axis = layout.seg_axis
    n_dev = mesh.shape[axis]
    p0 = plan.plans[0]
    spec0, kernels = p0.spec, p0.kernels
    stacked, time0s, R, K = _stack_segments(
        mesh, segments, p0.columns, plan.cascades, plan.packs,
        [p.filter_node for p in plan.plans],
        [p.kernels for p in plan.plans], layout)

    # per-segment RELATIVE interval bounds + bucket start offsets (the
    # stack holds the segments' starts)
    with trace_span("engine/sharded/put") as put_span:
        _, iv_rel, bucket_off = stacked_origins(
            segments, [intervals] * len(segments),
            [p.spec for p in plan.plans], K)
        aux = assemble_stacked_aux(spec0, p0.f_aux, p0.k_aux, p0.vc_luts)
        if put_span is not None:
            put_span.attrs["bytes"] = iv_rel.nbytes + bucket_off.nbytes \
                + devicepool.entry_bytes(aux)
        iv_rel = layout.put_interval_bounds(mesh, iv_rel)
        bucket_off = layout.put_bucket_offsets(mesh, bucket_off)

    sig = _sharded_sig(mesh, axis, spec0, p0.filter_node, kernels,
                       len(intervals), p0.vc_plans, K, R, p0.columns,
                       plan.cascades, plan.packs, p0.n_slots, layout)
    with _CACHE_LOCK:
        fn = _FN_CACHE.get(sig)
        # the miss IS the compile event (shard_map traces/compiles on the
        # first call below) — timing stays at the existing dispatch boundary
        compiled = fn is None
        if fn is None:
            fn = _build_sharded_fn(mesh, axis, n_dev, spec0, p0.filter_node,
                                   kernels, p0.vc_plans, layout, stacked)
            _FN_CACHE[sig] = fn
            while len(_FN_CACHE) > _FN_CACHE_CAP:
                _FN_CACHE.popitem(last=False)
        else:
            _FN_CACHE.move_to_end(sig)
    from druid_tpu.obs import dispatch as dispatch_mod
    dispatch_mod.record("sharded")
    with trace_span("engine/sharded/dispatch", strategy=spec0.strategy,
                    segments=K, devices=n_dev, compile=compiled,
                    program=program_name("sharded_agg",
                                         spec0.strategy)), \
            trace_span_when(compiled, "engine/compile", kind="sharded",
                            strategy=spec0.strategy):
        out = fn(stacked, time0s, iv_rel, bucket_off, aux)
    _SHARDED_STATS.record(len(segments))

    # NOT a host merge: counts/states left the program replicated and
    # already collective-merged; host_from_device only converts the merged
    # device representation (HLL registers, first/last packed pairs) to
    # the host one, exactly like the single-segment path does per segment
    partial, = fetch_partials(
        [(segments[0], spec0, kernels)], [out],
        post=lambda kernel, state, _segment: kernel.host_from_device(state),
        segments=K)
    return partial


def _common_descriptors(segments: Sequence[Segment],
                        columns: Tuple[str, ...]) -> Tuple[Tuple, Tuple]:
    """The (cascade, pack) descriptor pair EVERY segment can stage under.

    Per-segment plans come from the one shared derivation
    (cascade.plan_pair); a column keeps its encoding only when all
    segments planned the same (name, kind) with stack-compatible params:
    RLE run-table lengths normalize to the max (pow2 stays pow2, and
    encode_column pads per entry[2]), delta/FOR widths+bases must match
    exactly (word shapes must stack), and `lz4host` drops out (it stages
    the exact host-roundtripped decoded rows anyway). Everything else
    falls back to decoded [K, R] slots — never to a fallback PATH."""
    per_seg = [cascade_mod.plan_pair(s, columns) for s in segments]
    casc0, packs0 = per_seg[0]
    cascades: List[Tuple] = []
    for entry in casc0:
        name, kind = entry[0], entry[1]
        if kind == "lz4host":
            continue
        mates = []
        for cs, _ in per_seg:
            mate = next((e for e in cs if e[0] == name), None)
            if mate is None or mate[1] != kind:
                mates = None
                break
            mates.append(mate)
        if mates is None:
            continue
        if kind == "rle":
            # run counts are per-segment data; the stacked run tables pad
            # to the widest (max of pow2 paddings is one of them)
            cascades.append((name, kind, max(m[2] for m in mates)))
        elif all(m == entry for m in mates):
            cascades.append(entry)
    claimed = {e[0] for e in cascades}
    packs = tuple(e for e in packs0
                  if e[0] not in claimed
                  and all(e in ps for _, ps in per_seg))
    return tuple(cascades), packs


def _bitmap_nodes(filter_node, kernels: Sequence[AggKernel]) -> List:
    """Every DeviceBitmapNode of one segment's plan, slot order (the query
    filter's tree first, then each kernel's filter trees — the same walk
    assign_bitmap_slots numbers)."""
    nodes = list(filters_mod.collect_bitmap_nodes(filter_node))
    for k in kernels:
        for tree in k.filter_trees():
            nodes.extend(filters_mod.collect_bitmap_nodes(tree))
    return nodes


def _bitmap_digest(seg_filters: Sequence, seg_kernels: Sequence) -> str:
    """Content digest of every segment's bitmap-node set for the stack pool
    key: bitmap LUTs ride the stacked WORDS (per-segment data, aux-free by
    the DeviceBitmapNode contract), so two plans that differ only in word
    content must stack under different keys."""
    h = hashlib.sha1()
    any_nodes = False
    for fn_s, ks in zip(seg_filters, seg_kernels):
        for node in _bitmap_nodes(fn_s, ks):
            any_nodes = True
            h.update(node.col.encode())
            h.update(b"|")
            h.update(node.structure_sig().encode())
            h.update(b"|")
            h.update(node.digest().encode())
        h.update(b"||")
    return h.hexdigest()[:16] if any_nodes else ""


def _stack_tree(cols: List, K: int):
    """Stack K per-segment column pytrees (decoded arrays, PackedColumn,
    RLE/FOR/delta columns, the prefix mask) leaf-wise onto a leading
    segment axis. Padding segments are zeroed copies of the first: a zero
    row count is an all-invalid `__valid`, packed/FOR zeros decode to the
    base — every consumer masks them through `__valid`. Descriptor
    agreement (_common_descriptors) guarantees equal treedefs, so
    per-segment row counts/firsts ride as stacked [K] scalar leaves, not
    aux."""
    import jax
    if len(cols) < K:
        pad = jax.tree.map(lambda leaf: np.zeros_like(np.asarray(leaf)),
                           cols[0])
        cols = list(cols) + [pad] * (K - len(cols))
    return jax.tree.map(
        lambda *leaves: np.stack([np.asarray(l) for l in leaves], axis=0),
        *cols)


def _stack_segments(mesh, segments: Sequence[Segment],
                    columns: Tuple[str, ...], cascades: Tuple, packs: Tuple,
                    seg_filters: Sequence, seg_kernels: Sequence,
                    layout: "speclayout.SpecLayout"):
    """Stack segments into COMPRESSED-RESIDENT [K, ...] slots sharded over
    the mesh axis: cascade columns (RLE run tables, delta/FOR words, the
    validity's row counts), packed words, resident filter-bitmap words,
    decoded rows for the rest — the sharded program decodes in-program
    through cascade.split_resident exactly like the per-segment program.

    K pads to a multiple of the axis size with empty (all-invalid)
    segments; R pads rows to the max padded row count (1024-aligned — a
    multiple of every pack width's tile quantum). Stacks live in the
    process-wide device pool under the stack owner, accounted at actual
    bytes against the pool budget (PoolStats.stacked_*) — repeat queries
    reuse HBM-resident shards, the analog of the reference keeping
    segments mmapped across queries."""
    axis = layout.seg_axis
    n_dev = mesh.shape[axis]
    pool = devicepool.device_pool()
    # keyed by object identity, not segment-id strings: rebuilt segments can
    # legitimately reuse (datasource, interval, version, partition) and must
    # not be served stale stacked data. The cached value pins the segment
    # objects, so their id()s cannot be recycled while the entry lives. The
    # descriptors/bitmap digest join the key: latch flips (packed/cascade/
    # device-bitmap) and filter-word content changes restack.
    key = (devicepool.STACKED_KIND, tuple(id(s) for s in segments), columns,
           n_dev, tuple(int(d.id) for d in mesh.devices.flat), cascades,
           packs, _bitmap_digest(seg_filters, seg_kernels))
    built_bytes = None          # stays None on a pool hit

    def build():
        nonlocal built_bytes
        value = _build_stack(mesh, segments, columns, cascades, packs,
                             seg_filters, seg_kernels, layout, n_dev)
        built_bytes = devicepool.entry_bytes(value)
        return value

    with trace_span("engine/sharded/stack", segments=len(segments),
                    devices=n_dev) as sp:
        dev_arrays, dev_time0s, R, K = pool.get_or_build(
            _stack_owner_token(pool), key, build)[:4]
        if sp is not None:
            sp.attrs.update(hit=built_bytes is None,
                            builtBytes=built_bytes or 0,
                            paddedSegments=K, rows=R,
                            validity=dev_arrays["__valid"].cascade_kind)
    return dev_arrays, dev_time0s, R, K


def _stack_shape(segments: Sequence[Segment], n_dev: int) -> Tuple[int, int]:
    """(R, K) of the segments' stack: rows padded to the widest segment,
    1024-aligned (pack_padded's tile quantum, 128 * values per word, for
    every contract width, 4/8/16 alike); segments padded to a multiple of
    the mesh axis."""
    align = 1024
    R = max(align, max(((s.n_rows + align - 1) // align) * align
                       for s in segments))
    K = ((len(segments) + n_dev - 1) // n_dev) * n_dev
    return R, K


def _build_stack(mesh, segments: Sequence[Segment], columns: Tuple[str, ...],
                 cascades: Tuple, packs: Tuple,
                 seg_filters: Sequence, seg_kernels: Sequence,
                 layout: "speclayout.SpecLayout", n_dev: int):
    R, K = _stack_shape(segments, n_dev)
    casc_by_name = {e[0]: e for e in cascades}
    pack_by_name = {e[0]: (e[1], e[2]) for e in packs}

    def padded_col(s: Segment, name: str) -> np.ndarray:
        if name == "__time_offset":
            off = s.time_ms - s.interval.start
            if off.size and (off.min() < 0 or off.max() >= 2**31):
                raise ValueError(f"segment {s.id} outside int32 offset range")
            a = off.astype(np.int32)
        elif name in s.dims:
            a = s.dims[name].ids
        else:
            m = s.metrics[name]
            dt = s.staged_dtype(name)   # int32-narrowed longs stay narrow
            a = m.values if m.values.dtype == dt else m.values.astype(dt)
        out = np.zeros(R, dtype=a.dtype)
        out[: a.shape[0]] = a
        return out

    def encoded_col(s: Segment, name: str):
        padded = padded_col(s, name)
        entry = casc_by_name.get(name)
        if entry is not None:
            # host identity `put`: device placement happens once for the
            # whole stack below, with the layout's shardings
            return cascade_mod.encode_column(s, name, entry, padded,
                                             lambda x: x)
        wb = pack_by_name.get(name)
        if wb is not None:
            w, base = wb
            return packed_mod.PackedColumn(
                packed_mod.pack_padded(padded, w, base), w, base, R,
                str(padded.dtype))
        return padded

    arrays: Dict[str, object] = {}
    for name in ("__time_offset",) + tuple(columns):
        arrays[name] = _stack_tree([encoded_col(s, name) for s in segments],
                                   K)

    # validity is a row count a segment (4 bytes instead of R bools): the
    # program decodes `iota < n_rows`, bit-exact with the dense mask;
    # _stack_tree's padding segments count 0 rows
    arrays["__valid"] = _stack_tree(
        [cascade_mod.PrefixMaskColumn(np.asarray(s.n_rows, dtype=np.int32), R)
         for s in segments], K)

    # resident filter-bitmap words: the segments stage as ONE pooled wave
    # (query/filter/* accounting included; one hand-over and one fill for
    # the stack's cold segments), then each `__fbmpN` slot is stacked;
    # padding segments keep zero words (no row passes)
    bitmap_cols: Dict[str, np.ndarray] = {}
    with filters_mod.words_span(segments=K):
        staged = filters_mod.stage_device_bitmaps_multi(
            list(zip(segments, seg_filters, seg_kernels)), R)
        for i, words in enumerate(staged):
            for col, w in words.items():
                host = np.asarray(w)
                slot = bitmap_cols.get(col)
                if slot is None:
                    slot = np.zeros((K,) + host.shape, dtype=host.dtype)
                    bitmap_cols[col] = slot
                slot[i] = host
    arrays.update(bitmap_cols)

    time0s = np.zeros((K,), dtype=np.int64)
    for i, s in enumerate(segments):
        time0s[i] = s.interval.start

    dev_arrays = layout.put_stacked(mesh, arrays)
    dev_time0s = layout.put_time0s(mesh, time0s)
    # stacked column objects carry per-SEGMENT aux (the vmapped decode
    # slices one segment at a time), so their logical_nbytes describes one
    # segment while their leaves hold K — restore the missing (K-1) share
    # for the pool's decoded-equivalent accounting
    corr = sum((K - 1) * int(v.logical_nbytes)
               for v in dev_arrays.values()
               if getattr(v, "logical_nbytes", None) is not None)
    # the trailing segment tuple pins the objects (id()-recycling guard);
    # Segment carries no nbytes, so it counts 0 in the pool accounting
    return (dev_arrays, dev_time0s, R, K, tuple(segments),
            devicepool.LogicalBytes(corr))


def clear_stack_cache() -> int:
    """Release the HBM-resident stacked segment sets (and the segment
    objects each entry deliberately pins). Returns the entry count
    dropped. The ops analog of unloading segments to reclaim HBM without
    a restart — engine.release_device_caches() is the public surface."""
    global _STACK_TOKEN, _STACK_POOL
    with _CACHE_LOCK:
        token, _STACK_TOKEN = _STACK_TOKEN, None
        pool = _STACK_POOL() if _STACK_POOL is not None else None
        _STACK_POOL = None
    if token is None or pool is None:
        return 0
    n = pool.snapshot().stacked_entries
    pool.purge_owner(token)
    return n


def clear_fn_cache() -> int:
    """Drop the jitted sharded programs (their closures pin kernel aux
    arrays across segment generations)."""
    with _CACHE_LOCK:
        n = len(_FN_CACHE)
        _FN_CACHE.clear()
        return n


def _sharded_sig(mesh, axis, spec: GroupSpec, filter_node, kernels,
                 n_intervals, vc_plans, K, R, columns, cascades, packs,
                 n_bitmap_slots, layout) -> Tuple:
    """Cache key of one sharded program: the plan's structure as every
    builder keys it (grouping._structure_sig) and what pins the stacked
    pytree's treedef — mesh layout, [K, R], staged column set, bitmap slot
    count (`__valid` is always the prefix mask, its one static field R) —
    so two queries share a cached program only when their stacks share a
    structure."""
    return (speclayout.layout_sig(layout, mesh), axis,
            grouping._structure_sig(spec, n_intervals, filter_node, kernels,
                                    vc_plans, packs, cascades),
            K, R, columns, n_bitmap_slots)


def _merge_states(kernel: AggKernel, stacked_state, axis: str, n_dev: int,
                  k_local: int):
    """Fold per-segment states over the local axis, then across the mesh."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    kind = kernel.reduce_kind
    if kind == "sum":
        def local(x):
            if jnp.issubdtype(x.dtype, jnp.integer):
                # int64 before psum: exactness contract, x64 globally on
                x = x.astype(jnp.int64)  # druidlint: disable=x64-dtype
            return x.sum(axis=0)
        st = jax.tree.map(local, stacked_state)
        return jax.tree.map(lambda x: lax.psum(x, axis), st)
    if kind == "max":
        st = jax.tree.map(lambda x: x.max(axis=0), stacked_state)
        return jax.tree.map(lambda x: lax.pmax(x, axis), st)
    if kind == "min":
        st = jax.tree.map(lambda x: x.min(axis=0), stacked_state)
        return jax.tree.map(lambda x: lax.pmin(x, axis), st)
    # fold: pairwise device_combine locally, all_gather + fold across devices
    parts = [jax.tree.map(lambda x, i=i: x[i], stacked_state)
             for i in range(k_local)]
    st = functools.reduce(kernel.device_combine, parts)
    gathered = jax.tree.map(
        lambda x: lax.all_gather(x, axis, axis=0, tiled=False), st)
    parts = [jax.tree.map(lambda x, i=i: x[i], gathered) for i in range(n_dev)]
    return functools.reduce(kernel.device_combine, parts)


def _build_sharded_fn(mesh, axis: str, n_dev: int, spec: GroupSpec,
                      filter_node, kernels: List[AggKernel], vc_plans: Tuple,
                      layout: "speclayout.SpecLayout", stacked):
    import jax
    import jax.numpy as jnp
    from jax import shard_map

    def per_segment(arrays, time0, iv_rel, bucket_off, aux):
        counts, states = traced_segment(spec, filter_node, kernels, vc_plans,
                                        arrays, time0, iv_rel, bucket_off,
                                        aux)
        states = tuple(k.device_post(s, time0)
                       for k, s in zip(kernels, states))
        return counts, states

    def body(stacked, time0s, iv_rel, bucket_off, aux):
        k_local = time0s.shape[0]
        counts, states = jax.vmap(
            lambda a, t0, ivr, boff: per_segment(a, t0, ivr, boff, aux))(
                stacked, time0s, iv_rel, bucket_off)
        # int64 count totals across devices: exactness, x64 globally on
        counts = jax.lax.psum(counts.astype(jnp.int64).sum(axis=0), axis)  # druidlint: disable=x64-dtype
        merged = tuple(
            _merge_states(k, st, axis, n_dev, k_local)
            for k, st in zip(kernels, states))
        return counts, merged

    # fold-merged states go through all_gather, whose output the vma system
    # conservatively marks varying even though it is replicated by
    # construction — turn the static replication check off for those.
    has_fold = any(k.reduce_kind == "fold" for k in kernels)
    f = shard_map(body, mesh=mesh,
                  in_specs=layout.in_specs(stacked),
                  out_specs=layout.out_specs(), check_vma=not has_fold)
    return jax.jit(named_program(f, program_name("sharded_agg",
                                                 spec.strategy)))


# ---------------------------------------------------------------------------
# Observability: query/sharded/* metrics
# ---------------------------------------------------------------------------

class ShardedStats:
    """merged_device = sharded dispatches whose partials were merged by the
    in-program collectives (every dispatch since the host-merge tail was
    removed — the counter exists so its constancy is assertable);
    segments = segments those dispatches covered; fallbacks = queries a
    mesh node found ineligible and handed to the per-segment path."""

    def __init__(self):
        self._lock = threading.Lock()
        self.merged_device = 0
        self.segments = 0
        self.fallbacks = 0

    def record(self, n_segments: int) -> None:
        with self._lock:
            self.merged_device += 1
            self.segments += n_segments

    def record_fallback(self) -> None:
        with self._lock:
            self.fallbacks += 1

    def snapshot(self) -> Tuple[int, int, int]:
        with self._lock:
            return (self.merged_device, self.segments, self.fallbacks)


_SHARDED_STATS = ShardedStats()


def sharded_stats() -> ShardedStats:
    """The process-wide sharded-dispatch stats (tests + ShardedMonitor)."""
    return _SHARDED_STATS


class ShardedMonitor(Monitor):
    """Emits `query/sharded/*` per tick: device-merged dispatches and
    fall-backs over the tick window, and the stacked-shard residency gauges
    from the device pool's stacked accounting."""

    def __init__(self, stats: Optional[ShardedStats] = None,
                 pool: Optional["devicepool.DeviceSegmentPool"] = None):
        self.stats = stats or sharded_stats()
        self.pool = pool or devicepool.device_pool()
        self._last = (0, 0, 0)

    def do_monitor(self, emitter) -> None:
        s = self.stats.snapshot()
        last, self._last = self._last, s
        emitter.metric("query/sharded/mergeDevice", s[0] - last[0])
        emitter.metric("query/sharded/fallback", s[2] - last[2])
        p = self.pool.snapshot()
        emitter.metric("query/sharded/stackBytes", p.stacked_bytes)
        emitter.metric("query/sharded/packedRatio", p.stacked_ratio)
