"""Batched multi-segment device execution: dispatch amortization without a mesh.

The reference amortizes per-segment cost with a processing pool of
per-segment runners (ChainedExecutionQueryRunner); our non-mesh path instead
paid one device dispatch (and potentially one shape-specialized compile) PER
SEGMENT. Batched-kernel query accelerators solve exactly this by stacking
operator inputs across queries/segments — here:

  1. plan each segment and group shape-compatible ones by plan constants
     (structure signature, staged dtypes, filter/kernel aux, key-dim
     remaps) into SHAPE BUCKETS;
  2. pad rows up a powers-of-two ladder (rungs = 2^i × BATCH_ROW_ALIGN) and
     pin chunk sizes to powers of two, so compile counts stay bounded per
     structure (row ladder × K ladder);
  3. run the shared per-segment body (grouping.traced_segment)
     UNROLLED over the chunk's pooled DeviceBlocks inside ONE jitted
     program — HBM-resident blocks feed the program directly, no
     re-staging, and XLA schedules the K independent reduction subgraphs
     in a single dispatch;
  4. hand back ONE array a leaf of (counts, states), K on its leading
     axis, from that dispatch; the fetch cuts it into ONE SegmentPartial
     per segment, row views of the one host copy.

Stragglers — ineligible segments, and what the K ladder leaves of a bucket
— fall back to the per-segment path, through the plan already made for
them. Each is COUNTED (`stats()` `fallbackSegments`: every segment of a
batch-planned request that ran alone, whether or not a batch dispatched
beside it) and the request's `engine/batch/plan` span says how many
(`stragglers`) and why (`reason`, contracts.BATCH_FALLBACK_REASONS). Parity
is structural, not coincidental: the batched program runs the SAME traced
body (grouping.traced_segment) over the same staged columns and
post-processes states with the same host_post, so results are bit-identical
to per-segment execution.

Observability: every dispatch records (segments, fillRatio) for the
`query/batch/*` emitter metrics (BatchMetricsMonitor, wired by
cluster/dataserver.py) and carries `paddedRows` (K × R) and `realRows` on
its `engine/batch/dispatch` span.
"""
from __future__ import annotations

import collections
import functools
import logging
import os
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from druid_tpu.data import cascade
from druid_tpu.data.segment import DEFAULT_ROW_ALIGN, Segment
from druid_tpu.engine import filters as filters_mod
from druid_tpu.engine import grouping
from druid_tpu.engine.contracts import (BATCH_MAX_SEGMENT_ROWS,
                                        BATCH_MAX_SEGMENTS,
                                        BATCH_MIN_SEGMENTS, BATCH_ROW_ALIGN,
                                        batch_fallback_reason, named_program,
                                        program_name)
from druid_tpu.engine.filters import ConstNode
from druid_tpu.engine.grouping import (GroupPlan, GroupSpec, KeyDim,
                                       SegmentPartial, assemble_stacked_aux,
                                       aux_equal, common_window,
                                       enqueue_grouped_aggregate,
                                       fetch_partials, keydims_equal,
                                       plan_grouped_aggregate,
                                       run_grouped_aggregates,
                                       stacked_origins, traced_segment,
                                       windowed_window)
from druid_tpu.engine.kernels import AggKernel
from druid_tpu.obs.trace import span as trace_span
from druid_tpu.obs.trace import span_when as trace_span_when
from druid_tpu.query.aggregators import AggregatorSpec
from druid_tpu.utils.emitter import Monitor
from druid_tpu.utils.granularity import Granularity
from druid_tpu.utils.intervals import Interval

# the row ladder is denominated in the staging alignment: a rung IS a valid
# row_align for Segment.device_block, so batch-mates stage to exactly R rows
assert BATCH_ROW_ALIGN == DEFAULT_ROW_ALIGN, \
    "contracts.BATCH_ROW_ALIGN must match data.segment.DEFAULT_ROW_ALIGN"

#: process default; per-query override via context {"batchSegments": false}
_ENABLED = os.environ.get("DRUID_TPU_BATCH", "1").lower() \
    not in ("0", "false", "no")
_ENABLED_LOCK = threading.Lock()


def set_enabled(on: bool) -> bool:
    """Flip the process-wide batching default; returns the previous value
    (bench/test toggle)."""
    global _ENABLED
    with _ENABLED_LOCK:
        prev = _ENABLED
        _ENABLED = bool(on)
        return prev


def enabled() -> bool:
    return _ENABLED


def query_enabled(context: Optional[Dict]) -> bool:
    """Whether batching applies to one query: the process switch AND the
    per-query {"batchSegments": false} context opt-out. The ONE predicate
    the single-query path, the cross-query path, and the scheduler's
    routing (DataNode.fusable) must agree on — an opted-out query gains
    nothing from the scheduler hold and must not serialize on the
    dispatcher thread."""
    if not _ENABLED:
        return False
    return not (context
                and str(context.get("batchSegments", "true")).lower()
                in ("0", "false", "no"))


# Jitted batched programs keyed on (structure, K, R), LRU-bounded + locked
# for the same reasons as grouping._JIT_CACHE (broker thread-pool fan-out).
_JIT_CACHE: "collections.OrderedDict[str, object]" = collections.OrderedDict()
_JIT_CACHE_CAP = 64
_JIT_CACHE_LOCK = threading.Lock()


# ---------------------------------------------------------------------------
# Dispatch statistics (query/batch/* metrics)
# ---------------------------------------------------------------------------

class BatchStats:
    """Aggregate counters + a bounded per-dispatch event queue the emitter
    monitor drains. `batches` / `batched_segments` / `stacked_rows` /
    `stacked_slots` count stacked dispatches, their segments, their real
    rows and their padded rows (K × R); `fallback_segments` counts EVERY
    segment of a batch-planned request that ran alone through the
    per-segment path — a request none of whose segments batched counts all
    of them. Segments of a request that was never batch-planned (batching
    off, fewer than BATCH_MIN_SEGMENTS segments) are in neither."""

    EVENT_CAP = 4096

    def __init__(self):
        self._lock = threading.Lock()
        self.batches = 0
        self.batched_segments = 0
        self.stacked_rows = 0
        self.stacked_slots = 0          # K × R summed over dispatches
        self.fallback_segments = 0
        self.dropped_events = 0         # per-dispatch events lost to the cap
        self._events: "collections.deque[Tuple[int, float]]" = \
            collections.deque(maxlen=self.EVENT_CAP)

    def record_batch(self, n_segments: int, rows: int, slots: int) -> None:
        fill = rows / slots if slots else 0.0
        with self._lock:
            self.batches += 1
            self.batched_segments += n_segments
            self.stacked_rows += rows
            self.stacked_slots += slots
            if len(self._events) == self.EVENT_CAP:
                # the deque evicts its oldest silently; count the loss so
                # the monitor can surface truncation instead of silently
                # under-reporting the busiest windows
                self.dropped_events += 1
            self._events.append((n_segments, fill))

    def record_fallback(self, n_segments: int) -> None:
        with self._lock:
            self.fallback_segments += n_segments

    def drain_events(self) -> Tuple[List[Tuple[int, float]], int]:
        """Returns (events, dropped-since-last-drain)."""
        with self._lock:
            out = list(self._events)
            self._events.clear()
            dropped, self.dropped_events = self.dropped_events, 0
            return out, dropped

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            fill = (self.stacked_rows / self.stacked_slots
                    if self.stacked_slots else 0.0)
            return {"batches": self.batches,
                    "batchedSegments": self.batched_segments,
                    "fallbackSegments": self.fallback_segments,
                    "stackedRows": self.stacked_rows,
                    "stackedSlots": self.stacked_slots,
                    "fillRatio": fill}


_STATS = BatchStats()


def stats() -> BatchStats:
    return _STATS


class BatchMetricsMonitor(Monitor):
    """Emits one query/batch/segments + query/batch/fillRatio pair per
    recorded dispatch (drained at tick, the CacheMonitor discipline)."""

    def __init__(self, source: Optional[BatchStats] = None):
        self.source = source or _STATS

    def do_monitor(self, emitter):
        events, dropped = self.source.drain_events()
        for n_segments, fill in events:
            emitter.metric("query/batch/segments", n_segments)
            emitter.metric("query/batch/fillRatio", fill)
        if dropped:
            emitter.metric("query/batch/droppedEvents", dropped)


# ---------------------------------------------------------------------------
# Planning / eligibility
# ---------------------------------------------------------------------------

def row_rung(n_rows: int) -> int:
    """Padded-row ladder rung for a segment: the smallest 2^i ×
    BATCH_ROW_ALIGN holding n_rows. Bounds distinct row shapes (and hence
    compiles) per plan structure to the ladder height."""
    blocks = -(-max(n_rows, 1) // BATCH_ROW_ALIGN)
    return BATCH_ROW_ALIGN * (1 << (blocks - 1).bit_length())


@dataclass
class _Plan:
    """One segment's per-query plan, the unit of shape-bucket grouping: the
    shared host-side GroupPlan (grouping.plan_grouped_aggregate) with what
    only batching derives (ladder rung, descriptors, bucket digest). The
    GroupPlan rides along so straggler fallback re-executes WITHOUT
    re-planning (run_grouped_aggregate(plan=...)).

    Carries its OWN intervals/granularity: a chunk may mix plans from
    several concurrent queries (run_multi_with_batching), so per-query
    origins (relative interval bounds, bucket start) are derived per plan,
    not from the chunk reference. `req` tags the owning request — the
    queryId of the split-back."""
    segment: Segment
    index: int                       # position in the caller's segment list
    gplan: GroupPlan
    intervals: Tuple[Interval, ...] = ()
    granularity: Granularity = None
    req: int = 0                     # owning request (multi-query split-back)
    #: False = straggler (runs per-segment, but still through this gplan)
    eligible: bool = False
    #: why a plan no chunk takes runs alone (contracts.BATCH_FALLBACK_REASONS)
    reason: str = ""
    rung: int = 0
    packs: Tuple = ()                # pack descriptor (data/packed.py)
    cascades: Tuple = ()             # cascade descriptor (data/cascade.py)
    digest: Tuple = None             # hashable shape-bucket prefilter


def _alone(plan: _Plan, reason: str) -> _Plan:
    """`plan` as a straggler for `reason` (one of the closed set)."""
    plan.eligible = False
    plan.reason = batch_fallback_reason(reason)
    return plan


def _plan_for(segment: Segment, kds: Sequence[KeyDim], index: int,
              intervals: Sequence[Interval], granularity: Granularity,
              aggs: Sequence[AggregatorSpec], flt,
              virtual_columns: Sequence) -> _Plan:
    """Plan one segment for batched execution. ONE host-side planning pass
    (grouping.plan_grouped_aggregate) serves both outcomes: eligible plans
    group into shape buckets and drive the stacked program; ineligible
    plans (stragglers) keep `eligible=False` and re-execute per-segment
    through run_grouped_aggregate(plan=...) WITHOUT re-planning. The
    eligibility checks mirror distributed.try_sharded minus the
    cross-segment dictionary requirement: batched partials stay PER
    SEGMENT, so raw dictionary ids decode through each segment's own value
    list."""
    kds = tuple(kds)
    gplan = plan_grouped_aggregate(segment, intervals, granularity, kds,
                                   aggs, flt, virtual_columns)
    plan = _Plan(segment=segment, index=index, gplan=gplan,
                 intervals=tuple(intervals), granularity=granularity)
    if segment.n_rows > BATCH_MAX_SEGMENT_ROWS:
        return _alone(plan, "rows_over_limit")
    if cascade.enabled() and cascade.run_domain_probe(
            segment, intervals, granularity, gplan.spec, gplan.kernels,
            flt, virtual_columns):
        # code-domain eligible: the per-segment straggler path runs it
        # fully over run metadata (run_grouped_aggregate's cascade hook) —
        # stacking it into a row program would decode what never needs
        # decoding
        return _alone(plan, "code_domain")
    if any(d.host_ids is not None and d.ids_key is None for d in kds):
        # a derived id column with no stable cache identity cannot stage
        # through the pool — keep per-segment. Numeric/expression dims DO
        # carry ids_key, and their query-time dictionaries unify across
        # the query's segments (engines.unify_query_dims), so their plan
        # constants (cardinality, remap) are no longer segment-local —
        # the host-mask-era exclusion is gone.
        return _alone(plan, "no_stable_id_column")
    spec, filter_node, kernels = gplan.spec, gplan.filter_node, gplan.kernels
    if spec.key_mode != "dense" or spec.bucket_mode not in ("all", "uniform"):
        return _alone(plan, "key_or_bucket_mode")
    if spec.num_total > grouping.BLOCKED_GROUP_LIMIT:
        # bounded group spaces make select_strategy a pure function of
        # (num_total, kernels, dtypes) — identical for the batched rung and
        # the per-segment padding — so the bit-parity contract is
        # STRUCTURAL. Above the limit the choice consults per-segment row
        # clustering (windowed/projection), which could diverge between
        # chunk-mates and reorder float accumulation; those segments are
        # also scatter-compute-bound, where dispatch amortization is noise
        return _alone(plan, "group_space_over_limit")
    if isinstance(filter_node, ConstNode) and not filter_node.value:
        # constant-false: the per-segment path skips the device entirely —
        # batching it would only waste a stacked slot
        return _alone(plan, "constant_false")
    columns = gplan.columns
    # complex (2-D) metric columns — HLL registers, sketch states — stack
    # like any other column now that the mask is in-program; their width is
    # a compile-shape dimension, so it joins the digest below
    col_shapes = tuple(sorted(
        (c, np.asarray(segment.metrics[c].values).shape[1:])
        for c in columns if c in segment.metrics
        and np.asarray(segment.metrics[c].values).ndim > 1))
    plan.eligible = True
    plan.rung = row_rung(segment.n_rows)
    # cascade + pack descriptors (pure fns of column stats, pow2-quantized
    # widths/bases/run counts precisely so near-identical segments keep
    # sharing buckets): both change the stacked program's treedef, so
    # chunk-mates must agree on them — they join the signature AND the
    # digest (cascade.plan_pair is the same derivation device_block uses)
    plan.cascades, plan.packs = cascade.plan_pair(segment, columns)
    sig = grouping._structure_sig(spec, len(intervals), filter_node, kernels,
                                  gplan.vc_plans, plan.packs, plan.cascades)
    # granularity + bucket count join the digest for CROSS-QUERY grouping:
    # the stacked aux (assemble_stacked_aux) carries one shared period /
    # num_buckets for the whole chunk, so chunk-mates from different
    # queries must agree on them (within one query they are constant and
    # this changes nothing). Interval VALUES stay out — relative bounds
    # are per-segment mapped args (iv_rel), only their COUNT is shape
    # (already in the structure sig).
    plan.digest = (sig, plan.rung, columns, col_shapes,
                   tuple(sorted((c, str(d))
                                for c, d in gplan.col_dtypes.items())),
                   str(granularity), spec.num_buckets)
    return plan


def _compatible(ref: _Plan, cand: _Plan) -> bool:
    """Digest-equal plans still carry array-valued constants (filter LUTs,
    kernel aux, dim remaps, vc string LUTs) that become SHARED aux in the
    stacked program — they must be value-equal."""
    a, b = ref.gplan, cand.gplan
    return (keydims_equal(a.spec.dims, b.spec.dims)
            and aux_equal(a.f_aux, b.f_aux)
            and aux_equal(a.k_aux, b.k_aux)
            and aux_equal(a.vc_luts, b.vc_luts))


def _shape_buckets(plans: Sequence[_Plan]) -> List[List[_Plan]]:
    """Group plans into shape buckets: digest prefilter, then aux-equality
    subgroups within each digest."""
    by_digest: Dict[Tuple, List[List[_Plan]]] = {}
    for p in plans:
        groups = by_digest.setdefault(p.digest, [])
        for g in groups:
            if _compatible(g[0], p):
                g.append(p)
                break
        else:
            groups.append([p])
    return [g for groups in by_digest.values() for g in groups]


def _pow2_chunks(group: List[_Plan]) -> Tuple[List[List[_Plan]], List[_Plan]]:
    """Split a bucket into power-of-two-sized chunks ≤ BATCH_MAX_SEGMENTS
    (greedy binary decomposition: 13 → 8 + 4 + a 1-straggler). The program
    unrolls one body per segment, so the segment count is a compile-key
    dimension — pinning it to powers of two bounds compiles at
    log2(BATCH_MAX_SEGMENTS) per (structure, rung) instead of one per
    distinct K. Returns (chunks, remainder-for-per-segment-fallback)."""
    out: List[List[_Plan]] = []
    i, n = 0, len(group)
    while n - i >= BATCH_MIN_SEGMENTS:
        size = min(BATCH_MAX_SEGMENTS, 1 << ((n - i).bit_length() - 1))
        out.append(group[i:i + size])
        i += size
    return out, group[i:]


def _plan_chunks(plans: Sequence[_Plan], plan_span) -> List[List[_Plan]]:
    """The stacked dispatches of one planning pass, each a chunk of K
    bucket-mates (a power of two): eligible plans grouped into shape
    buckets, each bucket cut along the K ladder, each chunk's strategy
    selected and written into its plans' specs. A plan no chunk takes
    becomes a straggler with its reason; `plan_span` (the open
    `engine/batch/plan` span, None untraced) is stamped with what was
    decided."""
    eligible = [p for p in plans if p.eligible]
    buckets = _shape_buckets(eligible)
    chunks: List[List[_Plan]] = []
    for bucket in buckets:
        cut, remainder = _pow2_chunks(bucket)
        for mates in cut:
            ref = mates[0].gplan
            strategy, window = grouping.select_strategy(
                ref.spec, ref.kernels, ref.col_dtypes, mates[0].rung,
                lambda: common_window(
                    windowed_window(p.segment, p.intervals, p.granularity,
                                    p.gplan.spec) for p in mates))
            if strategy == "projection":
                # sorted projections are per-segment layouts a stacked
                # program cannot share — and projection-grade segments are
                # big enough that per-segment dispatch overhead is already
                # amortized
                for p in mates:
                    _alone(p, "projection_layout")
                continue
            for p in mates:
                p.gplan.spec.strategy, p.gplan.spec.window = strategy, window
            chunks.append(mates)
        for p in remainder:
            _alone(p, "ladder_remainder")
    if plan_span is not None:
        reasons = collections.Counter(p.reason for p in plans
                                      if not p.eligible)
        plan_span.attrs.update(
            eligible=len(eligible), buckets=len(buckets), chunks=len(chunks),
            stragglers=sum(reasons.values()))
        if reasons:
            plan_span.attrs["reason"] = reasons.most_common(1)[0][0]
    return chunks


# ---------------------------------------------------------------------------
# The batched device program
# ---------------------------------------------------------------------------

def _build_batched_fn(spec: GroupSpec, filter_node,
                      kernels: List[AggKernel], vc_plans: Tuple, K: int):
    """One jitted program for a whole shape bucket: the shared per-segment
    body UNROLLED over the K pooled blocks. Per-segment origins (time0,
    relative interval bounds, bucket origin) index into [K] arrays; plan
    constants ride aux. Unrolling (not vmap) is deliberate: XLA schedules K
    independent reduction subgraphs better than one batched-axis program —
    measured ~3.6x faster than the vmapped equivalent and ~1.5x faster than
    K separate dispatches on the CPU backend. The K bodies' outputs leave
    as ONE `[K, ...]` array a leaf of (counts, states), in their own
    dtypes: every body is the same traced function over the same spec, so
    they share one tree and shapes, and a chunk hands the host one array
    a leaf to copy and free, not K (PR 37) — the fetch takes slot i's
    partial as row i of the host copy."""
    import jax
    import jax.numpy as jnp

    body = functools.partial(traced_segment, spec, filter_node, kernels,
                             vc_plans)

    def fn(blocks, time0s, iv_rel, bucket_off, aux):
        bodies = [body(blocks[i], time0s[i], iv_rel[i], bucket_off[i], aux)
                  for i in range(K)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *bodies)

    return jax.jit(named_program(fn, program_name("batch_agg",
                                                  spec.strategy)))


def _run_batch(chunk: List[_Plan]) -> List[SegmentPartial]:
    """One planned chunk, enqueued and fetched on its own: what the
    cross-query entry runs, whose per-chunk failure fall-back needs each
    chunk's result before the next."""
    targets, outs = zip(*_enqueue_batch(chunk))
    return fetch_partials(targets, outs, segments=len(chunk))


def _enqueue_batch(chunk: List[_Plan]) -> List[Tuple]:
    """ENQUEUE one planned chunk (`_plan_chunks`: its strategy is in its
    plans' specs) as a single dispatch; returns an entry a segment for
    `fetch_partials` / `run_grouped_aggregates`: ((segment, spec,
    kernels), StackedSlot(out, i)), where `out` is the chunk's ONE
    (counts, states) of `[K, ...]` leaves, still on its way, and `i` the
    segment's slot in it. The chunk may mix plans from several queries
    (run_multi_with_batching): every
    per-query origin — interval bounds, bucket start — is derived from the
    plan's OWN intervals, so cross-query mates produce exactly the partials
    their own serial run would.

    The host work around the one dispatch is under names (PR 36):
    `engine/batch/blocks` (the K pool probes for the blocks and the
    unified id columns), `engine/filter/words`, `engine/batch/assemble`
    (origins, aux, the structure signature — `sigBytes` — and the program
    cache's probe), `engine/batch/dispatch`: four spans a chunk, none a
    segment."""
    ref = chunk[0].gplan
    strategy = ref.spec.strategy
    R = chunk[0].rung
    K = len(chunk)                  # a power of two by _pow2_chunks

    # per-segment derived inputs ride the mapped arrays, not aux: query-time
    # dictionary id columns (unified id spaces — engines.unify_query_dims)
    # and resident filter-bitmap words (engine/filters.py device-bitmap
    # path; each plan stages ITS OWN words — query filter AND filtered
    # aggregators — so chunk-mates from different queries may carry
    # entirely different bitmap filters under one shared program structure)
    with trace_span("engine/batch/blocks", segments=K, rows=R):
        blocks = [p.segment.device_block(list(ref.columns), row_align=R)
                  for p in chunk]
        assert all(b.padded_rows == R for b in blocks), \
            "ladder rung must equal the staged row count"
        arrs_per_slot = []
        for p, b in zip(chunk, blocks):
            arrs = dict(b.arrays)
            for d in p.gplan.spec.dims:
                if d.host_ids is not None:
                    arrs[d.column] = grouping._pad_device_cached(
                        p.segment, d.ids_key, d.host_ids, R, 0)
            arrs_per_slot.append(arrs)
    with filters_mod.words_span(segments=K):
        bmp_per_slot = filters_mod.stage_device_bitmaps_multi(
            [(p.segment, p.gplan.filter_node, p.gplan.kernels)
             for p in chunk], R)
    for arrs, bmp in zip(arrs_per_slot, bmp_per_slot):
        arrs.update(bmp)

    with trace_span("engine/batch/assemble") as asm_span:
        time0s, iv_rel, bucket_off = stacked_origins(
            [p.segment for p in chunk], [p.intervals for p in chunk],
            [p.gplan.spec for p in chunk])
        aux = assemble_stacked_aux(ref.spec, ref.f_aux, ref.k_aux,
                                   ref.vc_luts)
        sig = "batched|" + grouping._structure_sig(
            ref.spec, len(chunk[0].intervals), ref.filter_node, ref.kernels,
            ref.vc_plans, chunk[0].packs, chunk[0].cascades) \
            + f"|K={K}|R={R}"
        if asm_span is not None:
            asm_span.attrs["sigBytes"] = len(sig)
        with _JIT_CACHE_LOCK:
            fn = _JIT_CACHE.get(sig)
            # the miss IS the compile event (jit traces/compiles on the
            # first call below) — timing stays at the dispatch boundary
            compiled = fn is None
            if fn is None:
                fn = _build_batched_fn(ref.spec, ref.filter_node,
                                       ref.kernels, ref.vc_plans, K)
                _JIT_CACHE[sig] = fn
                while len(_JIT_CACHE) > _JIT_CACHE_CAP:
                    _JIT_CACHE.popitem(last=False)
            else:
                _JIT_CACHE.move_to_end(sig)

    from druid_tpu.obs import dispatch as dispatch_mod
    real_rows = sum(p.segment.n_rows for p in chunk)
    with trace_span("engine/batch/dispatch", strategy=strategy, segments=K,
                    rows=R, paddedRows=K * R, realRows=real_rows,
                    compile=compiled,
                    program=program_name("batch_agg", strategy)), \
            trace_span_when(compiled, "engine/compile", kind="batched",
                            strategy=strategy):
        outs = fn(tuple(arrs_per_slot), time0s, iv_rel,
                  bucket_off, aux)
    # successful dispatches only (grouping's discipline): a failed batch
    # falls back per-segment and must not double-bill the scoreboard
    dispatch_mod.record("batched")

    _STATS.record_batch(K, real_rows, K * R)
    return [((p.segment, p.gplan.spec, p.gplan.kernels),
             grouping.StackedSlot(outs, i))
            for i, p in enumerate(chunk)]


# ---------------------------------------------------------------------------
# Entry point (engines._make_partials)
# ---------------------------------------------------------------------------

def run_with_batching(segs: Sequence[Segment], intervals: Sequence[Interval],
                      granularity: Granularity,
                      kds_per_seg: Sequence[Sequence[KeyDim]],
                      aggs: Sequence[AggregatorSpec], flt,
                      virtual_columns: Sequence = (),
                      context: Optional[Dict] = None,
                      check=None) -> Optional[List[SegmentPartial]]:
    """Produce one SegmentPartial per segment (same order as `segs`), using
    batched dispatches for every shape bucket of ≥ BATCH_MIN_SEGMENTS
    compatible segments and the per-segment path for stragglers. Returns
    None when batching is off / inapplicable (caller runs plain
    per-segment). Every chunk is ENQUEUED, then every straggler, and the
    request's results are fetched once (grouping.run_grouped_aggregates:
    ONE `engine/fetch`, `programs` = chunks + stragglers that ran a
    program). `check` (optional cancel/timeout probe) fires between
    enqueues — batch and straggler alike — and before the fetch."""
    if not query_enabled(context) or len(segs) < BATCH_MIN_SEGMENTS:
        return None

    with trace_span("engine/batch/plan", segments=len(segs)) as plan_span:
        plans = [_plan_for(s, kds, i, intervals, granularity, aggs, flt,
                           virtual_columns)
                 for i, (s, kds) in enumerate(zip(segs, kds_per_seg))]
        chunks = _plan_chunks(plans, plan_span)

    # the rest runs alone — the per-segment planning already happened, so
    # the plans are executed HERE, not rebuilt by the caller
    stragglers = [p for p in plans if not p.eligible]
    _STATS.record_fallback(len(stragglers))
    work = [functools.partial(_enqueue_batch, chunk) for chunk in chunks]
    work += [functools.partial(_enqueue_straggler, p, aggs, flt,
                               virtual_columns) for p in stragglers]
    order = [p.index for chunk in chunks for p in chunk]
    order += [p.index for p in stragglers]
    results: List[Optional[SegmentPartial]] = [None] * len(segs)
    for i, partial in zip(order, run_grouped_aggregates(work, check)):
        results[i] = partial
    return results


def _enqueue_straggler(p: _Plan, aggs, flt, virtual_columns):
    """Per-segment enqueue reusing the plan built for bucket grouping (the
    ROADMAP's 'stragglers are planned twice' follow-on, closed)."""
    return enqueue_grouped_aggregate(
        p.segment, p.intervals, p.granularity, p.gplan.spec.dims, aggs, flt,
        virtual_columns=virtual_columns, plan=p.gplan)


# ---------------------------------------------------------------------------
# Cross-query entry point (server/scheduler.py via engines)
# ---------------------------------------------------------------------------

@dataclass
class BatchWork:
    """One query's segment work, as submitted to run_multi_with_batching —
    the same argument tuple run_with_batching takes, boxed so a scheduler
    flush can carry many of them."""
    segs: Sequence[Segment]
    intervals: Sequence[Interval]
    granularity: Granularity
    kds_per_seg: Sequence[Sequence[KeyDim]]
    aggs: Sequence[AggregatorSpec]
    flt: object = None
    virtual_columns: Sequence = ()
    context: Optional[Dict] = None
    check: Optional[object] = None   # cancel/timeout probe for THIS query


def run_multi_with_batching(work: Sequence[BatchWork],
                            on_batch=None) -> List[object]:
    """Cross-query fused execution: plan every request's segments, group
    plans into shape buckets ACROSS requests (the _Plan digest already
    carries everything two dispatches must agree on, plus granularity /
    bucket count for the cross-query case), run each bucket as single
    dispatches, and split partials back per request by the plan's `req`
    tag.

    Returns one entry per request: a List[SegmentPartial] (same order as
    that request's `segs`) or the Exception that request's check raised —
    one cancelled/timed-out query must not fail its batch-mates. Results
    are bit-identical to running each request through run_with_batching /
    the per-segment path serially: the chunk a plan lands in changes only
    WHICH dispatch computes it, never what it computes (per-plan origins,
    strategy a pure function of digest-shared constants).

    `on_batch(n_queries, n_segments, fill_ratio)` fires per fused dispatch
    — the scheduler's query/crossBatch/* metrics hook."""
    all_plans: List[List[_Plan]] = []
    with trace_span("engine/batch/plan",
                    queries=len(work),
                    segments=sum(len(w.segs) for w in work)) as plan_span:
        for r, w in enumerate(work):
            opted_out = not query_enabled(w.context)
            plans = []
            for i, (s, kds) in enumerate(zip(w.segs, w.kds_per_seg)):
                p = _plan_for(s, kds, i, w.intervals, w.granularity,
                              w.aggs, w.flt, w.virtual_columns)
                p.req = r
                if opted_out:
                    _alone(p, "opted_out")
                plans.append(p)
            all_plans.append(plans)
        chunks = _plan_chunks([p for plans in all_plans for p in plans],
                              plan_span)

    results: List[List[Optional[SegmentPartial]]] = \
        [[None] * len(plans) for plans in all_plans]
    dead: Dict[int, BaseException] = {}

    def _poll_checks():
        for r, w in enumerate(work):
            if r in dead or w.check is None:
                continue
            try:
                w.check()
            except Exception as e:
                dead[r] = e

    dispatched = 0
    for live in chunks:
        if dispatched:
            _poll_checks()
        if any(p.req in dead for p in live):
            # a cancelled mate shrank the chunk below its pow2 size — K is
            # a compile key, so dispatching the odd size would pay a
            # one-off compile; survivors take the (cached) per-segment
            # straggler path instead
            continue
        try:
            partials = _run_batch(live)
        except Exception:
            # a batch-specific failure must not kill queries that would
            # succeed serially: participants fall back to the per-segment
            # straggler path below
            logging.getLogger(__name__).exception(
                "batched dispatch failed; falling back per-segment")
            continue
        dispatched += 1
        if on_batch is not None:
            slots = len(live) * live[0].rung
            rows = sum(p.segment.n_rows for p in live)
            on_batch(len({p.req for p in live}), len(live),
                     rows / slots if slots else 0.0)
        for p, partial in zip(live, partials):
            results[p.req][p.index] = partial

    _poll_checks()
    out: List[object] = []
    for r, (w, plans) in enumerate(zip(work, all_plans)):
        if r in dead:
            out.append(dead[r])
            continue
        res = results[r]
        alone = [p for i, p in enumerate(plans) if res[i] is None]
        _STATS.record_fallback(len(alone))
        try:
            partials = run_grouped_aggregates(
                [functools.partial(_enqueue_straggler, p, w.aggs, w.flt,
                                   w.virtual_columns) for p in alone],
                w.check)
        except Exception as e:
            out.append(e)
            continue
        for p, partial in zip(alone, partials):
            res[p.index] = partial
        out.append(res)
    return out
