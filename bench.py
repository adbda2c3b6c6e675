"""Headline benchmark: GroupBy + TopN rows/sec on one TPU chip, plus the
batched-vs-per-segment dispatch-amortization comparison.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"per_segment_rate", "batched_rate", "batch_speedup",
"sharded_decoded_rate", "sharded_packed_rate", "sharded_merge_host_ms",
"sharded_merge_device_ms", "packed_rate",
"filter_host_rate", "filter_device_rate", "filter_cache_hit_rate",
"decoded_rate", "pack_ratio", "fused_rate", "staged_rate",
"dispatch_count_fused", "dispatch_count_staged", "donated_tick_rate",
"rle_rate", "packed_only_rate", "cascade_ratio", "code_domain_rate",
"v1_load_rate", "v2_load_rate", "disk_ratio", "wire_bytes_v1",
"wire_bytes_v2", "hll_log2m12_rate",
"untraced_rate", "traced_rate", "trace_overhead"} — sharded_* compare
compressed-resident vs decoded cold-stack mesh execution plus the warm
device-merged vs host-merged tail; packed_* compare
compressed-domain vs decoded staging on the cold-miss H2D path; fused_*
compare the one-dispatch megakernel path vs the staged fill-wave path on
cold queries (dispatch_count_fused must be exactly 1); traced_* track
qtrace span overhead from run to run.

Config mirrors BASELINE.json: TPC-H-style GroupBy (2 dims, 3 aggs, numeric
bound filter) + TopN (1 dim, metric-ordered) over synthetic segments.
Baseline comparator: the reference whitepaper's per-core scan-aggregate rate
(36,246,530 rows/sec/core for sum-over-interval, druid.tex:882) — the Java
engine's upper bound; its GroupBy path is strictly slower.

The benchmark measures the chip: finding no TPU is an error (non-zero exit,
no number), never a quiet run on another platform, and the JSON line carries
the device it ran on (platform, device_kind, device_count).
DRUID_TPU_BENCH_PLATFORM names another platform EXPLICITLY — the tier-1
smoke pins cpu to check this file's output contract at toy sizes; such a
line says "platform": "cpu" and is no device number. A section that raises
reports its *_error field and the process exits non-zero, as it does when
the Pallas path latched off (a headline after the latch is an XLA number
under another name).

Environment:
  DRUID_TPU_BENCH_PLATFORM  run on this jax platform instead of the TPU
  DRUID_TPU_BENCH_ROWS      total headline rows (default 100_000_000)
  DRUID_TPU_BENCH_SEGMENTS  headline segment count (default 8)
  DRUID_TPU_BENCH_ITERS     timed iterations per query (default 5)
  DRUID_TPU_BENCH_BATCH_SEGMENTS  segments in the batch comparison (default 16)
  DRUID_TPU_BENCH_BATCH_ROWS      rows PER SEGMENT there (default 4096)
  DRUID_TPU_BENCH_CASCADE_SEGMENTS  cascade-comparison segments (default 8)
  DRUID_TPU_BENCH_CASCADE_ROWS      rows PER SEGMENT there (default 8192)
  DRUID_TPU_BENCH_SEGIO_ROWS        segment-io comparison rows (default 65536)
  DRUID_TPU_BENCH_CLIENTS         concurrent closed-loop clients (default 8)
  DRUID_TPU_BENCH_CLIENT_QUERIES  queries per client per mode (default 12)
  DRUID_TPU_BENCH_SCHED_ROWS      rows per segment in that mode (default 4096)
  DRUID_TPU_BENCH_SOAK            opt-in soak mode: N query waves + server
                                  start/stop cycles, reporting rss/fd/thread
                                  drift in the JSON line (default off)
"""
import json
import os
import sys
import time

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# -- headline configuration, shared with tools/chip_suite.py and
#    tools/chip_pallas_test.py so tuning/validation and the gate measure
#    the SAME shape -------------------------------------------------------

HEADLINE_SEED = 1234


def headline_interval():
    from druid_tpu.utils.intervals import Interval
    return Interval.of("2026-01-01", "2026-01-02")


def headline_segments(rows: int, n_segments: int, seed: int = HEADLINE_SEED):
    from druid_tpu.data.generator import ColumnSpec, DataGenerator
    schema = (
        ColumnSpec("dimA", "string", cardinality=100, distribution="uniform"),
        ColumnSpec("dimB", "string", cardinality=1000, distribution="zipf"),
        ColumnSpec("metLong", "long", low=0, high=10_000),
        ColumnSpec("metFloat", "float", distribution="normal", mean=100.0,
                   std=25.0),
    )
    gen = DataGenerator(schema, seed=seed)
    return gen.segments(n_segments, rows // n_segments, headline_interval(),
                        datasource="bench")


def headline_groupby():
    from druid_tpu.query.aggregators import (CountAggregator,
                                             FloatMaxAggregator,
                                             LongSumAggregator)
    from druid_tpu.query.filters import BoundFilter
    from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery
    return GroupByQuery.of(
        "bench", [headline_interval()],
        [DefaultDimensionSpec("dimA"), DefaultDimensionSpec("dimB")],
        [CountAggregator("rows"), LongSumAggregator("lsum", "metLong"),
         FloatMaxAggregator("fmax", "metFloat")],
        granularity="all",
        filter=BoundFilter("metLong", lower=100, upper=9_900,
                           ordering="numeric"))


def headline_topn(segments):
    from druid_tpu.query.aggregators import (CountAggregator,
                                             LongSumAggregator)
    from druid_tpu.query.filters import InFilter
    from druid_tpu.query.model import TopNQuery
    # filter on REAL dictionary values (half of dimA) — a padded-format
    # mismatch here would silently benchmark an empty-result query
    dimA_vals = list(segments[0].dims["dimA"].dictionary.values)
    assert len(dimA_vals) >= 100, "unexpected dimA cardinality"
    return TopNQuery.of(
        "bench", [headline_interval()], "dimB", "lsum", 100,
        [CountAggregator("rows"), LongSumAggregator("lsum", "metLong")],
        granularity="all",
        filter=InFilter("dimA", dimA_vals[0:100:2]))


def _init_backend():
    """The device the benchmark runs on, as a dict for the output line —
    or exit non-zero: no TPU (or, when DRUID_TPU_BENCH_PLATFORM names one,
    no such platform) is an error, and no number is printed."""
    plat = os.environ.get("DRUID_TPU_BENCH_PLATFORM")
    if plat:
        os.environ["JAX_PLATFORMS"] = plat
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"bench: backend unavailable: {e}")
        sys.exit(1)
    device = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "device_count": len(devices)}
    if device["platform"] != (plat or "tpu"):
        log(f"bench: needs a {plat or 'tpu'} device, JAX found {device}")
        sys.exit(1)
    log(f"devices: {devices}")
    return device


def batch_groupby():
    """The batch-comparison query: 1 dim / 3 aggs / numeric filter. A SMALL
    group space (cardinality 100) on purpose — per-segment device compute
    is tiny there, so the measurement isolates what batching amortizes
    (dispatch round-trips + per-call overheads), not scatter throughput."""
    from druid_tpu.query.aggregators import (CountAggregator,
                                             FloatMaxAggregator,
                                             LongSumAggregator)
    from druid_tpu.query.filters import BoundFilter
    from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery
    return GroupByQuery.of(
        "bench", [headline_interval()], [DefaultDimensionSpec("dimA")],
        [CountAggregator("rows"), LongSumAggregator("lsum", "metLong"),
         FloatMaxAggregator("fmax", "metFloat")],
        granularity="all",
        filter=BoundFilter("metLong", lower=100, upper=9_900,
                           ordering="numeric"))


def _bench_batching(iters: int):
    """Per-path comparison at many small same-schema segments: the
    dispatch-amortization story in one number. Runs batch_groupby()
    meshless, once with batching forced off (one device dispatch per
    segment) and once on (one dispatch per shape bucket)."""
    from druid_tpu.engine import batching
    from druid_tpu.engine.executor import QueryExecutor

    n_segments = int(os.environ.get("DRUID_TPU_BENCH_BATCH_SEGMENTS", 16))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_BATCH_ROWS", 4096))
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    total_rows = sum(s.n_rows for s in segments)
    query = batch_groupby()
    executor = QueryExecutor(segments)    # meshless: the batched path's home

    rates = {}
    prev = batching.enabled()
    before = batching.stats().snapshot()
    try:
        for label, on in (("per_segment", False), ("batched", True)):
            batching.set_enabled(on)
            t = time.time()
            executor.run(query)
            log(f"batch-bench warmup {label}: {time.time() - t:.2f}s")
            times = []
            for _ in range(max(iters, 3)):
                t = time.time()
                executor.run(query)
                times.append(time.time() - t)
            best = min(times)
            rates[label] = total_rows / best
            log(f"batch-bench {label}: best {best * 1e3:.1f}ms over "
                f"{len(times)} iters -> {rates[label] / 1e6:.1f}M rows/s")
    finally:
        batching.set_enabled(prev)
    # fill ratio over THIS comparison's dispatches only — the headline
    # queries may themselves have batched into the process-wide stats
    after = batching.stats().snapshot()
    d_rows = after["stackedRows"] - before["stackedRows"]
    d_slots = after["stackedSlots"] - before["stackedSlots"]
    fill = d_rows / d_slots if d_slots else 0.0
    log(f"batch-bench stats: +{after['batches'] - before['batches']} "
        f"dispatches, fill {fill:.3f}")
    return {
        "per_segment_rate": round(rates["per_segment"], 0),
        "batched_rate": round(rates["batched"], 0),
        "batch_speedup": round(rates["batched"] / rates["per_segment"], 2),
        "batch_segments": n_segments,
        "batch_fill_ratio": round(fill, 3),
    }


def _bench_sharded(iters: int):
    """Pod-scale mesh comparison over the batch-shape segments: the
    compressed-resident sharded path (one shard_map dispatch, partials
    merged in-program with collectives) on whatever mesh the backend
    offers. The rate pair is COLD-STACK: the stacked block is released
    before every timed iteration so each run pays the full stack-build +
    H2D tax — once compressed-resident (packed words + cascade
    descriptors ride the mesh and decode in-program) and once decoded.
    The merge pair is WARM and times the two tail disciplines over
    identical segments: the meshless path (per-segment/batched dispatch,
    partials merged on the host — the broker tail the sharded path
    replaced) vs the single sharded dispatch."""
    import jax

    from druid_tpu.data import cascade as cascade_mod
    from druid_tpu.data import packed as packed_mod
    from druid_tpu.data.devicepool import device_pool
    from druid_tpu.engine.executor import QueryExecutor
    from druid_tpu.parallel import distributed, make_mesh, use_mesh

    n_dev = len(jax.devices())
    n_segments = int(os.environ.get("DRUID_TPU_BENCH_BATCH_SEGMENTS", 16))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_BATCH_ROWS", 4096))
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    total_rows = sum(s.n_rows for s in segments)
    query = batch_groupby()
    executor = QueryExecutor(segments)
    mesh = make_mesh()
    before = distributed.sharded_stats().snapshot()

    def timed_sharded(label, cold_stack):
        with use_mesh(mesh):
            t = time.time()
            executor.run(query)
            log(f"sharded-bench warmup {label}: {time.time() - t:.2f}s")
            times = []
            for _ in range(max(iters, 3)):
                if cold_stack:
                    distributed.clear_stack_cache()
                t = time.time()
                executor.run(query)
                times.append(time.time() - t)
        return min(times)

    rates = {}
    for label, on in (("packed", True), ("decoded", False)):
        prev_p = packed_mod.set_enabled(on)
        prev_c = cascade_mod.set_enabled(on)
        try:
            distributed.clear_stack_cache()
            best = timed_sharded(label, cold_stack=True)
        finally:
            packed_mod.set_enabled(prev_p)
            cascade_mod.set_enabled(prev_c)
        rates[label] = total_rows / best
        log(f"sharded-bench {label}: best {best * 1e3:.1f}ms cold-stack "
            f"over {n_dev} device(s) -> {rates[label] / 1e6:.1f}M rows/s")

    # merge tails, warm: device = one sharded dispatch (collective merge
    # in-program, the host only converts representations); host = the
    # meshless path over the same segments (partials host-merged)
    t_dev = timed_sharded("merge-device", cold_stack=False)
    t = time.time()
    executor.run(query)
    log(f"sharded-bench warmup merge-host: {time.time() - t:.2f}s")
    host_times = []
    for _ in range(max(iters, 3)):
        t = time.time()
        executor.run(query)
        host_times.append(time.time() - t)
    t_host = min(host_times)
    log(f"sharded-bench merge tails: device {t_dev * 1e3:.1f}ms vs "
        f"host {t_host * 1e3:.1f}ms warm")

    after = distributed.sharded_stats().snapshot()
    if after[0] <= before[0]:
        raise RuntimeError("sharded path never dispatched — fell back to "
                           "the host-merged path")
    snap = device_pool().snapshot()
    return {
        "sharded_decoded_rate": round(rates["decoded"], 0),
        "sharded_packed_rate": round(rates["packed"], 0),
        "sharded_merge_host_ms": round(t_host * 1e3, 2),
        "sharded_merge_device_ms": round(t_dev * 1e3, 2),
        "sharded_devices": n_dev,
        "sharded_stack_ratio": round(snap.stacked_ratio, 3),
    }


def _bench_packed(iters: int):
    """Compressed-domain cold-miss comparison: the batch query over the
    small-segment shape with the device pool CLEARED before every timed
    run, so each run pays the full H2D staging tax — once with bit-packed
    staging (data/packed.py) and once decoded. The packed win is the
    smaller bus transfer + the pool holding pack-ratio more segments;
    pack_ratio reports the measured decoded/actual byte ratio of the
    packed run's pool residency."""
    from druid_tpu.data import packed
    from druid_tpu.data.devicepool import device_pool
    from druid_tpu.engine.executor import QueryExecutor

    n_segments = int(os.environ.get("DRUID_TPU_BENCH_BATCH_SEGMENTS", 16))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_BATCH_ROWS", 4096))
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    total_rows = sum(s.n_rows for s in segments)
    query = batch_groupby()
    executor = QueryExecutor(segments)
    pool = device_pool()

    rates = {}
    pack_ratio = 0.0
    for label, on in (("decoded", False), ("packed", True)):
        prev = packed.set_enabled(on)
        try:
            t = time.time()
            executor.run(query)          # warm: compile once per mode
            log(f"packed-bench warmup {label}: {time.time() - t:.2f}s")
            times = []
            for _ in range(max(iters, 3)):
                pool.clear()             # force the cold-miss H2D path
                t = time.time()
                executor.run(query)
                times.append(time.time() - t)
            if on:
                pack_ratio = pool.snapshot().packed_ratio
        finally:
            packed.set_enabled(prev)
        best = min(times)
        rates[label] = total_rows / best
        log(f"packed-bench {label}: best {best * 1e3:.1f}ms over "
            f"{len(times)} cold iters -> {rates[label] / 1e6:.1f}M rows/s")
    log(f"packed-bench pool pack ratio: {pack_ratio:.2f}x")
    return {
        "packed_rate": round(rates["packed"], 0),
        "decoded_rate": round(rates["decoded"], 0),
        "pack_ratio": round(pack_ratio, 3),
    }


def _bench_filter(iters: int):
    """Selective-filter comparison (filter passes ~5% of 16×4096 rows,
    groupBy on a different dim): the device-bitmap filter path
    (engine/filters.py — resident packed words + in-program bit test) vs
    the LUT/column path, COLD (pool cleared before every timed iter, so
    each run pays full staging: the device path ships 1 bit/row of filter
    state instead of a 4-byte/row id column), plus the WARM
    filter_cache_hit_rate (resident filter results skipping the algebra)."""
    from druid_tpu.data.devicepool import device_pool
    from druid_tpu.engine import filters as filters_mod
    from druid_tpu.engine.executor import QueryExecutor
    from druid_tpu.query.aggregators import CountAggregator, LongSumAggregator
    from druid_tpu.query.filters import InFilter
    from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery

    n_segments = int(os.environ.get("DRUID_TPU_BENCH_BATCH_SEGMENTS", 16))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_BATCH_ROWS", 4096))
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    total_rows = sum(s.n_rows for s in segments)
    dimA_vals = list(segments[0].dims["dimA"].dictionary.values)
    query = GroupByQuery.of(
        "bench", [headline_interval()], [DefaultDimensionSpec("dimB")],
        [CountAggregator("rows"), LongSumAggregator("lsum", "metLong")],
        granularity="all",
        # uniform dimA: k of 100 values ≈ k% selectivity; dimA is
        # filter-ONLY, so the device path never stages its id column
        filter=InFilter("dimA", dimA_vals[: max(len(dimA_vals) // 20, 1)]))
    executor = QueryExecutor(segments)
    pool = device_pool()

    rates = {}
    for label, on in (("host", False), ("device", True)):
        prev = filters_mod.set_device_bitmap_enabled(on)
        try:
            t = time.time()
            executor.run(query)
            log(f"filter-bench warmup {label}: {time.time() - t:.2f}s")
            times = []
            for _ in range(max(iters, 3)):
                pool.clear()             # cold: full staging every iter
                t = time.time()
                executor.run(query)
                times.append(time.time() - t)
        finally:
            filters_mod.set_device_bitmap_enabled(prev)
        rates[label] = total_rows / min(times)
        log(f"filter-bench {label}: best {min(times) * 1e3:.1f}ms over "
            f"{len(times)} cold iters -> {rates[label] / 1e6:.1f}M rows/s")

    # warm: resident filter results — two uncleared device-mode runs, hit
    # rate over the second run's probes
    prev = filters_mod.set_device_bitmap_enabled(True)
    try:
        executor.run(query)
        s0 = filters_mod.filter_bitmap_stats().snapshot()
        executor.run(query)
        s1 = filters_mod.filter_bitmap_stats().snapshot()
    finally:
        filters_mod.set_device_bitmap_enabled(prev)
    d_hits = s1["hits"] - s0["hits"]
    probes = d_hits + (s1["misses"] - s0["misses"])
    hit_rate = d_hits / probes if probes else 0.0
    log(f"filter-bench warm cache hit rate: {hit_rate:.3f} "
        f"({d_hits}/{probes} probes)")
    return {
        "filter_host_rate": round(rates["host"], 0),
        "filter_device_rate": round(rates["device"], 0),
        "filter_speedup": round(rates["device"] / rates["host"], 2),
        "filter_cache_hit_rate": round(hit_rate, 3),
    }


def _bench_fused(iters: int):
    """Megakernel comparison: a bitmap-eligible filter on a filter-only
    dim, groupBy on another dim, per-segment execution (batching off) —
    the shape where the staged path pays a bitmap fill dispatch PLUS the
    aggregation dispatch per cold segment and the fused path
    (engine/megakernel.py) pays exactly one program per segment. The pool
    is cleared before every timed iteration so each run is a true cold
    query (full staging both modes; the delta is the fill-dispatch work),
    and rounds INTERLEAVE the modes so machine-load drift cancels.
    dispatch_count_* come from a dedicated single-segment cold run per
    mode via the obs dispatch counter — the megakernel's one-dispatch
    contract as a recorded number. donated_tick_rate is the WARM
    repeated-execution rate through the fused path (the scheduler-tick
    shape whose partial buffers donate in place on accelerator
    backends)."""
    from druid_tpu.data.devicepool import device_pool
    from druid_tpu.engine import batching, megakernel
    from druid_tpu.engine.executor import QueryExecutor
    from druid_tpu.obs import dispatch as dispatch_mod
    from druid_tpu.query.aggregators import CountAggregator, LongSumAggregator
    from druid_tpu.query.filters import InFilter
    from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery

    # many SMALL segments: per-query fixed cost amortizes over 2N staged
    # dispatches vs N fused ones, so the fused margin is structural
    n_segments = int(os.environ.get("DRUID_TPU_BENCH_FUSED_SEGMENTS", 8))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_FUSED_ROWS", 2048))
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    total_rows = sum(s.n_rows for s in segments)
    dimA_vals = list(segments[0].dims["dimA"].dictionary.values)
    query = GroupByQuery.of(
        "bench", [headline_interval()], [DefaultDimensionSpec("dimB")],
        [CountAggregator("rows"), LongSumAggregator("lsum", "metLong")],
        granularity="all",
        filter=InFilter("dimA", dimA_vals[: max(len(dimA_vals) // 20, 1)]))
    executor = QueryExecutor(segments)
    single = QueryExecutor(segments[:1])
    pool = device_pool()

    modes = (("staged", False), ("fused", True))
    dispatches = {}
    pb = batching.set_enabled(False)     # per-segment: the megaize path
    try:
        for label, on in modes:
            prev = megakernel.set_enabled(on)
            try:
                t = time.time()
                executor.run(query)      # warm: compile both programs
                log(f"fused-bench warmup {label}: {time.time() - t:.2f}s")
                single.run(query)
                pool.clear()             # dedicated cold dispatch count:
                d0 = dispatch_mod.count()    # ONE segment, ONE cold query
                single.run(query)
                dispatches[label] = dispatch_mod.count() - d0
            finally:
                megakernel.set_enabled(prev)
        times = {label: [] for label, _ in modes}
        for _ in range(max(iters, 5)):
            for label, on in modes:
                prev = megakernel.set_enabled(on)
                try:
                    pool.clear()         # cold: full staging every iter
                    t = time.time()
                    executor.run(query)
                    times[label].append(time.time() - t)
                finally:
                    megakernel.set_enabled(prev)
    finally:
        batching.set_enabled(pb)
    rates = {label: total_rows / min(ts) for label, ts in times.items()}
    for label, _ in modes:
        log(f"fused-bench {label}: best {min(times[label]) * 1e3:.1f}ms "
            f"over {len(times[label])} cold iters "
            f"(single-segment cold = {dispatches[label]} dispatch(es)) "
            f"-> {rates[label] / 1e6:.1f}M rows/s")

    # warm repeated execution through the fused path — the scheduler-tick
    # shape; on accelerator backends the partial grids donate in place.
    # Batching stays OFF here too: the batched path never megaizes, so
    # re-enabling it would time the wrong code path.
    prev = megakernel.set_enabled(True)
    pb = batching.set_enabled(False)
    d0 = megakernel.stats().snapshot()["donatedBytes"]
    try:
        executor.run(query)
        ticks = max(iters, 3)
        t0 = time.time()
        for _ in range(ticks):
            executor.run(query)
        tick_rate = total_rows * ticks / (time.time() - t0)
    finally:
        batching.set_enabled(pb)
        megakernel.set_enabled(prev)
    d_donated = megakernel.stats().snapshot()["donatedBytes"] - d0
    log(f"fused-bench donated ticks: {ticks} warm run(s) "
        f"-> {tick_rate / 1e6:.1f}M rows/s (donated {d_donated}B)")
    return {
        "fused_rate": round(rates["fused"], 0),
        "staged_rate": round(rates["staged"], 0),
        "fused_speedup": round(rates["fused"] / rates["staged"], 2),
        "dispatch_count_fused": dispatches["fused"],
        "dispatch_count_staged": dispatches["staged"],
        "donated_tick_rate": round(tick_rate, 0),
    }


def cascade_segments(n_segments: int, rows: int):
    """Rollup-shaped RLE-friendly segments: dimension-sorted rows,
    near-constant time, a constant rollup count metric and a run-aligned
    small-range value metric — the skewed-real-data shape the cascade
    rungs (data/cascade.py) exist for."""
    from druid_tpu.data.segment import SegmentBuilder
    iv = headline_interval()
    card = 64
    reps = -(-rows // card)
    segs = []
    for si in range(n_segments):
        b = SegmentBuilder("cascade", iv, version="v0", partition=si)
        dim_a = np.repeat([f"a{i:04d}" for i in range(card)], reps)[:rows]
        dim_b = np.repeat([f"b{i:04d}" for i in range(card)], reps)[:rows]
        time = iv.start + (np.arange(rows, dtype=np.int64) // 64)
        val = np.repeat((np.arange(card) * 37) % 1000, reps)[:rows]
        b.add_columns(time, {"dimA": dim_a.tolist(), "dimB": dim_b.tolist()},
                      {"cnt": np.ones(rows, dtype=np.int64),
                       "val": val.astype(np.int64)})
        segs.append(b.build())
    return segs


def _bench_cascade(iters: int):
    """Cascaded-encodings comparison (data/cascade.py) on the RLE-friendly
    rollup shape, pool CLEARED before every timed iteration:

      rle_rate          cold rate with the cascade rungs on, through the
                        ROW program (run-domain pinned off — since the
                        uniform-granularity rung even the hour query
                        would ride run space), vs packed-only (logged);
      cascade_ratio     decoded-equivalent / actual bytes of the
                        cascade-encoded pool entries after the cold run;
      code_domain_rate  WARM rate of the run-domain-eligible variant
                        (granularity all): the whole aggregation over run
                        metadata, zero unpack, zero row-width staging.
    """
    from druid_tpu.data import cascade
    from druid_tpu.data.devicepool import device_pool
    from druid_tpu.engine.executor import QueryExecutor
    from druid_tpu.query.aggregators import (CountAggregator,
                                             LongSumAggregator)
    from druid_tpu.query.filters import InFilter
    from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery

    n_segments = int(os.environ.get("DRUID_TPU_BENCH_CASCADE_SEGMENTS", 8))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_CASCADE_ROWS", 8192))
    segments = cascade_segments(n_segments, rows_per_seg)
    total_rows = sum(s.n_rows for s in segments)
    dim_b_vals = list(segments[0].dims["dimB"].dictionary.values)
    aggs = [CountAggregator("rows"), LongSumAggregator("c", "cnt"),
            LongSumAggregator("v", "val")]
    flt = InFilter("dimB", dim_b_vals[::2])
    row_query = GroupByQuery.of(
        "cascade", [headline_interval()], [DefaultDimensionSpec("dimA")],
        aggs, granularity="hour", filter=flt)
    run_query = GroupByQuery.of(
        "cascade", [headline_interval()], [DefaultDimensionSpec("dimA")],
        aggs, granularity="all", filter=flt)
    executor = QueryExecutor(segments)
    pool = device_pool()

    rates = {}
    cascade_ratio = 0.0
    # rle_rate/cascade_ratio measure the ROW program's STAGED bytes: the
    # uniform-granularity run-domain rung would serve this hour-aligned
    # shape from run tables with no column staging at all, so it is
    # pinned off here (code_domain_rate below measures it on)
    prev_rd = cascade.set_run_domain_enabled(False)
    try:
        for label, on in (("packed_only", False), ("cascade", True)):
            prev = cascade.set_enabled(on)
            try:
                t = time.time()
                executor.run(row_query)  # warm: compile once per mode
                log(f"cascade-bench warmup {label}: "
                    f"{time.time() - t:.2f}s")
                times = []
                for _ in range(max(iters, 3)):
                    pool.clear()         # force the cold-miss H2D path
                    t = time.time()
                    executor.run(row_query)
                    times.append(time.time() - t)
                if on:
                    cascade_ratio = pool.snapshot().cascade_ratio
            finally:
                cascade.set_enabled(prev)
            rates[label] = total_rows / min(times)
            log(f"cascade-bench {label}: best {min(times) * 1e3:.1f}ms "
                f"over {len(times)} cold iters -> "
                f"{rates[label] / 1e6:.1f}M rows/s")
    finally:
        # restored in a finally: main() swallows bench-section failures,
        # and leaving run-domain off would silently poison every later
        # section's numbers in the same JSON line
        cascade.set_run_domain_enabled(prev_rd)
    log(f"cascade-bench pool cascade ratio: {cascade_ratio:.2f}x")

    # code-domain: warm repeated execution of the run-space variant
    prev = cascade.set_enabled(True)
    try:
        executor.run(run_query)          # warm: run tables + compile
        h0 = cascade.code_domain_stats().snapshot()["hits"]
        ticks = max(iters, 3)
        t0 = time.time()
        for _ in range(ticks):
            executor.run(run_query)
        code_rate = total_rows * ticks / (time.time() - t0)
        hits = cascade.code_domain_stats().snapshot()["hits"] - h0
    finally:
        cascade.set_enabled(prev)
    log(f"cascade-bench code-domain: {ticks} warm run(s), {hits} run-space "
        f"executions -> {code_rate / 1e6:.1f}M rows/s")
    return {
        "rle_rate": round(rates["cascade"], 0),
        "packed_only_rate": round(rates["packed_only"], 0),
        "cascade_ratio": round(cascade_ratio, 3),
        "code_domain_rate": round(code_rate, 0),
    }


def _bench_segment_io(iters: int):
    """Segment format V1 vs V2 (storage/format_v2.py) on the RLE-friendly
    rollup shape:

      v1_load_rate / v2_load_rate  rows/s of a cold load_segment() from a
                                   freshly persisted directory (V2 is mmap
                                   + descriptor reconstruction — the block
                                   codec never runs for eligible columns);
      disk_ratio                   V1 on-disk bytes / V2 on-disk bytes;
      wire_bytes_v1 / wire_bytes_v2  dumps_partials payload size for the
                                   same AggregatePartials, raw (version-1)
                                   vs compressed (version-2) wire mode.
    """
    import shutil
    import tempfile

    from druid_tpu.cluster import wire
    from druid_tpu.cluster.view import DataNode
    from druid_tpu.query.aggregators import (CountAggregator,
                                             LongSumAggregator)
    from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery
    from druid_tpu.storage.format import load_segment, persist_segment
    from druid_tpu.storage.format_v2 import persist_segment_v2

    rows = int(os.environ.get("DRUID_TPU_BENCH_SEGIO_ROWS", 65536))
    seg = cascade_segments(1, rows)[0]
    tmp = tempfile.mkdtemp(prefix="bench-segio-")
    try:
        d1 = os.path.join(tmp, "v1")
        d2 = os.path.join(tmp, "v2")
        b1 = persist_segment(seg, d1)
        b2 = persist_segment_v2(seg, d2)

        def load_rate(d):
            times = []
            for _ in range(max(iters, 3)):
                t = time.time()
                s = load_segment(d)
                times.append(time.time() - t)
                del s  # V2 holds mmaps via its mapper; drop before rmtree
            return rows / min(times)

        r1 = load_rate(d1)
        r2 = load_rate(d2)
        log(f"segio-bench load: v1 {r1 / 1e6:.1f}M rows/s, "
            f"v2 {r2 / 1e6:.1f}M rows/s "
            f"(disk {b1} -> {b2} bytes, {b1 / b2:.2f}x)")

        # wire: partials for a granularity-hour groupBy over the rollup
        # shape — the per-bucket states are heavily repeated, the shape
        # the wire rle/narrow encodings exist for
        node = DataNode("bench-segio")
        node.load_segment(seg)
        query = GroupByQuery.of(
            "cascade", [headline_interval()], [DefaultDimensionSpec("dimA")],
            [CountAggregator("rows"), LongSumAggregator("c", "cnt")],
            granularity="hour")
        ap, served = node.run_partials(query, [str(seg.id)])
        w1 = len(wire.dumps_partials(ap, served, compress=False))
        w2 = len(wire.dumps_partials(ap, served, compress=True))
        log(f"segio-bench wire: raw {w1} -> compressed {w2} bytes "
            f"({w1 / max(w2, 1):.2f}x)")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "v1_load_rate": round(r1, 0),
        "v2_load_rate": round(r2, 0),
        "disk_ratio": round(b1 / b2, 3),
        "wire_bytes_v1": w1,
        "wire_bytes_v2": w2,
    }


def _bench_hll(iters: int):
    """hyperUnique/cardinality at a NON-default register count (log2m=12;
    the ROADMAP-carried rider): per-core rate of a groupBy carrying a
    4096-register sketch, so sketch-width regressions show up here
    instead of only at the default 2048 registers."""
    from druid_tpu.engine.executor import QueryExecutor

    n_segments = int(os.environ.get("DRUID_TPU_BENCH_BATCH_SEGMENTS", 16))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_BATCH_ROWS", 4096))
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    total_rows = sum(s.n_rows for s in segments)
    iv = headline_interval()
    q = {"queryType": "groupBy", "dataSource": "bench",
         "intervals": [str(iv)], "granularity": "all",
         "dimensions": ["dimA"],
         "aggregations": [
             {"type": "count", "name": "rows"},
             {"type": "hyperUnique", "name": "u", "fieldName": "dimB",
              "log2m": 12}]}
    executor = QueryExecutor(segments)
    t = time.time()
    executor.run_json(q)
    log(f"hll-bench warmup: {time.time() - t:.2f}s")
    times = []
    for _ in range(max(iters, 3)):
        t = time.time()
        executor.run_json(q)
        times.append(time.time() - t)
    rate = total_rows / min(times)
    log(f"hll-bench log2m=12: best {min(times) * 1e3:.1f}ms "
        f"-> {rate / 1e6:.1f}M rows/s")
    return {"hll_log2m12_rate": round(rate, 0)}


def _bench_tracing(iters: int):
    """qtrace overhead in one number pair: the batch-comparison query at
    many small segments (the worst case for per-dispatch span overhead —
    tiny device programs, many dispatch boundaries), run with a trace root
    open (every span live) vs without (every span a no-op thread-local
    read). Tracked from run to run so a regression in span cost shows
    up as traced_rate falling away from untraced_rate."""
    from druid_tpu.engine.executor import QueryExecutor
    from druid_tpu.obs import trace as qtrace

    n_segments = int(os.environ.get("DRUID_TPU_BENCH_BATCH_SEGMENTS", 16))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_BATCH_ROWS", 4096))
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    total_rows = sum(s.n_rows for s in segments)
    query = batch_groupby()
    executor = QueryExecutor(segments)

    executor.run(query)                  # warm: compile + staging
    rates = {}
    for label in ("untraced", "traced"):
        times = []
        for _ in range(max(iters, 3)):
            t = time.time()
            if label == "traced":
                with qtrace.root_span("bench/query", service="bench"):
                    executor.run(query)
            else:
                executor.run(query)
            times.append(time.time() - t)
        rates[label] = total_rows / min(times)
        log(f"trace-bench {label}: best {min(times) * 1e3:.1f}ms "
            f"-> {rates[label] / 1e6:.1f}M rows/s")
    return {
        "untraced_rate": round(rates["untraced"], 0),
        "traced_rate": round(rates["traced"], 0),
        "trace_overhead": round(
            1.0 - rates["traced"] / rates["untraced"], 4),
    }


def _bench_scheduler():
    """Closed-loop concurrent-client mode: N clients each issue M SMALL
    queries (one segment apiece — too small for within-query batching, the
    'thousands of small concurrent queries on one hot datasource' shape)
    against a data node, once through the admission-control scheduler
    (cross-query fusion) and once direct. Reports aggregate rows/s and
    per-query p50/p99 latency for both modes — the scheduler's win is the
    cross-query dispatch amortization, its cost is the batching window."""
    import threading

    from druid_tpu.cluster.view import DataNode
    from druid_tpu.server.scheduler import (DataNodeScheduler,
                                            SchedulerConfig)

    n_clients = int(os.environ.get("DRUID_TPU_BENCH_CLIENTS", 8))
    n_queries = int(os.environ.get("DRUID_TPU_BENCH_CLIENT_QUERIES", 12))
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_SCHED_ROWS", 4096))
    n_segments = max(n_clients, 8)
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    node = DataNode("bench-node")
    for s in segments:
        node.load_segment(s)
    sids = [str(s.id) for s in segments]
    query = batch_groupby()

    def run_mode(use_sched: bool):
        sched = None
        if use_sched:
            sched = DataNodeScheduler(
                node, SchedulerConfig(batch_window_ms=3.0,
                                      max_queue_depth=4 * n_clients,
                                      lane_depths={})).start()
        lat_ms = [[] for _ in range(n_clients)]
        barrier = threading.Barrier(n_clients)

        def client(ci: int, record: bool):
            barrier.wait()
            for k in range(n_queries):
                sid = [sids[(ci + k) % n_segments]]
                t = time.time()
                if sched is not None:
                    sched.submit(query, sid)
                else:
                    node.run_partials(query, sid)
                if record:
                    lat_ms[ci].append((time.time() - t) * 1e3)

        def wave(record: bool) -> float:
            threads = [threading.Thread(target=client, args=(ci, record))
                       for ci in range(n_clients)]
            t0 = time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.time() - t0

        try:
            # warm waves: flush composition is timing-dependent (chunk
            # size K is a compile key), so no warmup can GUARANTEE every
            # shape the recorded wave will hit — two waves cover the
            # common ones and a stray compile shows up as a p99 outlier,
            # not a shifted p50
            wave(record=False)
            wave(record=False)
            wall = wave(record=True)
        finally:
            if sched is not None:
                sched.stop()
        lats = sorted(x for per in lat_ms for x in per)
        seg_rows = {str(s.id): s.n_rows for s in segments}
        total_rows = sum(seg_rows[sids[(ci + k) % n_segments]]
                         for ci in range(n_clients)
                         for k in range(n_queries))
        return {
            "rate": total_rows / wall,
            "p50_ms": lats[len(lats) // 2],
            "p99_ms": lats[min(len(lats) - 1, int(len(lats) * 0.99))],
        }

    off = run_mode(use_sched=False)
    on = run_mode(use_sched=True)
    for label, r in (("off", off), ("on", on)):
        log(f"sched-bench {label}: {r['rate'] / 1e6:.1f}M rows/s "
            f"p50 {r['p50_ms']:.1f}ms p99 {r['p99_ms']:.1f}ms")
    return {
        "sched_clients": n_clients,
        "sched_off_rate": round(off["rate"], 0),
        "sched_on_rate": round(on["rate"], 0),
        "sched_speedup": round(on["rate"] / off["rate"], 2),
        "sched_off_p50_ms": round(off["p50_ms"], 2),
        "sched_off_p99_ms": round(off["p99_ms"], 2),
        "sched_on_p50_ms": round(on["p50_ms"], 2),
        "sched_on_p99_ms": round(on["p99_ms"], 2),
    }


def _bench_standing():
    """Standing queries over streaming ingest: per-wave tick cost of the
    incremental standing program vs a from-scratch re-scan of every sink
    (rates are cumulative rows SERVED per second of serving work), plus
    the fan-out story — N subscribers on one hub (ONE standing program)
    vs N independent queries."""
    import numpy as np

    from druid_tpu.cluster.metadata import MetadataStore
    from druid_tpu.engine.standing import StandingQuery
    from druid_tpu.ingest import (Appenderator, RowBatch, SegmentAllocator,
                                  StreamAppenderatorDriver)
    from druid_tpu.query import aggregators as A
    from druid_tpu.query.model import TimeseriesQuery, query_from_json
    from druid_tpu.server.subscriptions import SubscriptionHub
    from druid_tpu.utils.intervals import Interval

    rows = int(os.environ.get("DRUID_TPU_BENCH_STANDING_ROWS", 400_000))
    waves = int(os.environ.get("DRUID_TPU_BENCH_STANDING_WAVES", 8))
    n_subs = int(os.environ.get("DRUID_TPU_BENCH_STANDING_SUBS", 64))
    per_wave = max(rows // waves, 1)

    iv = Interval.of("2026-03-01", "2026-03-02")
    rng = np.random.default_rng(7)
    app = Appenderator(
        "bench_rt",
        [A.CountAggregator("rows"), A.LongSumAggregator("v", "value")],
        query_granularity="none", max_rows_per_hydrant=per_wave)
    driver = StreamAppenderatorDriver(
        app, SegmentAllocator(MetadataStore(), "day"), MetadataStore())
    q = query_from_json({
        "queryType": "timeseries", "dataSource": "bench_rt",
        "intervals": [str(iv)], "granularity": "hour",
        "aggregations": [
            {"type": "longSum", "name": "rows", "fieldName": "rows"},
            {"type": "longSum", "name": "v", "fieldName": "v"}]})
    assert isinstance(q, TimeseriesQuery)
    sq = StandingQuery(q, [app])

    def wave_batch():
        ts = iv.start + rng.integers(0, 24 * 3_600_000, size=per_wave)
        return RowBatch(ts.astype(np.int64), {
            "page": [f"p{int(x)}" for x in rng.integers(16, size=per_wave)],
            "value": rng.integers(0, 100, size=per_wave)})

    served = 0
    t_standing = 0.0
    t_rescan = 0.0
    total = 0
    for w in range(waves):
        driver.add_batch(wave_batch())
        total += per_wave
        if w % 2 == 1:
            app.persist_all()
        t = time.time()
        sq.tick()
        sq.rows()
        t_standing += time.time() - t
        t = time.time()
        sq.rescan_rows()
        t_rescan += time.time() - t
        served += total
    sq.close()
    standing_rate = served / max(t_standing, 1e-9)
    rescan_rate = served / max(t_rescan, 1e-9)
    log(f"standing-bench: {waves} waves x {per_wave} rows — standing "
        f"{t_standing * 1e3:.1f}ms vs rescan {t_rescan * 1e3:.1f}ms "
        f"({standing_rate / rescan_rate:.2f}x)")

    # fan-out: N subscribers dedupe onto ONE standing program; the
    # comparison is N independent executor runs over the same sinks
    hub = SubscriptionHub(idle_timeout_s=0)
    hub.attach(app)
    subs = [hub.subscribe(q) for _ in range(n_subs)]
    driver.add_batch(wave_batch())
    hub.tick()                            # warm: compile + first fold
    driver.add_batch(wave_batch())
    t = time.time()
    hub.tick()
    for sid, _ in subs:
        hub.poll(sid)
    t_hub = time.time() - t
    n_programs = hub.active_programs()

    from druid_tpu.engine import QueryExecutor
    world = app.query_segments()
    QueryExecutor().run(q, segments=world)   # warm
    t = time.time()
    for _ in range(n_subs):
        QueryExecutor().run(q, segments=world)
    t_ind = time.time() - t
    hub.stop()
    log(f"standing-bench fanout x{n_subs}: hub {t_hub * 1e3:.1f}ms vs "
        f"independent {t_ind * 1e3:.1f}ms "
        f"({t_ind / max(t_hub, 1e-9):.1f}x), {n_programs} program(s)")
    return {
        "standing_rate": round(standing_rate, 0),
        "rescan_rate": round(rescan_rate, 0),
        "standing_speedup": round(standing_rate / rescan_rate, 3),
        "standing_fanout_subs": n_subs,
        "standing_fanout_hub_ms": round(t_hub * 1e3, 2),
        "standing_fanout_independent_ms": round(t_ind * 1e3, 2),
        "standing_fanout_speedup": round(t_ind / max(t_hub, 1e-9), 3),
        "standing_programs": n_programs,
    }


def _bench_soak():
    """Opt-in (DRUID_TPU_BENCH_SOAK=<waves>) resource-drift mode: repeated
    query waves + full server start/stop cycles, reporting rss/fd/thread
    drift between a post-warmup baseline and the end state. Zero drift is
    the contract a months-long serving process needs; any linear growth
    here is the wedged-run (rc=124) failure class in miniature."""
    import gc
    import threading

    from druid_tpu.cluster.dataserver import DataNodeServer
    from druid_tpu.cluster.view import DataNode

    waves = int(os.environ.get("DRUID_TPU_BENCH_SOAK", 0))
    if waves <= 0:
        return {}
    rows_per_seg = int(os.environ.get("DRUID_TPU_BENCH_SCHED_ROWS", 4096))
    n_segments = 4
    segments = headline_segments(rows_per_seg * n_segments, n_segments)
    sids = [str(s.id) for s in segments]
    query = batch_groupby()

    def rss_kb() -> int:
        try:
            with open("/proc/self/status") as fh:
                for line in fh:
                    if line.startswith("VmRSS:"):
                        return int(line.split()[1])
        except OSError:
            pass
        return 0

    def fd_count() -> int:
        try:
            return len(os.listdir("/proc/self/fd"))
        except OSError:
            return 0

    def cycle():
        node = DataNode("soak-node")
        for s in segments:
            node.load_segment(s)
        srv = DataNodeServer(node).start()
        try:
            for _ in range(3):
                node.run_partials(query, sids)
        finally:
            srv.stop()

    cycle()                               # warmup: lazy init + compiles
    gc.collect()
    base = (rss_kb(), fd_count(), threading.active_count())
    t0 = time.time()
    for _ in range(waves):
        cycle()
    gc.collect()
    end = (rss_kb(), fd_count(), threading.active_count())
    log(f"soak: {waves} wave(s) in {time.time() - t0:.1f}s — rss drift "
        f"{end[0] - base[0]}KB, fd drift {end[1] - base[1]}, thread "
        f"drift {end[2] - base[2]}")
    return {
        "soak_waves": waves,
        "soak_rss_drift_kb": end[0] - base[0],
        "soak_fd_drift": end[1] - base[1],
        "soak_thread_drift": end[2] - base[2],
    }


def main():
    rows = int(os.environ.get("DRUID_TPU_BENCH_ROWS", 100_000_000))
    n_segments = int(os.environ.get("DRUID_TPU_BENCH_SEGMENTS", 8))
    iters = int(os.environ.get("DRUID_TPU_BENCH_ITERS", 5))

    device = _init_backend()

    from druid_tpu.engine import QueryExecutor, pallas_agg
    from druid_tpu.parallel import make_mesh

    t0 = time.time()
    segments = headline_segments(rows, n_segments)
    total_rows = sum(s.n_rows for s in segments)
    log(f"generated {total_rows:,} rows in {n_segments} segments "
        f"({time.time() - t0:.1f}s)")

    groupby = headline_groupby()
    topn = headline_topn(segments)

    executor = QueryExecutor(segments, mesh=make_mesh(1))

    def timed(query, label):
        t = time.time()
        n = len(executor.run(query))
        log(f"warmup {label}: {time.time() - t:.2f}s ({n} rows) "
            "[compile + H2D staging]")
        times = []
        for _ in range(iters):
            t = time.time()
            executor.run(query)
            times.append(time.time() - t)
        best = min(times)
        log(f"{label}: best {best * 1e3:.1f}ms over {iters} iters "
            f"-> {total_rows / best / 1e6:.0f}M rows/s")
        return best, times

    t_gb, gb_times = timed(groupby, "groupBy 2dim/3agg+filter")
    t_tn, tn_times = timed(topn, "topN dimB/2agg+filter")

    # warm-latency story (BASELINE.json's metric includes p50 latency)
    lat = sorted(gb_times + tn_times)
    p50 = lat[len(lat) // 2] * 1e3
    p95 = lat[min(len(lat) - 1, int(len(lat) * 0.95))] * 1e3
    log(f"warm latency: p50 {p50:.0f}ms  p95 {p95:.0f}ms "
        f"(over {len(lat)} timed queries @ {total_rows:,} rows)")

    # an add-on comparison that raises must not cost the already-measured
    # headline its ONE JSON line — it reports an error field instead, and
    # the process exits non-zero after printing
    sections = {}
    for name, fn in [("batch", lambda: _bench_batching(iters)),
                     ("sharded", lambda: _bench_sharded(iters)),
                     ("packed", lambda: _bench_packed(iters)),
                     ("filter", lambda: _bench_filter(iters)),
                     ("fused", lambda: _bench_fused(iters)),
                     ("cascade", lambda: _bench_cascade(iters)),
                     ("segio", lambda: _bench_segment_io(iters)),
                     ("hll", lambda: _bench_hll(iters)),
                     ("trace", lambda: _bench_tracing(iters)),
                     ("sched", _bench_scheduler),
                     ("standing", _bench_standing),
                     ("soak", _bench_soak)]:
        try:
            sections.update(fn())
        except Exception as e:  # druidlint: disable=swallowed-exception
            log(f"{name}-bench failed: {type(e).__name__}: {e}")
            sections[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]

    value = 2 * total_rows / (t_gb + t_tn)
    baseline = 36_246_530.0  # Java rows/sec/core scan-aggregate upper bound
    out = {
        "metric": "groupby+topn_scan_rate",
        "value": round(value, 0),
        "unit": "rows/sec/chip",
        "vs_baseline": round(value / baseline, 2),
        "p50_ms": round(p50, 1),
        "p95_ms": round(p95, 1),
    }
    out.update(device)
    out.update(sections)
    failed = sorted(k for k in sections if k.endswith("_error"))
    if pallas_agg.broken_reason() is not None:
        out["pallas_broken"] = pallas_agg.broken_reason()[:300]
        failed.append("pallas_broken")
    print(json.dumps(out), flush=True)
    if failed:
        log(f"bench: FAILED — {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
