"""Device-side bitmap algebra (ROADMAP item 5): filter bitmaps as resident
packed words, combined in-program, cached like jit programs.

The exhaustive parity gate: random filter trees (depth ≤ 4 over
selector/in/bound/not) evaluated host-mask (device bitmaps off) vs
device-bitmap vs per-segment vs batched must agree EXACTLY — floats
included — across sparse/dense/boundary densities (n_rows not divisible by
32). Plus: the filter-result cache (hits skip leaf staging + algebra), the
no-column-staging contract, the batching widenings the in-program mask
unblocks (2-D HLL metric columns, per-segment query-time dictionaries),
and cross-filter chunk fusion.
"""
import numpy as np
import pytest

import druid_tpu.engine  # noqa: F401  (x64 on before jax numerics)
from druid_tpu.data.bitmap import SparseBitmap
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine import batching
from druid_tpu.engine import filters as filters_mod
from druid_tpu.engine.executor import QueryExecutor
from druid_tpu.engine.filters import (DeviceBitmapNode, collect_bitmap_nodes,
                                      filter_bitmap_stats, host_mask,
                                      plan_filter, simplify_node)
from druid_tpu.query import filters as F
from druid_tpu.utils.intervals import Interval

IV = Interval.of("2026-05-01", "2026-05-05")

SCHEMA = (
    ColumnSpec("dLo", "string", cardinality=8),       # dense leaves
    ColumnSpec("dMid", "string", cardinality=60),
    ColumnSpec("dHi", "string", cardinality=800),     # sparse leaves
    ColumnSpec("metLong", "long", low=0, high=1000),
    ColumnSpec("metDouble", "double", low=0.0, high=1.0),
)


@pytest.fixture(scope="module")
def fb_segments():
    # 3333 rows/segment: n_rows not divisible by 32 (word-boundary rows)
    return DataGenerator(SCHEMA, seed=13).segments(
        4, 3333, IV, datasource="fb")


@pytest.fixture(autouse=True)
def _bitmap_on():
    # this module tests the STAGED device-bitmap path (fill wave + resident
    # combined words); the megakernel would fuse cold per-segment filters
    # inline and skip the combined-words cache entirely — its own behavior
    # is covered by tests/test_megakernel.py
    from druid_tpu.engine import megakernel
    prev = filters_mod.set_device_bitmap_enabled(True)
    prev_mega = megakernel.set_enabled(False)
    yield
    filters_mod.set_device_bitmap_enabled(prev)
    megakernel.set_enabled(prev_mega)


def _rand_leaf(rng, seg):
    dim = ("dLo", "dMid", "dHi")[rng.integers(3)]
    vals = list(seg.dims[dim].dictionary.values)
    kind = rng.integers(3)
    if kind == 0:
        v = vals[rng.integers(len(vals))] if rng.random() < 0.85 \
            else "zzz-missing"
        return F.SelectorFilter(dim, v)
    if kind == 1:
        k = int(rng.integers(1, 5))
        picks = [vals[rng.integers(len(vals))] for _ in range(k)]
        return F.InFilter(dim, tuple(picks))
    lo = vals[rng.integers(len(vals))]
    hi = vals[rng.integers(len(vals))]
    lo, hi = (lo, hi) if lo <= hi else (hi, lo)
    return F.BoundFilter(dim, lower=lo, upper=hi,
                         lower_strict=bool(rng.integers(2)))


def _rand_tree(rng, seg, depth):
    if depth == 0 or rng.random() < 0.35:
        return _rand_leaf(rng, seg)
    op = rng.integers(3)
    if op == 0:
        return F.NotFilter(_rand_tree(rng, seg, depth - 1))
    kids = tuple(_rand_tree(rng, seg, depth - 1)
                 for _ in range(int(rng.integers(2, 4))))
    return F.AndFilter(kids) if op == 1 else F.OrFilter(kids)


def _query(flt):
    q = {"queryType": "timeseries", "dataSource": "fb",
         "intervals": [str(IV)], "granularity": "all",
         "aggregations": [
             {"type": "count", "name": "n"},
             {"type": "longSum", "name": "s", "fieldName": "metLong"},
             {"type": "doubleSum", "name": "d", "fieldName": "metDouble"}]}
    if flt is not None:
        q["filter"] = flt.to_json()
    return q


def _oracle_count(flt, segs):
    return sum(int(host_mask(flt, s).sum()) for s in segs)


def test_random_tree_parity_gate(fb_segments):
    """host-mask vs device-bitmap vs per-segment vs batched: exact equality
    including float aggregates, counts pinned to the numpy host-mask oracle."""
    rng = np.random.default_rng(99)
    ex = QueryExecutor(fb_segments)
    for i in range(14):
        flt = _rand_tree(rng, fb_segments[0], depth=4 if i % 2 else 2)
        q = _query(flt)
        batched = ex.run_json(q)                     # device bitmap + batch
        pb = batching.set_enabled(False)
        try:
            per_segment = ex.run_json(q)             # device bitmap, no batch
            prev = filters_mod.set_device_bitmap_enabled(False)
            try:
                host = ex.run_json(q)                # LUT/host-mask path
            finally:
                filters_mod.set_device_bitmap_enabled(prev)
        finally:
            batching.set_enabled(pb)
        assert batched == per_segment == host, f"tree {i}: {flt}"
        got_n = batched[0]["result"]["n"] if batched else 0
        assert got_n == _oracle_count(flt, fb_segments), f"tree {i}"


def test_mixed_tree_partial_rewrite_parity(fb_segments):
    """AND of a bitmap subtree and a numeric (non-bitmap) predicate: only
    the eligible branch compiles to words; results stay exact."""
    vals = fb_segments[0].dims["dMid"].dictionary.values
    flt = F.AndFilter((
        F.OrFilter((F.SelectorFilter("dLo",
                                     fb_segments[0].dims["dLo"]
                                     .dictionary.values[2]),
                    F.InFilter("dMid", tuple(vals[:4])))),
        F.BoundFilter("metLong", lower=100, upper=900, ordering="numeric"),
    ))
    node = simplify_node(plan_filter(flt, fb_segments[0]))
    bns = collect_bitmap_nodes(node)
    assert len(bns) == 1                    # the string branch, not the root
    assert node.required_device_columns() == {"metLong"}
    ex = QueryExecutor(fb_segments)
    q = _query(flt)
    on = ex.run_json(q)
    prev = filters_mod.set_device_bitmap_enabled(False)
    try:
        off = ex.run_json(q)
    finally:
        filters_mod.set_device_bitmap_enabled(prev)
    assert on == off
    assert on[0]["result"]["n"] == _oracle_count(flt, fb_segments)


def test_filter_only_dims_are_not_staged(fb_segments):
    """The staging win: a dim referenced ONLY by the filter compiles to
    resident words (1 bit/row) — no id column staging at all."""
    seg = fb_segments[0]
    flt = F.InFilter("dHi", tuple(seg.dims["dHi"].dictionary.values[:5]))
    node = simplify_node(plan_filter(flt, seg))
    assert isinstance(node, DeviceBitmapNode)
    assert node.required_device_columns() == set()
    from druid_tpu.engine.grouping import needed_columns
    _, columns = needed_columns(seg, [], (), node, [])
    assert "dHi" not in columns


def test_result_cache_hits_skip_rebuild():
    """Warm queries hit resident words: the filter structural signature +
    segment identity + aux digest key the pool like the jit caches.
    A DEDICATED segment: the pool is session-global and owner-keyed, so a
    shared fixture segment could already hold entries from earlier tests."""
    seg = DataGenerator(SCHEMA, seed=77).segments(
        1, 3333, IV, datasource="fb")[0]
    vals = seg.dims["dLo"].dictionary.values
    flt = F.NotFilter(F.SelectorFilter("dLo", vals[0]))
    ex = QueryExecutor([seg])
    q = _query(flt)
    ex.run_json(q)
    s0 = filter_bitmap_stats().snapshot()
    r1 = ex.run_json(q)
    s1 = filter_bitmap_stats().snapshot()
    assert s1["hits"] == s0["hits"] + 1          # resident words reused
    assert s1["misses"] == s0["misses"]
    assert s1["builtBytes"] == s0["builtBytes"]
    # a DIFFERENT value set (same structure) is a different aux digest
    flt2 = F.NotFilter(F.SelectorFilter("dLo", vals[1]))
    ex.run_json(_query(flt2))
    s2 = filter_bitmap_stats().snapshot()
    assert s2["misses"] == s1["misses"] + 1
    assert r1 == ex.run_json(q)


def test_opt_out_plans_column_path(fb_segments):
    seg = fb_segments[0]
    flt = F.SelectorFilter("dLo", seg.dims["dLo"].dictionary.values[0])
    prev = filters_mod.set_device_bitmap_enabled(False)
    try:
        node = simplify_node(plan_filter(flt, seg))
    finally:
        filters_mod.set_device_bitmap_enabled(prev)
    assert not collect_bitmap_nodes(node)
    # and the explicit arg overrides the process default both ways
    assert collect_bitmap_nodes(simplify_node(
        plan_filter(flt, seg, device_bitmap=True)))
    assert not collect_bitmap_nodes(simplify_node(
        plan_filter(flt, seg, device_bitmap=False)))


def test_fill_program_sparse_scatter_and_xor(fb_segments):
    """The word-wise algebra program directly: a block of sparse id lists
    scatters into words on device, a block of dense words passes through,
    AND/OR/NOT/XOR combine word-wise — against the numpy truth, in a
    two-slot layout whose second slot is padding."""
    import jax
    from druid_tpu.data.bitmap import Bitmap, leaf_rows
    from druid_tpu.engine.filters import _build_fill_wave
    padded = 2048
    rng = np.random.default_rng(4)
    a = rng.random(padded) < 0.003                  # sparse
    b = rng.random(padded) < 0.5                    # dense
    ka, pa = leaf_rows([SparseBitmap(
        np.flatnonzero(a).astype(np.int32), padded)], padded)
    kb, pb = leaf_rows([Bitmap.from_bool(b)], padded)
    assert (ka, kb) == ("sparse", "dense")
    ids = np.full((2, pa.shape[1]), padded, dtype=np.int32)
    ids[0] = pa[0]
    dense = np.zeros((2, padded // 32), dtype=np.int32)
    dense[0] = pb[0].view(np.int32)
    for op, truth in (("and", a & b), ("or", a | b), ("xor", a ^ b),
                      ("not", ~a)):
        structure = ("not", ("leaf", 0)) if op == "not" \
            else (op, (("leaf", 0), ("leaf", 1)))
        blocks = (("sparse",),) if op == "not" \
            else (("sparse",), ("dense",))
        buf = ids.reshape(-1) if op == "not" \
            else np.concatenate([ids.reshape(-1), dense.reshape(-1)])
        words, padding = _build_fill_wave(
            ((structure, 2, blocks),), padded)(jax.device_put(buf))
        words = np.asarray(words)
        rows = np.arange(padded)
        bits = (words[rows // 32] >> (rows % 32).astype(np.uint32)) & 1
        assert np.array_equal(bits.astype(bool), truth), op
        # a padding slot's leaves are empty: all-zero (all-one under NOT)
        assert set(np.asarray(padding).tolist()) \
            == ({0xFFFFFFFF} if op == "not" else {0}), op


# ---------------------------------------------------------------------------
# batching widenings: the workload classes the host-mask path excluded
# ---------------------------------------------------------------------------

def _parity_on_off_batching(ex, q):
    before = batching.stats().snapshot()
    on = ex.run_json(q)
    after = batching.stats().snapshot()
    pb = batching.set_enabled(False)
    try:
        off = ex.run_json(q)
    finally:
        batching.set_enabled(pb)
    assert on == off
    return after["batches"] - before["batches"], \
        after["batchedSegments"] - before["batchedSegments"]


def _hll_segments(n_segments=4, log2m=6):
    """Rolled-up segments carrying a REAL 2-D complex metric column (HLL
    registers) — the workload class `m.values.ndim != 1` used to exclude
    from batching."""
    from druid_tpu.ingest.incremental import IncrementalIndex
    from druid_tpu.query.aggregators import (CountAggregator,
                                             HyperUniqueAggregator)
    specs = [CountAggregator("count"),
             HyperUniqueAggregator("uu", "user", log2m=log2m)]
    t0 = IV.start
    segs = []
    for p in range(n_segments):
        idx = IncrementalIndex("hll", IV, specs, dimensions=["d"],
                               query_granularity="hour")
        for i in range(300):
            idx.add({"timestamp": t0 + i * 1000, "d": f"x{i % 5}",
                     "user": f"u{p}_{i % 40}"})
        segs.append(idx.to_segment(partition=p))
    return segs


def test_complex_2d_metric_columns_take_batched_path():
    """A pre-aggregated HLL register column (ndim == 2) stacks fine now
    that the mask is in-program: the hyperUnique query over rolled-up
    segments batches with exact parity."""
    segs = _hll_segments()
    assert np.asarray(segs[0].metrics["uu"].values).ndim == 2
    q = {"queryType": "groupBy", "dataSource": "hll",
         "intervals": [str(IV)], "granularity": "all",
         "dimensions": ["d"],
         "filter": {"type": "not", "field": {"type": "selector",
                                             "dimension": "d",
                                             "value": "x0"}},
         "aggregations": [
             {"type": "hyperUnique", "name": "u", "fieldName": "uu",
              "log2m": 6},
             {"type": "longSum", "name": "n", "fieldName": "count"}]}
    ex = QueryExecutor(segs)
    batches, n_batched = _parity_on_off_batching(ex, q)
    assert batches >= 1 and n_batched == len(segs)


def test_register_width_is_a_shape_bucket_key():
    """The 2-D column's width is a compile shape: two segments differing
    only in register width must land in DIFFERENT shape buckets (a fused
    chunk would stack mismatched shapes). hyperUnique itself rejects a
    width-mismatched query outright, so this pins the digest directly."""
    from druid_tpu.engine.batching import _plan_for
    from druid_tpu.query.aggregators import HyperUniqueAggregator
    from druid_tpu.query.model import query_from_json
    a = _hll_segments(1, log2m=6)[0]
    b = _hll_segments(1, log2m=7)[0]
    assert np.asarray(a.metrics["uu"].values).shape[1] != \
        np.asarray(b.metrics["uu"].values).shape[1]
    plans = [_plan_for(s, [], 0, [IV], query_from_json(
        {"queryType": "timeseries", "dataSource": "hll",
         "intervals": [str(IV)], "granularity": "all",
         "aggregations": []}).granularity,
        [HyperUniqueAggregator("u", "uu", log2m=lg)], None, ())
        for s, lg in ((a, 6), (b, 7))]
    assert all(p.eligible for p in plans)
    assert plans[0].digest != plans[1].digest


def test_query_time_dictionaries_take_batched_path(fb_segments):
    """Numeric dimensions (per-segment query-time dictionaries) batch: id
    spaces unify across the query's segments (engines.unify_query_dims),
    with exact parity against the per-segment path."""
    q = {"queryType": "groupBy", "dataSource": "fb",
         "intervals": [str(IV)], "granularity": "all",
         "dimensions": ["metLong"],
         "filter": {"type": "bound", "dimension": "metLong", "lower": 0,
                    "upper": 40, "ordering": "numeric"},
         "aggregations": [{"type": "count", "name": "n"},
                          {"type": "doubleSum", "name": "d",
                           "fieldName": "metDouble"}]}
    ex = QueryExecutor(fb_segments)
    batches, segs = _parity_on_off_batching(ex, q)
    assert batches >= 1 and segs == len(fb_segments)


def test_different_bitmap_filters_fuse_into_one_chunk(fb_segments):
    """Two queries with DIFFERENT bitmap filters share one program
    structure (resident words + bit test) and therefore one fused chunk —
    per-slot words carry each query's own filter."""
    from druid_tpu.engine.engines import make_aggregate_partials_multi
    vals = fb_segments[0].dims["dLo"].dictionary.values
    from druid_tpu.query.model import query_from_json
    q1 = query_from_json(_query(F.SelectorFilter("dLo", vals[0])))
    q2 = query_from_json(_query(
        F.NotFilter(F.InFilter("dLo", tuple(vals[1:3])))))
    seen = []
    out = make_aggregate_partials_multi(
        [(q1, fb_segments, None), (q2, fb_segments, None)],
        on_batch=lambda nq, ns, fill: seen.append((nq, ns)))
    assert not any(isinstance(o, BaseException) for o in out)
    assert any(nq == 2 and ns == 2 * len(fb_segments) for nq, ns in seen), \
        seen
    # parity of the fused results against serial single-query execution
    from druid_tpu.engine.engines import make_aggregate_partials
    serial1 = make_aggregate_partials(q1, fb_segments, clamp=False)
    assert len(out[0].partials) == len(serial1.partials)
    for a, b in zip(out[0].partials, serial1.partials):
        assert np.array_equal(a.counts, b.counts)
        for k in a.states:
            assert np.array_equal(np.asarray(a.states[k]),
                                  np.asarray(b.states[k]))


def test_staging_wave_dedups_identical_filters():
    """N fused copies of the same dashboard query build the words ONCE:
    duplicates in one wave count as hits and share the resident array."""
    from druid_tpu.engine.filters import stage_device_bitmaps_multi
    seg = DataGenerator(SCHEMA, seed=88).segments(
        1, 2048, IV, datasource="fbd")[0]
    flt = F.InFilter("dLo", tuple(seg.dims["dLo"].dictionary.values[:2]))
    node = simplify_node(plan_filter(flt, seg))
    s0 = filter_bitmap_stats().snapshot()
    out = stage_device_bitmaps_multi([(seg, node)] * 3, 2048)
    s1 = filter_bitmap_stats().snapshot()
    assert s1["misses"] - s0["misses"] == 1
    assert s1["hits"] - s0["hits"] == 2
    assert s1["builtBytes"] - s0["builtBytes"] == 2048 // 8
    assert out[0][node.col] is out[1][node.col] is out[2][node.col]


def test_monitor_names_declared_and_emitting(fb_segments):
    from druid_tpu.obs import catalog
    from druid_tpu.engine.filters import FilterBitmapMonitor

    class Rec:
        def __init__(self):
            self.seen = {}

        def metric(self, name, value, **dims):
            self.seen[name] = value

    ex = QueryExecutor([fb_segments[0]])
    ex.run_json(_query(F.SelectorFilter(
        "dLo", fb_segments[0].dims["dLo"].dictionary.values[3])))
    mon = FilterBitmapMonitor()
    rec = Rec()
    mon.do_monitor(rec)
    assert not catalog.validate_emitted(rec.seen)
    assert set(rec.seen) == {"query/filter/deviceBitmapHits",
                             "query/filter/deviceBitmapMisses",
                             "query/filter/bytes"}


def test_pool_peek_does_not_touch_stats(fb_segments):
    seg = fb_segments[0]
    pool = seg._pool
    base = pool.snapshot()
    assert seg.device_contains(("nope", 1)) is False
    s = pool.snapshot()
    assert (s.hits, s.misses) == (base.hits, base.misses)
