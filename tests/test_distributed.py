"""Sharded (shard_map + collectives) execution must be result-identical to
the per-segment host-merged path.

Reference analog: CachingClusteredClientTest.java:171 — scatter-gather over
fake servers asserted against direct execution, no sockets. Here: an 8-way
virtual CPU mesh (conftest) stands in for the pod.
"""
import numpy as np
import pytest

from druid_tpu.engine import QueryExecutor
from druid_tpu.parallel import make_mesh, use_mesh
from druid_tpu.query.aggregators import (CardinalityAggregator, CountAggregator,
                                         DoubleMaxAggregator,
                                         DoubleSumAggregator, FilteredAggregator,
                                         FirstAggregator, LastAggregator,
                                         LongMinAggregator, LongSumAggregator)
from druid_tpu.query.filters import (AndFilter, BoundFilter, InFilter,
                                     NotFilter, SelectorFilter)
from druid_tpu.query.model import (DefaultDimensionSpec, GroupByQuery,
                                   TimeseriesQuery, TopNQuery)
from tests.conftest import WEEK

AGGS = [
    CountAggregator("rows"),
    LongSumAggregator("lsum", "metLong"),
    DoubleSumAggregator("dsum", "metDouble"),
    LongMinAggregator("lmin", "metLong"),
    DoubleMaxAggregator("dmax", "metFloat"),
]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _run_both(query, segments, mesh):
    plain = QueryExecutor(segments).run(query)
    with use_mesh(mesh):
        sharded = QueryExecutor(segments).run(query)
    return plain, sharded


def _value_close(a, b):
    if isinstance(a, float) or isinstance(b, float):
        return abs(float(a) - float(b)) <= 1e-6 * (1 + abs(float(a)))
    return a == b


def _assert_rows_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra.keys() == rb.keys()
        for k in ra:
            va, vb = ra[k], rb[k]
            if isinstance(va, dict):
                assert va.keys() == vb.keys()
                for f in va:
                    assert _value_close(va[f], vb[f]), (k, f, va[f], vb[f])
            elif isinstance(va, list):
                assert len(va) == len(vb), k
                for ea, eb in zip(va, vb):
                    assert ea.keys() == eb.keys()
                    for f in ea:
                        assert _value_close(ea[f], eb[f]), (k, f, ea[f], eb[f])
            else:
                assert va == vb, (k, va, vb)


def test_timeseries_sharded_matches(segments, mesh):
    q = TimeseriesQuery.of("test", [WEEK], AGGS, granularity="day",
                           filter=BoundFilter("metLong", lower=10, upper=80,
                                              ordering="numeric"))
    plain, sharded = _run_both(q, segments, mesh)
    _assert_rows_equal(plain, sharded)


def test_timeseries_first_last_sharded(segments, mesh):
    q = TimeseriesQuery.of(
        "test", [WEEK],
        [FirstAggregator("f", "metLong", "long"),
         LastAggregator("l", "metDouble", "double")],
        granularity="day")
    plain, sharded = _run_both(q, segments, mesh)
    _assert_rows_equal(plain, sharded)


def test_timeseries_hll_sharded(segments, mesh):
    q = TimeseriesQuery.of(
        "test", [WEEK],
        [CardinalityAggregator("card", ["dimHi"]), CountAggregator("rows")],
        granularity="all")
    plain, sharded = _run_both(q, segments, mesh)
    _assert_rows_equal(plain, sharded)


def test_topn_sharded_matches(segments, mesh):
    q = TopNQuery.of("test", [WEEK], "dimB", "lsum", 10, AGGS,
                     granularity="all",
                     filter=InFilter("dimA", ["v0", "v1", "v2", "v3"]))
    plain, sharded = _run_both(q, segments, mesh)
    _assert_rows_equal(plain, sharded)


def test_groupby_sharded_matches(segments, mesh):
    q = GroupByQuery.of(
        "test", [WEEK],
        [DefaultDimensionSpec("dimA"), DefaultDimensionSpec("dimB")],
        AGGS + [FilteredAggregator("fsum",
                                   LongSumAggregator("fsum", "metLong"),
                                   SelectorFilter("dimA", "v1"))],
        granularity="day",
        filter=AndFilter([NotFilter(SelectorFilter("dimA", "v9")),
                          BoundFilter("metLong", lower=5, ordering="numeric")]))
    plain, sharded = _run_both(q, segments, mesh)
    # groupBy rows are sorted by the engine's limit path; compare as sets
    key = lambda r: (r["timestamp"], r["event"]["dimA"], r["event"]["dimB"])
    _assert_rows_equal(sorted(plain, key=key), sorted(sharded, key=key))


def test_groupby_uneven_segments(generator, mesh):
    """Segment count not divisible by mesh size → padded empty shards."""
    segs = generator.segments(5, 3_000, WEEK, datasource="uneven")
    q = GroupByQuery.of("uneven", [WEEK], [DefaultDimensionSpec("dimA")],
                        [CountAggregator("rows"),
                         LongSumAggregator("lsum", "metLong")],
                        granularity="all")
    plain, sharded = _run_both(q, segs, mesh)
    key = lambda r: r["event"]["dimA"]
    _assert_rows_equal(sorted(plain, key=key), sorted(sharded, key=key))


UNEQUAL_ROWS = (2_500, 1, 1_777)     # R = 3,072: no count is aligned to it


def _unequal_segments(generator, datasource):
    """Three day segments of unequal row counts sharing dictionaries."""
    from druid_tpu.utils.intervals import Interval
    day = WEEK.width // 7
    return [generator.segment(
        n, Interval(WEEK.start + i * day, WEEK.start + (i + 1) * day),
        datasource=datasource) for i, n in enumerate(UNEQUAL_ROWS)]


@pytest.mark.parametrize("n_dev", [2, 4])
def test_groupby_unequal_row_counts_padded_k(generator, n_dev):
    """3 segments of unequal row counts on 2 and 4 devices (K pads to 4):
    each segment's validity is its own row count, a padding segment's is 0.
    Sharded == per-segment == a numpy reference, `==` (count, long sum and
    float max are exact merges)."""
    from tests.conftest import rows_as_frame
    segs = _unequal_segments(generator, f"unequal{n_dev}")
    q = GroupByQuery.of(
        f"unequal{n_dev}", [WEEK], [DefaultDimensionSpec("dimA")],
        [CountAggregator("rows"), LongSumAggregator("lsum", "metLong"),
         DoubleMaxAggregator("fmax", "metFloat")], granularity="all")
    plain, sharded = _run_both(q, segs, make_mesh(n_dev))
    key = lambda r: r["event"]["dimA"]
    assert sorted(sharded, key=key) == sorted(plain, key=key)
    want = {}
    for frame in map(rows_as_frame, segs):
        for d, m, f in zip(frame["dimA"], frame["metLong"], frame["metFloat"]):
            n, total, top = want.get(d, (0, 0, -np.inf))
            want[d] = (n + 1, total + int(m), max(top, float(f)))
    got = {r["event"]["dimA"]: (r["event"]["rows"], r["event"]["lsum"],
                                r["event"]["fmax"]) for r in sharded}
    assert got == want
    assert sum(n for n, _, _ in got.values()) == sum(UNEQUAL_ROWS)


def test_stack_validity_is_a_row_count_in_the_pool(generator, monkeypatch):
    """The stack that serves a mesh query holds `__valid` as one int32 a
    segment (padding segments 0), sharded over the segment axis, counted by
    the pool's cascade accounting at those 4 bytes; its program's decode
    counts as `prefix` and no run-table decode enters it."""
    from druid_tpu.data import cascade, devicepool
    from druid_tpu.parallel import distributed
    pool = devicepool.DeviceSegmentPool(budget_bytes=1 << 40)
    monkeypatch.setattr(devicepool, "_POOL", pool)
    built = []
    build_stack = distributed._build_stack

    def capture(*args, **kwargs):
        built.append(build_stack(*args, **kwargs))
        return built[-1]
    monkeypatch.setattr(distributed, "_build_stack", capture)
    distributed.clear_stack_cache()     # re-home the owner token on this pool
    distributed.clear_fn_cache()        # decode_stats counts at trace time
    segs = _unequal_segments(generator, "validity")
    q = TimeseriesQuery.of("validity", [WEEK], [CountAggregator("rows")],
                           granularity="all")
    mesh = make_mesh(4)
    before = cascade.decode_stats()
    try:
        with use_mesh(mesh):
            rows = QueryExecutor(segs).run(q)
        after = cascade.decode_stats()
        snap = pool.snapshot()
    finally:
        distributed.clear_stack_cache()
        distributed.clear_fn_cache()
    assert rows[0]["result"]["rows"] == sum(UNEQUAL_ROWS)
    (stack,) = built
    arrays, R, K = stack[0], stack[2], stack[3]
    valid = arrays["__valid"]
    assert isinstance(valid, cascade.PrefixMaskColumn)
    assert (R, K, valid.padded_rows) == (3_072, 4, 3_072)
    assert np.asarray(valid.n_rows).tolist() == list(UNEQUAL_ROWS) + [0]
    assert valid.n_rows.dtype == np.int32
    assert valid.n_rows.sharding.spec == \
        distributed.speclayout.layout_for(mesh).time0s()
    assert devicepool.entry_cascade_bytes({"__valid": valid}) == (4 * K, R)
    # the pool counts the whole entry the same way, and its decoded
    # equivalent is K * R of int32 time offsets and of validity bools (one
    # segment's share in each column, K - 1 in the LogicalBytes correction)
    assert (snap.cascade_bytes, snap.cascade_logical_bytes) == \
        devicepool.entry_cascade_bytes(stack)
    assert snap.stacked_logical_bytes == K * R * (4 + 1) + stack[1].nbytes
    assert after.get("prefix", 0) == before.get("prefix", 0) + 1
    assert after.get("rle", 0) == before.get("rle", 0)


def test_heterogeneous_column_presence(mesh):
    """A filter column existing in only SOME segments must not shortcut to a
    whole-query zero (const-false plan on segment 0 only)."""
    from druid_tpu.data.segment import SegmentBuilder
    from druid_tpu.utils.intervals import Interval

    iv = Interval.of("2026-01-01", "2026-01-02")
    b1 = SegmentBuilder("het", iv, partition=0)
    for i in range(100):
        b1.add_row(iv.start + i, {"common": f"c{i % 3}"}, {"m": i})
    b2 = SegmentBuilder("het", iv, partition=1)
    for i in range(100):
        b2.add_row(iv.start + i, {"common": f"c{i % 3}", "extra": f"e{i % 2}"},
                   {"m": i})
    segs = [b1.build(), b2.build()]
    q = TimeseriesQuery.of("het", [iv],
                           [CountAggregator("rows"),
                            LongSumAggregator("ms", "m")],
                           granularity="all",
                           filter=SelectorFilter("extra", "e0"))
    plain, sharded = _run_both(q, segs, mesh)
    assert plain[0]["result"]["rows"] == 50
    _assert_rows_equal(plain, sharded)


def test_differing_dictionaries_fall_back(mesh):
    """Equal-cardinality but different dictionaries must NOT fuse ids in the
    sharded path — values would decode through the wrong dictionary."""
    from druid_tpu.data.segment import SegmentBuilder
    from druid_tpu.utils.intervals import Interval

    iv = Interval.of("2026-01-01", "2026-01-02")
    b1 = SegmentBuilder("dicts", iv, partition=0)
    for i, v in enumerate(["apple", "berry"] * 4):
        b1.add_row(iv.start + i, {"d": v}, {"m": 1})
    b2 = SegmentBuilder("dicts", iv, partition=1)
    for i, v in enumerate(["cherry", "date"] * 4):
        b2.add_row(iv.start + i, {"d": v}, {"m": 1})
    segs = [b1.build(), b2.build()]
    q = GroupByQuery.of("dicts", [iv], [DefaultDimensionSpec("d")],
                        [CountAggregator("rows")], granularity="all")
    plain, sharded = _run_both(q, segs, mesh)
    key = lambda r: r["event"]["d"]
    plain, sharded = sorted(plain, key=key), sorted(sharded, key=key)
    assert [r["event"]["d"] for r in plain] == ["apple", "berry", "cherry",
                                               "date"]
    _assert_rows_equal(plain, sharded)


def test_executor_mesh_arg(segments, mesh):
    q = TimeseriesQuery.of("test", [WEEK], AGGS, granularity="hour")
    plain = QueryExecutor(segments).run(q)
    sharded = QueryExecutor(segments, mesh=mesh).run(q)
    _assert_rows_equal(plain, sharded)


def test_missing_metric_column_in_later_segment(mesh):
    """A metric present only in segment 0 must not crash the sharded path —
    it falls back and matches the plain path (missing aggregates as zero)."""
    from druid_tpu.data.segment import SegmentBuilder
    from druid_tpu.utils.intervals import Interval

    iv = Interval.of("2026-01-01", "2026-01-02")
    b1 = SegmentBuilder("mm", iv, partition=0)
    for i in range(50):
        b1.add_row(iv.start + i, {"d": "x"}, {"m": 1, "m2": i})
    b2 = SegmentBuilder("mm", iv, partition=1)
    for i in range(50):
        b2.add_row(iv.start + i, {"d": "x"}, {"m": 1})
    segs = [b1.build(), b2.build()]
    q = TimeseriesQuery.of("mm", [iv],
                           [CountAggregator("rows"),
                            LongSumAggregator("s", "m2")],
                           granularity="all")
    plain, sharded = _run_both(q, segs, mesh)
    assert plain[0]["result"] == {"rows": 100, "s": 1225}
    _assert_rows_equal(plain, sharded)


def test_rebuilt_segments_not_served_stale(generator, mesh):
    """Segments rebuilt with identical SegmentIds must not hit a stale
    stacked-HBM cache entry (cache is keyed by object identity)."""
    from tests.conftest import TEST_SCHEMA
    from druid_tpu.data.generator import DataGenerator
    from druid_tpu.utils.intervals import Interval

    iv = Interval.of("2026-01-01", "2026-01-05")
    q = TimeseriesQuery.of("test", [iv],
                           [LongSumAggregator("s", "metLong")],
                           granularity="all")
    for seed in (1, 2):
        gen = DataGenerator(TEST_SCHEMA, seed=seed)
        segs = gen.segments(4, 2_000, iv, datasource="test")
        plain, sharded = _run_both(q, segs, mesh)
        _assert_rows_equal(plain, sharded)


def test_two_cardinality_aggs_different_columns(segments, mesh):
    """Different-field HLL aggs must not collide in the jit program caches."""
    for field in ("dimA", "dimB"):
        q = TimeseriesQuery.of(
            "test", [WEEK], [CardinalityAggregator("c", [field])],
            granularity="all")
        plain, sharded = _run_both(q, segments, mesh)
        _assert_rows_equal(plain, sharded)
        # dimA card=10, dimB card=100: estimates must differ between fields
        if field == "dimA":
            assert 8 <= plain[0]["result"]["c"] <= 12
        else:
            assert 80 <= plain[0]["result"]["c"] <= 120
