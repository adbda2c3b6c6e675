"""One-dispatch megakernel (engine/megakernel.py): random-tree parity vs
the staged path and the numpy host-mask oracle (n_rows % 32 != 0
included), the exactly-ONE-cold-dispatch contract (obs/dispatch deltas),
the fused pallas projection variant (in-kernel word-mask unpack) with
donated-carry ticks (no per-tick pool growth, donated reuse bit-identical
to fresh buffers), perm-keyed bitmap cache entries for the projection
layout, filtered aggregators planning bitmap words, the unify-remap TTL
sweep, and the new obs metrics."""
import warnings

import numpy as np
import pytest

import druid_tpu.engine  # noqa: F401  (x64 on before jax numerics)
from druid_tpu.data.devicepool import device_pool
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine import engines, filters as filters_mod, grouping
from druid_tpu.engine import megakernel, pallas_agg
from druid_tpu.engine.executor import QueryExecutor
from druid_tpu.engine.filters import (DeviceBitmapNode, collect_bitmap_nodes,
                                      host_mask)
from druid_tpu.engine.kernels import FilteredKernel, make_kernel
from druid_tpu.obs import dispatch as dispatch_mod
from druid_tpu.query import filters as F
from druid_tpu.query.aggregators import (CountAggregator, FilteredAggregator,
                                         LongSumAggregator)
from druid_tpu.utils.intervals import Interval

IV = Interval.of("2026-05-01", "2026-05-05")

SCHEMA = (
    ColumnSpec("dLo", "string", cardinality=8),
    ColumnSpec("dMid", "string", cardinality=60),
    ColumnSpec("dHi", "string", cardinality=800),
    ColumnSpec("metLong", "long", low=0, high=1000),
    ColumnSpec("metDouble", "double", low=0.0, high=1.0),
)


@pytest.fixture(scope="module")
def mk_segments():
    # 3333 rows: n_rows % 32 != 0, so word-boundary rows are exercised
    return DataGenerator(SCHEMA, seed=21).segments(
        2, 3333, IV, datasource="mk")


@pytest.fixture(autouse=True)
def _mega_on():
    prev = megakernel.set_enabled(True)
    prev_b = filters_mod.set_device_bitmap_enabled(True)
    yield
    megakernel.set_enabled(prev)
    filters_mod.set_device_bitmap_enabled(prev_b)


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
        return F.InFilter(dim, tuple(vals[rng.integers(len(vals))]
                                     for _ in range(k)))
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


def _query(flt, aggs=None):
    q = {"queryType": "timeseries", "dataSource": "mk",
         "intervals": [str(IV)], "granularity": "all",
         "aggregations": aggs or [
             {"type": "count", "name": "n"},
             {"type": "longSum", "name": "s", "fieldName": "metLong"},
             {"type": "doubleSum", "name": "d", "fieldName": "metDouble"}]}
    if flt is not None:
        q["filter"] = flt.to_json()
    return q


def _oracle_count(flt, segs):
    return sum(int(host_mask(flt, s).sum()) for s in segs)


# ---------------------------------------------------------------------------
# parity: randomized filter trees × aggregators, fused vs staged vs oracle
# ---------------------------------------------------------------------------

def test_random_tree_fused_parity_gate(mk_segments):
    """The PR 9 discipline for the fused path: random trees evaluated
    through the megakernel (per-segment, batching off) must EXACTLY match
    the staged path — floats included — with counts pinned to the numpy
    host-mask oracle."""
    from druid_tpu.engine import batching
    rng = np.random.default_rng(5)
    ex = QueryExecutor(mk_segments)
    pb = batching.set_enabled(False)     # per-segment: the megaize path
    try:
        for i in range(12):
            flt = _rand_tree(rng, mk_segments[0], depth=3 if i % 2 else 2)
            q = _query(flt)
            device_pool().clear()        # cold: the one-shot fused shape
            fused = ex.run_json(q)
            prev = megakernel.set_enabled(False)
            try:
                device_pool().clear()
                staged = ex.run_json(q)
            finally:
                megakernel.set_enabled(prev)
            assert fused == staged, f"tree {i}: {flt}"
            got_n = fused[0]["result"]["n"] if fused else 0
            assert got_n == _oracle_count(flt, mk_segments), f"tree {i}"
    finally:
        batching.set_enabled(pb)


def test_cold_query_is_exactly_one_dispatch(mk_segments):
    """The tentpole contract: a cold bitmap-filtered query through the
    fused path costs exactly ONE device dispatch; the staged path pays the
    bitmap fill wave too."""
    seg = mk_segments[0]
    ex = QueryExecutor([seg])
    flt = F.NotFilter(F.SelectorFilter(
        "dLo", seg.dims["dLo"].dictionary.values[0]))
    q = _query(flt)
    device_pool().clear()
    d0 = dispatch_mod.count()
    fused = ex.run_json(q)
    assert dispatch_mod.count() - d0 == 1
    prev = megakernel.set_enabled(False)
    try:
        device_pool().clear()
        d0 = dispatch_mod.count()
        staged = ex.run_json(q)
        assert dispatch_mod.count() - d0 == 2     # fill wave + aggregation
    finally:
        megakernel.set_enabled(prev)
    assert fused == staged


def test_resident_combined_words_keep_cached_path(mk_segments):
    """Hot dashboards: when the combined words are ALREADY resident the
    planner keeps the cached bit-test path (one dispatch, no algebra) and
    counts it as a megakernel fallback, not a hit."""
    seg = DataGenerator(SCHEMA, seed=33).segments(
        1, 3333, IV, datasource="mk")[0]
    ex = QueryExecutor([seg])
    flt = F.SelectorFilter("dMid", seg.dims["dMid"].dictionary.values[1])
    q = _query(flt)
    prev = megakernel.set_enabled(False)
    try:
        warm = ex.run_json(q)            # builds + caches combined words
    finally:
        megakernel.set_enabled(prev)
    s0 = megakernel.stats().snapshot()
    d0 = dispatch_mod.count()
    again = ex.run_json(q)               # mega on, words resident
    s1 = megakernel.stats().snapshot()
    assert dispatch_mod.count() - d0 == 1
    assert s1["fallbacks"] == s0["fallbacks"] + 1
    assert s1["hits"] == s0["hits"]
    assert again == warm


# ---------------------------------------------------------------------------
# the fused pallas variant: in-kernel word mask + donated carries
# ---------------------------------------------------------------------------

def _proj_setup(monkeypatch):
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)
    schema = (
        ColumnSpec("dimA", "string", cardinality=30),
        ColumnSpec("dimB", "string", cardinality=200, distribution="zipf"),
        ColumnSpec("metLong", "long", low=-500, high=9000),
        ColumnSpec("metFloat", "float", distribution="normal", mean=10.0,
                   std=400.0),
    )
    segs = DataGenerator(schema, seed=77).segments(2, 20000, IV,
                                                   datasource="pj")
    vals = list(segs[0].dims["dimA"].dictionary.values)
    q = {"queryType": "groupBy", "dataSource": "pj",
         "intervals": [str(IV)], "granularity": "all",
         "dimensions": ["dimA", "dimB"],
         "aggregations": [
             {"type": "count", "name": "rows"},
             {"type": "longSum", "name": "lsum", "fieldName": "metLong"},
             {"type": "floatSum", "name": "fsum", "fieldName": "metFloat"},
             {"type": "longMin", "name": "lmin", "fieldName": "metLong"}],
         "filter": {"type": "in", "dimension": "dimA", "values": vals[:20]}}
    return segs, q


def test_mega_pallas_strategy_selected_and_bit_identical(monkeypatch,
                                                         mk_segments):
    """On the sorted-projection path the fused variant upgrades "pallas" to
    "megakernel" (mask rides into the kernel as words) and stays
    bit-identical to the staged pallas kernel — floats included, since the
    block/accumulation order is the same."""
    segs, q = _proj_setup(monkeypatch)
    ex = QueryExecutor(segs)
    seen = []
    orig = grouping.fuse_filter_update

    def spy(*a, **k):
        seen.append(k.get("strategy"))
        return orig(*a, **k)
    monkeypatch.setattr(grouping, "fuse_filter_update", spy)
    fused = ex.run_json(q)
    monkeypatch.setattr(grouping, "fuse_filter_update", orig)
    assert "megakernel" in seen, seen
    prev = megakernel.set_enabled(False)
    try:
        staged = ex.run_json(q)          # staged pallas kernel
    finally:
        megakernel.set_enabled(prev)
    assert fused == staged               # exact, floats included


def test_mega_carry_ticks_no_pool_growth_and_parity(monkeypatch):
    """Repeated (scheduler-tick-style) execution cycles ONE carry entry
    through the pool — no per-tick HBM growth, asserted under the leak
    witness — and donated-carry reuse is bit-identical to fresh buffers
    (the kernel re-inits at grid step 0). The carry handoff follows
    donation support (off on CPU), so the test forces it on."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from tools.druidlint.leakwitness import LeakWitness
    segs, q = _proj_setup(monkeypatch)
    ex = QueryExecutor(segs)
    prev_c = megakernel.set_force_carry(True)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            first = ex.run_json(q)       # cold: fresh zero carries
            with LeakWitness(
                    str(Path(__file__).resolve().parent.parent)) as w:
                base = w.snapshot()      # post-first-tick resource state
                ticks = [ex.run_json(q) for _ in range(3)]
                residue = w.leaks(base, grace_s=2.0)
        assert all(t == first for t in ticks)     # carried ≡ fresh, bitwise
        assert not residue, residue               # zero per-tick growth
        # the carry entries really exist (one per (segment, program))
        carry_keys = [k for s in segs
                      for k in s._pool._entries
                      if "megacarry" in k]
        assert carry_keys
        device_pool().clear()
        again = ex.run_json(q)                    # cold again: same results
        assert again == first
    finally:
        megakernel.set_force_carry(prev_c)
    # CPU default: no donation support ⇒ carryless execution parks NOTHING
    # in the budgeted pool (the grids would only evict useful entries)
    device_pool().clear()
    ex.run_json(q)
    leftover = [k for s in segs
                for k in s._pool._entries
                if "megacarry" in k]
    assert not leftover


def test_mega_carry_failed_dispatch_discards_ownership(monkeypatch):
    """A build failure AFTER the carry take (the Mosaic-compile window)
    must DISCARD the popped grids, not re-park them — donation may have
    invalidated the buffers mid-flight — and leave the pool's byte
    accounting truthful: resident bytes must equal the entries actually
    held, with no megacarry entry surviving the failure (donorguard
    take-without-repark, enforced on grouping's exception path)."""
    import collections
    segs, q = _proj_setup(monkeypatch)
    ex = QueryExecutor(segs)
    prev_c = megakernel.set_force_carry(True)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            first = ex.run_json(q)          # parks one carry per segment
            assert [k for s in segs for k in s._pool._entries
                    if "megacarry" in k]
            discards = []
            real_discard = megakernel.discard_carries

            def spy_discard(carries):
                discards.append(len(carries))
                return real_discard(carries)

            monkeypatch.setattr(megakernel, "discard_carries", spy_discard)
            # fresh program cache + a builder whose megakernel product
            # raises: the dispatch fails between the take and the re-park
            monkeypatch.setattr(grouping, "_JIT_CACHE",
                                collections.OrderedDict())
            real_build = grouping._build_device_fn

            def broken_build(spec, *a, **k):
                fn = real_build(spec, *a, **k)
                if spec.strategy != "megakernel":
                    return fn

                class Boom:
                    # the BUILD fails (the one failure the latch catches)
                    def lower(self, *a, **k):
                        raise RuntimeError("synthetic Mosaic failure")

                return Boom()

            monkeypatch.setattr(grouping, "_build_device_fn", broken_build)
            fallback = ex.run_json(q)       # fails mid-carry, falls back
        # XLA fallback stays correct (floats to tolerance: the windowed
        # path accumulates in a different block order than the kernel)
        assert len(fallback) == len(first)
        for got, want in zip(fallback, first):
            assert got["event"].keys() == want["event"].keys()
            for name, v in got["event"].items():
                if isinstance(v, float):
                    assert v == pytest.approx(want["event"][name],
                                              rel=1e-5)
                else:
                    assert v == want["event"][name]
        assert discards                     # popped grids were discharged
        pool = device_pool()
        with pool._lock:
            leftover = [k for k in pool._entries if "megacarry" in k]
            drift = pool._resident - sum(v[1]
                                         for v in pool._entries.values())
        assert not leftover                 # discarded, NOT re-parked
        assert drift == 0                   # books match held entries
    finally:
        megakernel.set_force_carry(prev_c)
        pallas_agg._BROKEN = None           # un-latch for later tests
        device_pool().clear()


def test_mega_pallas_packed_columns_parity(monkeypatch, mk_segments):
    """Packed value columns ride the fused kernel as words (the PR 9
    in-kernel unpack) — parity against decoded staging through the same
    fused path."""
    from druid_tpu.data import packed
    segs, q = _proj_setup(monkeypatch)
    ex = QueryExecutor(segs)
    prev = packed.set_enabled(True)
    try:
        device_pool().clear()
        with_packed = ex.run_json(q)
    finally:
        packed.set_enabled(prev)
    prev = packed.set_enabled(False)
    try:
        device_pool().clear()
        decoded = ex.run_json(q)
    finally:
        packed.set_enabled(prev)
    assert with_packed == decoded


# ---------------------------------------------------------------------------
# perm-keyed bitmap cache entries (projection layout)
# ---------------------------------------------------------------------------

def test_projection_bitmap_words_perm_keyed(monkeypatch):
    """The projection path stages PERMUTED bitmap words under its own
    permutation digest instead of re-planning onto the column path: the
    planned tree keeps its bitmap nodes, results stay exact, and the
    second run hits the perm-keyed entries."""
    monkeypatch.setenv("DRUID_TPU_PALLAS", "0")   # projection → windowed
    segs, q = _proj_setup(monkeypatch)
    prev = megakernel.set_enabled(False)  # the staged (resident-words) path
    try:
        ex = QueryExecutor(segs)
        device_pool().clear()
        got = ex.run_json(q)
        s0 = filters_mod.filter_bitmap_stats().snapshot()
        again = ex.run_json(q)
        s1 = filters_mod.filter_bitmap_stats().snapshot()
        assert again == got
        assert s1["hits"] > s0["hits"]           # perm-keyed entries hit
        assert s1["misses"] == s0["misses"]
        # parity against the un-projected mixed path
        monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 1 << 60)
        want = ex.run_json(q)
        assert {r["event"]["dimA"] + "|" + r["event"]["dimB"]:
                (r["event"]["rows"], r["event"]["lsum"]) for r in got} == \
               {r["event"]["dimA"] + "|" + r["event"]["dimB"]:
                (r["event"]["rows"], r["event"]["lsum"]) for r in want}
    finally:
        megakernel.set_enabled(prev)


# ---------------------------------------------------------------------------
# filtered aggregators plan bitmap words
# ---------------------------------------------------------------------------

def test_filtered_agg_plans_bitmap_words(mk_segments):
    seg = mk_segments[0]
    spec = FilteredAggregator(
        "fsum", delegate=LongSumAggregator("fsum", "metLong"),
        filter=F.SelectorFilter("dHi", seg.dims["dHi"].dictionary.values[2]))
    k = make_kernel(spec, seg)
    assert isinstance(k, FilteredKernel)
    assert collect_bitmap_nodes(k.filter_node), \
        "filtered aggregator's filter must compile to bitmap words"
    # the filter-only dim stops staging: the kernel's planned needs carry
    # no filter columns at all
    assert k.required_device_columns() == {"metLong"}


def test_filtered_agg_parity_fused_vs_column_path(mk_segments):
    ex = QueryExecutor(mk_segments)
    dHi_vals = mk_segments[0].dims["dHi"].dictionary.values
    aggs = [{"type": "count", "name": "n"},
            {"type": "filtered", "name": "fs",
             "aggregator": {"type": "longSum", "name": "fs",
                            "fieldName": "metLong"},
             "filter": {"type": "in", "dimension": "dHi",
                        "values": list(dHi_vals[:40])}}]
    q = _query(None, aggs=aggs)
    device_pool().clear()
    fused = ex.run_json(q)
    prev = filters_mod.set_device_bitmap_enabled(False)
    try:
        device_pool().clear()
        column = ex.run_json(q)          # the old decoded-column path
    finally:
        filters_mod.set_device_bitmap_enabled(prev)
    assert fused == column
    # oracle on the filtered sum
    want = 0
    for s in mk_segments:
        m = host_mask(F.InFilter("dHi", tuple(dHi_vals[:40])), s)
        want += int(s.metrics["metLong"].values[m].sum())
    assert fused[0]["result"]["fs"] == want


def test_filtered_agg_slots_do_not_collide_with_query_filter(mk_segments):
    """The query filter AND a filtered aggregator both carry bitmap
    subtrees: global slot assignment keeps their staged word arrays
    distinct, and results match the all-column path exactly."""
    ex = QueryExecutor(mk_segments)
    dLo_vals = mk_segments[0].dims["dLo"].dictionary.values
    dMid_vals = mk_segments[0].dims["dMid"].dictionary.values
    aggs = [{"type": "count", "name": "n"},
            {"type": "filtered", "name": "fs",
             "aggregator": {"type": "longSum", "name": "fs",
                            "fieldName": "metLong"},
             "filter": {"type": "selector", "dimension": "dMid",
                        "value": dMid_vals[3]}}]
    q = _query(F.NotFilter(F.SelectorFilter("dLo", dLo_vals[1])), aggs=aggs)
    device_pool().clear()
    fused = ex.run_json(q)
    prev_b = filters_mod.set_device_bitmap_enabled(False)
    prev_m = megakernel.set_enabled(False)
    try:
        device_pool().clear()
        column = ex.run_json(q)
    finally:
        filters_mod.set_device_bitmap_enabled(prev_b)
        megakernel.set_enabled(prev_m)
    assert fused == column


# ---------------------------------------------------------------------------
# unify_query_dims TTL sweep (carried-over ROADMAP rider)
# ---------------------------------------------------------------------------

def test_unidim_remap_ttl_sweeps_stale_slots():
    # few rows over a wide value range: the two segments' query-time
    # numeric dictionaries differ, so unify_query_dims really unions
    schema = (ColumnSpec("dimA", "string", cardinality=4),
              ColumnSpec("metLong", "long", low=0, high=100_000))
    segs = DataGenerator(schema, seed=3).segments(2, 64, IV,
                                                  datasource="un")
    from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery
    q = GroupByQuery.of("un", [IV], [DefaultDimensionSpec("metLong")],
                        [CountAggregator("n")], granularity="all")
    kds, vals = engines._keydims_for_query(q, segs)
    slots = [s._aux_cache[k] for s in segs
             for k in s._aux_cache if k[0] == "unidim"]
    assert slots and all(len(sl) == 1 for sl in slots)
    prev = engines.set_unidim_ttl(1e-9)
    try:
        import time as _time
        _time.sleep(0.01)
        # any subsequent unify pass sweeps stale slots, whoever owns them
        other = DataGenerator(schema, seed=9).segments(2, 64, IV,
                                                       datasource="un2")
        engines._keydims_for_query(q, other)
        assert all(len(sl) == 0 for sl in slots), "stale remaps must clear"
    finally:
        engines.set_unidim_ttl(prev)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_mega_and_dispatch_metrics_declared_and_emitting(mk_segments):
    from druid_tpu.obs import catalog
    from druid_tpu.obs.dispatch import DispatchMonitor

    class Rec:
        def __init__(self):
            self.seen = {}

        def metric(self, name, value, **dims):
            self.seen[name] = value

    ex = QueryExecutor([mk_segments[0]])
    mega_mon = megakernel.MegakernelMonitor()
    disp_mon = DispatchMonitor()
    device_pool().clear()
    ex.run_json(_query(F.SelectorFilter(
        "dLo", mk_segments[0].dims["dLo"].dictionary.values[4])))
    rec = Rec()
    mega_mon.do_monitor(rec)
    disp_mon.do_monitor(rec)
    assert not catalog.validate_emitted(rec.seen)
    assert set(rec.seen) == {"query/megakernel/hits",
                             "query/megakernel/fallbacks",
                             "query/megakernel/donatedBytes",
                             "query/dispatch/count"}
    assert rec.seen["query/dispatch/count"] >= 1
    assert rec.seen["query/megakernel/hits"] >= 1


def test_disabled_megakernel_records_fallbacks(mk_segments):
    seg = mk_segments[0]
    ex = QueryExecutor([seg])
    q = _query(F.SelectorFilter("dLo",
                                seg.dims["dLo"].dictionary.values[5]))
    prev = megakernel.set_enabled(False)
    try:
        s0 = megakernel.stats().snapshot()
        device_pool().clear()
        ex.run_json(q)
        s1 = megakernel.stats().snapshot()
    finally:
        megakernel.set_enabled(prev)
    assert s1["fallbacks"] > s0["fallbacks"]
    assert s1["hits"] == s0["hits"]
