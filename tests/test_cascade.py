"""Cascaded encodings + code-domain aggregation (data/cascade.py).

The acceptance bar of the cascade PR: cascade-encoded execution is
bit-identical (floats included) to the decoded oracle over groupBy /
timeseries / topN / virtual-column / batched / megakernel paths; the
code-domain paths perform ZERO unpack (trace-time decode counter) and
stage no row-width column; and the fixed-budget residency test holds ≥3x
more segments than packed-only staging on an RLE-friendly shape."""
import numpy as np
import pytest

import druid_tpu.engine  # noqa: F401  (x64 on before jax numerics)
from druid_tpu.data import cascade, devicepool, packed
from druid_tpu.data.devicepool import DeviceSegmentPool, entry_cascade_bytes
from druid_tpu.data.segment import SegmentBuilder
from druid_tpu.engine.executor import QueryExecutor
from druid_tpu.native import lz4block
from druid_tpu.utils.intervals import Interval

IV = Interval.of("2026-06-01", "2026-06-02")


@pytest.fixture
def fresh_pool(monkeypatch):
    pool = DeviceSegmentPool(budget_bytes=1 << 40)
    monkeypatch.setattr(devicepool, "_POOL", pool)
    return pool


def rollup_segments(n=3, rows=2048, card=8, n_dims=2, n_mets=2,
                    float_col=False, seed=0):
    """Rollup-shaped segments: dimension-sorted rows, near-constant time,
    a constant `cnt` metric, run-aligned small-range `mN` metrics, a
    row-random `noise` metric, and optionally a compressible float."""
    rng = np.random.default_rng(seed)
    reps = -(-rows // card)
    segs = []
    for si in range(n):
        b = SegmentBuilder("casc", IV, version="v0", partition=si)
        dims = {f"d{i}": np.repeat(
            [f"v{i}_{j:03d}" for j in range(card)], reps)[:rows].tolist()
            for i in range(n_dims)}
        mets = {"cnt": np.ones(rows, dtype=np.int64),
                "noise": rng.integers(0, 500, rows).astype(np.int64)}
        for i in range(n_mets):
            mets[f"m{i}"] = np.repeat(
                (np.arange(card) * (7 + i)) % 13, reps)[:rows].astype(
                    np.int64)
        if float_col:
            mets["f"] = (np.arange(rows) % 16).astype(np.float32)
        time = IV.start + (np.arange(rows, dtype=np.int64) // 64)
        b.add_columns(time, dims, mets)
        segs.append(b.build())
    return segs


def _run_modes(query_json, segments):
    """(decoded-oracle results, cascade results) — the oracle runs with
    BOTH cascade and packing off (fully decoded staging)."""
    ex = QueryExecutor(segments)
    pc, pk = cascade.set_enabled(False), packed.set_enabled(False)
    try:
        oracle = ex.run_json(query_json)
    finally:
        cascade.set_enabled(pc)
        packed.set_enabled(pk)
    return oracle, ex.run_json(query_json)


# ---------------------------------------------------------------------------
# encoder unit level
# ---------------------------------------------------------------------------

def test_rle_roundtrip_device():
    import jax
    v = np.repeat(np.arange(11, dtype=np.int32), 97)[:1000]
    values, ends = cascade.rle_encode(v)
    assert values.shape == ends.shape and ends[-1] == 1000
    rpad = cascade.pad_pow2(values.shape[0])
    pv = np.zeros(rpad, np.int32)
    pv[: values.shape[0]] = values
    pe = np.full(rpad, 1000, np.int32)
    pe[: ends.shape[0]] = ends
    rc = cascade.RleColumn(jax.device_put(pv), jax.device_put(pe),
                           1000, 1024)
    out = np.asarray(jax.jit(cascade.rle_decode_device)(rc))
    np.testing.assert_array_equal(out[:1000], v)
    np.testing.assert_array_equal(out[1000:], 0)   # staging pad fill


# a stacked segment's validity: rows [0, n_rows) of R, as its row count
PREFIX_R = 3 * 1024
PREFIX_COUNTS = [0, 1, PREFIX_R - 1, PREFIX_R, 1777]


def _dense_valid(counts, K, R):
    """The dense [K, R] mask a stack held before validity was a row count."""
    valid = np.zeros((K, R), dtype=bool)
    for i, n in enumerate(counts):
        valid[i, :n] = True
    return valid


def _decode_valid(col):
    return cascade.split_resident({"__valid": col})[1]["__valid"]


def _prefix(n_rows):
    return cascade.PrefixMaskColumn(np.asarray(n_rows, np.int32), PREFIX_R)


def _stacked(cols, K=4):
    """Per-segment columns stacked as `_build_stack` stacks them: padding
    segments are `_stack_tree`'s zeroed copies."""
    from druid_tpu.parallel.distributed import _stack_tree
    return _stack_tree(cols, K)


@pytest.mark.parametrize("form", ["alone", "stacked"])
@pytest.mark.parametrize("n_rows", PREFIX_COUNTS)
def test_prefix_mask_decode_equals_the_dense_mask(n_rows, form):
    """`iota < n_rows` through split_resident, the one decode entry point:
    alone, and stacked [K] under vmap with the all-invalid padding segments
    `_stack_tree` adds (row count 0)."""
    import jax
    if form == "alone":
        out = jax.jit(_decode_valid)(jax.device_put(_prefix(n_rows)))
        want = _dense_valid([n_rows], 1, PREFIX_R)[0]
    else:
        counts = [n_rows, 5, PREFIX_R]
        stacked = _stacked([_prefix(n) for n in counts])
        assert stacked.n_rows.shape == (4,) and stacked.n_rows.dtype == np.int32
        out = jax.jit(jax.vmap(_decode_valid))(jax.device_put(stacked))
        want = _dense_valid(counts, 4, PREFIX_R)
    out = np.asarray(out)
    assert out.dtype == np.bool_ and out.shape == want.shape
    np.testing.assert_array_equal(out, want)


def _rle_valid_table(n_rows, R):
    """The 8-run validity table the stack held before (the control)."""
    vals = np.zeros(8, np.int32)
    vals[0] = 1 if n_rows else 0
    return cascade.RleColumn(vals, np.full(8, n_rows, np.int32),
                             np.asarray(n_rows, np.int32), R, "bool")


@pytest.mark.parametrize("kind,form", [("prefix", "alone"),
                                       ("prefix", "stacked"),
                                       ("rle", "stacked")])
def test_validity_decode_compiles_to_no_search(kind, form):
    """The structural guard: the compiled decode of a row-count validity
    holds neither a `while` nor a `gather`; the run table's (the control,
    what the mesh program spent 58% of its time in) holds one."""
    import jax
    one = _prefix(1777) if kind == "prefix" \
        else _rle_valid_table(1777, PREFIX_R)
    if form == "alone":
        fn, arg = _decode_valid, one
    else:
        fn, arg = jax.vmap(_decode_valid), _stacked([one] * 3)
    text = jax.jit(fn).lower(arg).compile().as_text()
    searched = "while" in text or "gather" in text
    assert searched == (kind == "rle"), text[:2000]


def test_prefix_mask_accounting_and_decode_kind():
    """4 bytes a segment against R bools, counted by the pool's cascade
    walker; the decode counts under a kind of its own, never `rle`."""
    import jax
    K = 4
    stacked = jax.device_put(
        _stacked([_prefix(n) for n in (7, 1777, PREFIX_R)], K))
    assert stacked.cascade_kind == "prefix"
    assert (stacked.nbytes, stacked.logical_nbytes) == (4 * K, PREFIX_R)
    assert entry_cascade_bytes({"__valid": stacked, "x": np.zeros(16)}) \
        == (4 * K, PREFIX_R)
    assert devicepool.entry_bytes({"__valid": stacked}) == 4 * K
    before = cascade.decode_stats()
    jax.jit(jax.vmap(_decode_valid)).lower(stacked)
    after = cascade.decode_stats()
    assert after.get("prefix", 0) == before.get("prefix", 0) + 1
    assert after.get("rle", 0) == before.get("rle", 0)


def test_delta_roundtrip_device():
    import jax
    v = np.cumsum(np.random.default_rng(1).integers(
        0, 13, 2048)).astype(np.int32)
    padded = np.zeros(4096, np.int32)
    padded[:2048] = v
    w = packed.width_for(12, 0)
    words, first = cascade.delta_encode(padded, 2048, w)
    dc = cascade.DeltaColumn(jax.device_put(words), jax.device_put(first),
                             w, 4096)
    out = np.asarray(jax.jit(cascade.delta_decode_device)(dc))
    np.testing.assert_array_equal(out[:2048], v)
    np.testing.assert_array_equal(out[2048:], v[-1])  # pad repeats last


@pytest.mark.parametrize("codec", ["python", "best"])
def test_lz4_block_roundtrip(codec):
    rng = np.random.default_rng(2)
    for data in (b"", b"abc", b"a" * 5000,
                 bytes(rng.integers(0, 4, 400).astype(np.uint8)),
                 (np.arange(999, dtype=np.float32) % 7).tobytes(),
                 bytes(rng.integers(0, 256, 256).astype(np.uint8))):
        comp = lz4block.py_compress(data) if codec == "python" \
            else lz4block.compress(data)
        assert lz4block.py_decompress(comp, len(data)) == data
        lits, ll, ml, off = lz4block.tokenize(comp)
        assert int(ll.sum()) + int(ml.sum()) == len(data)
        assert int(ll.sum()) == lits.shape[0]


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_lz4_device_decode_bit_identical(dtype):
    import jax
    vals = ((np.arange(3000) % 21) * 0.5).astype(dtype)
    comp = lz4block.compress(vals.tobytes())
    lits, ll, ml, off = lz4block.tokenize(comp)
    tp = cascade.pad_pow2(ll.shape[0])
    lp = cascade.pad_pow2(max(lits.shape[0], 1))

    def padto(a, n, dt):
        out = np.zeros(n, dtype=dt)
        out[: a.shape[0]] = a
        return jax.device_put(out)
    col = cascade.Lz4Column(padto(lits, lp, np.uint8),
                            padto(ll, tp, np.int32),
                            padto(ml, tp, np.int32),
                            padto(off, tp, np.int32),
                            3000, 4096, np.dtype(dtype).name)
    out = np.asarray(jax.jit(cascade.lz4_decode_device)(col))
    np.testing.assert_array_equal(out[:3000], vals)   # exact, bit-level
    np.testing.assert_array_equal(out[3000:], 0)


def test_plan_is_pure_and_claims_are_exclusive(fresh_pool):
    seg = rollup_segments(1, rows=2048, float_col=True)[0]
    cols = ["d0", "d1", "cnt", "m0", "noise", "f"]
    cascades, packs = cascade.plan_pair(seg, cols)
    by_name = {e[0]: e for e in cascades}
    assert by_name["d0"][1] == "rle"            # sorted dim: RLE
    assert by_name["cnt"][1] == "rle"           # constant metric: 1 run
    assert by_name["m0"][1] == "rle"
    assert by_name["__time_offset"][1] in ("delta", "for")
    assert by_name["f"][1] == "lz4"             # compressible float
    assert "noise" not in by_name               # row-random: no runs
    packed_names = {p[0] for p in packs}
    assert packed_names.isdisjoint(by_name)     # one encoding per column
    assert "noise" in packed_names              # small range still packs
    # purity: identical stats -> identical descriptors, every call
    assert cascade.plan_pair(seg, cols) == (cascades, packs)
    # permuted staging never cascades (a row permutation destroys runs)
    assert cascade.plan_columns(seg, cols, permuted=True) == ()
    prev = cascade.set_enabled(False)
    try:
        assert cascade.plan_columns(seg, cols) == ()
    finally:
        cascade.set_enabled(prev)


def test_wide_time_spread_does_not_cascade(fresh_pool):
    b = SegmentBuilder("casc", IV)
    rng = np.random.default_rng(5)
    t = np.sort(rng.integers(IV.start, IV.end, 512))
    b.add_columns(t, {"d": [f"x{i}" for i in range(512)]},
                  {"m": rng.integers(0, 100, 512).astype(np.int64)})
    seg = b.build()
    assert cascade.plan_column(seg, "__time_offset") is None


# ---------------------------------------------------------------------------
# engine parity (the acceptance bar: exact equality, floats included)
# ---------------------------------------------------------------------------

GROUPBY = {
    "queryType": "groupBy", "dataSource": "casc", "intervals": [str(IV)],
    "granularity": "all", "dimensions": ["d0"],
    "aggregations": [
        {"type": "count", "name": "n"},
        {"type": "longSum", "name": "c", "fieldName": "cnt"},
        {"type": "longSum", "name": "s", "fieldName": "m0"},
        {"type": "longMin", "name": "lm", "fieldName": "noise"},
    ],
    "filter": {"type": "in", "dimension": "d1",
               "values": [f"v1_{j:03d}" for j in range(0, 8, 2)]},
}

#: the fully run-aligned variant: every referenced column (group dim,
#: filter dim, summed/min'd metrics) is constant within the shared run
#: partition, so granularity-"all" executions go code-domain
RUN_GROUPBY = dict(GROUPBY)
RUN_GROUPBY["aggregations"] = [
    {"type": "count", "name": "n"},
    {"type": "longSum", "name": "c", "fieldName": "cnt"},
    {"type": "longSum", "name": "s", "fieldName": "m0"},
    {"type": "longMin", "name": "lm", "fieldName": "m1"},
]


@pytest.mark.parametrize("granularity", ["all", "hour"],
                         ids=["all", "hour"])
def test_groupby_parity(fresh_pool, granularity):
    # GROUPBY aggregates the row-random `noise` column, so even the
    # granularity-all variant stays a ROW program (the joint run
    # partition is too fine) — the code-domain variant is RUN_GROUPBY
    q = dict(GROUPBY, granularity=granularity)
    oracle, casc = _run_modes(q, rollup_segments())
    assert oracle == casc


def test_groupby_run_domain_parity(fresh_pool):
    oracle, casc = _run_modes(RUN_GROUPBY, rollup_segments())
    assert oracle == casc


def test_timeseries_and_topn_parity(fresh_pool):
    segs = rollup_segments(float_col=True)
    ts = {"queryType": "timeseries", "dataSource": "casc",
          "intervals": [str(IV)], "granularity": "hour",
          "aggregations": [
              {"type": "count", "name": "n"},
              {"type": "longSum", "name": "s", "fieldName": "m0"},
              {"type": "doubleSum", "name": "fs", "fieldName": "f"},
          ]}
    oracle, casc = _run_modes(ts, segs)
    assert oracle == casc
    topn = {"queryType": "topN", "dataSource": "casc",
            "intervals": [str(IV)], "granularity": "all",
            "dimension": "d0", "metric": "s", "threshold": 5,
            "aggregations": [
                {"type": "count", "name": "n"},
                {"type": "longSum", "name": "s", "fieldName": "m1"}]}
    oracle, casc = _run_modes(topn, segs)
    assert oracle == casc


def test_virtual_column_parity_reads_cascade_input(fresh_pool):
    q = dict(GROUPBY)
    q["virtualColumns"] = [{"type": "expression", "name": "v",
                            "expression": "m0 * 2 + 1",
                            "outputType": "long"}]
    q["aggregations"] = GROUPBY["aggregations"] + [
        {"type": "longSum", "name": "vs", "fieldName": "v"}]
    oracle, casc = _run_modes(q, rollup_segments())
    assert oracle == casc


def test_batched_path_parity_and_shared_buckets(fresh_pool):
    from druid_tpu.engine import batching
    from druid_tpu.query.aggregators import (CountAggregator,
                                             LongSumAggregator)
    from druid_tpu.utils.granularity import Granularity

    segs = rollup_segments(4, rows=1500)
    # pin the ROW program: the near-constant time column makes even the
    # hour query run-domain eligible since the uniform-granularity rung —
    # this test measures the BATCHED staging path
    prev_rd = cascade.set_run_domain_enabled(False)
    try:
        q = dict(GROUPBY, granularity="hour")   # row program: batchable
        oracle, casc = _run_modes(q, segs)
        assert oracle == casc
        # chunk-mates agree on the cascade descriptor: same-stats segments
        # share one shape bucket, and the descriptor is present in it
        aggs = [CountAggregator("n"), LongSumAggregator("s", "m0")]
        plans = [batching._plan_for(s, [], i, [IV],
                                    Granularity.of("hour"),
                                    aggs, None, [])
                 for i, s in enumerate(segs)]
        assert all(p.eligible for p in plans)
        assert len({p.cascades for p in plans}) == 1
        assert plans[0].cascades
        assert len({p.digest for p in plans}) == 1
    finally:
        cascade.set_run_domain_enabled(prev_rd)


def test_megakernel_path_parity(fresh_pool):
    """Single-segment cold query with a bitmap-eligible filter: the
    megakernel (one-dispatch) path over cascade-staged columns."""
    from druid_tpu.engine import megakernel
    assert megakernel.enabled()
    segs = rollup_segments(1, rows=4096)
    q = dict(GROUPBY, granularity="hour",
             filter={"type": "or", "fields": [
                 {"type": "selector", "dimension": "d1",
                  "value": "v1_001"},
                 {"type": "selector", "dimension": "d1",
                  "value": "v1_005"}]})
    oracle, casc = _run_modes(q, segs)
    assert oracle == casc


def test_staged_bitmap_runs_leaf_parity(fresh_pool):
    """The staged (fill-wave) device-bitmap path with the RLE-run-aware
    leaf representation: a sorted dim's leaf ships as a run table and the
    expanded words match the row-built oracle bit-for-bit."""
    from druid_tpu.engine import filters as filters_mod
    from druid_tpu.engine import megakernel
    seg = rollup_segments(1, rows=4096)[0]
    lut = np.zeros(seg.dims["d1"].cardinality, dtype=bool)
    lut[1::2] = True
    payload = filters_mod._run_leaf_payload(seg, "d1", lut, 4096)
    assert payload is not None and payload.shape[1] == 2
    prev = megakernel.set_enabled(False)   # pin the staged fill path
    try:
        oracle, casc = _run_modes(
            dict(GROUPBY, granularity="hour"), [seg])
    finally:
        megakernel.set_enabled(prev)
    assert oracle == casc


# ---------------------------------------------------------------------------
# code-domain: zero unpack, zero row-width staging
# ---------------------------------------------------------------------------

def test_run_domain_zero_unpack_and_parity(fresh_pool):
    segs = rollup_segments(2, rows=4096)
    oracle, _ = _run_modes(RUN_GROUPBY, segs)  # oracle decodes; then reset
    fresh_pool.clear()
    cascade.reset_decode_stats()
    h0 = cascade.code_domain_stats().snapshot()
    from druid_tpu.obs import dispatch as dispatch_mod
    d0 = dispatch_mod.stats().snapshot().get("runDomain", 0)
    got = QueryExecutor(segs).run_json(RUN_GROUPBY)
    assert got == oracle
    # ZERO unpack: no decode of any kind entered any program
    assert cascade.decode_stats() == {}
    h1 = cascade.code_domain_stats().snapshot()
    assert h1["hits"] - h0["hits"] == len(segs)
    assert h1["rows"] - h0["rows"] == sum(s.n_rows for s in segs)
    assert dispatch_mod.stats().snapshot()["runDomain"] - d0 == len(segs)
    # zero row-width staging: every pool entry is run-table sized
    assert fresh_pool.snapshot().resident_bytes < 4096 * 4


def test_const_sum_column_never_stages(fresh_pool):
    """sum-over-dictionary-constant: the constant column contributes NO
    staged column even on the row program path (required_device_columns
    = {}), and the sum is exact."""
    segs = rollup_segments(2, rows=2048)
    q = {"queryType": "timeseries", "dataSource": "casc",
         "intervals": [str(IV)], "granularity": "hour",
         "aggregations": [{"type": "count", "name": "n"},
                          {"type": "longSum", "name": "c",
                           "fieldName": "cnt"}]}
    oracle, casc_rows = _run_modes(q, segs)
    assert oracle == casc_rows
    for row in casc_rows:
        assert row["result"]["c"] == row["result"]["n"]  # cnt ≡ 1
    from druid_tpu.engine.kernels import SumKernel, make_kernel
    from druid_tpu.query.aggregators import LongSumAggregator
    k = make_kernel(LongSumAggregator("c", "cnt"), segs[0])
    assert isinstance(k, SumKernel) and k.const_value == 1
    assert k.required_device_columns() == set()
    prev = cascade.set_enabled(False)
    try:
        k2 = make_kernel(LongSumAggregator("c", "cnt"), segs[0])
    finally:
        cascade.set_enabled(prev)
    assert k2.const_value is None              # opt-out restores old world


def test_run_domain_respects_optout(fresh_pool):
    segs = rollup_segments(2, rows=2048)
    prev = cascade.set_enabled(False)
    try:
        h0 = cascade.code_domain_stats().snapshot()["hits"]
        QueryExecutor(segs).run_json(RUN_GROUPBY)
        assert cascade.code_domain_stats().snapshot()["hits"] == h0
    finally:
        cascade.set_enabled(prev)


# ---------------------------------------------------------------------------
# residency: ≥3x more segments than packed-only at a fixed budget
# ---------------------------------------------------------------------------

def test_pool_holds_3x_more_segments_than_packed_only(fresh_pool):
    """The acceptance bar on the RLE-friendly shape: cascade staging must
    fit ≥ 3x the segments packed-only staging fits at one byte budget."""
    n_segments, rows = 12, 2048
    segs = rollup_segments(n_segments, rows=rows, card=8, n_dims=5,
                           n_mets=3, seed=3)
    q = {"queryType": "groupBy", "dataSource": "casc",
         "intervals": [str(IV)], "granularity": "hour",
         "dimensions": ["d0", "d1"],
         "filter": {"type": "and", "fields": [
             {"type": "in", "dimension": d,
              "values": [f"v{d[1]}_{j:03d}" for j in range(4)]}
             for d in ("d2", "d3", "d4")]},
         "aggregations": [{"type": "count", "name": "n"},
                          {"type": "longSum", "name": "s0",
                           "fieldName": "m0"},
                          {"type": "longSum", "name": "s1",
                           "fieldName": "m1"},
                          {"type": "longMin", "name": "s2",
                           "fieldName": "m2"}]}
    ex = QueryExecutor(segs)
    # pin the column paths: this measures STAGED bytes, so the device
    # bitmap path (which stops staging filter columns) is disabled in
    # both modes, exactly like test_packed's ≥3x test
    from druid_tpu.engine import filters as _filters
    prev_bmp = _filters.set_device_bitmap_enabled(False)
    # ...and the run-domain path, which since the uniform-granularity rung
    # would serve this aligned shape from run tables with no column
    # staging at all — this test measures STAGED column bytes
    prev_rd = cascade.set_run_domain_enabled(False)
    prev_c = cascade.set_enabled(False)
    try:
        packed_only = ex.run_json(q)
        packed_per_seg = fresh_pool.snapshot().resident_bytes / n_segments
        fresh_pool.clear()
        cascade.set_enabled(True)
        casc_rows = ex.run_json(q)
        s = fresh_pool.snapshot()
        assert packed_only == casc_rows            # parity rides along
        casc_per_seg = s.resident_bytes / n_segments
        multiplier = packed_per_seg / casc_per_seg
        assert multiplier >= 3.0, (
            f"cascade staging only {multiplier:.2f}x over packed-only "
            f"({packed_per_seg:.0f}B -> {casc_per_seg:.0f}B per segment)")
        assert s.cascade_ratio >= 3.0
        # a budget sized for ~4 packed-only stagings holds every segment
        budget = int(packed_per_seg * 4)
        fresh_pool.clear()
        fresh_pool.configure(budget)
        ex.run_json(q)
        s = fresh_pool.snapshot()
        assert s.entries >= n_segments
        assert s.resident_bytes <= budget
    finally:
        cascade.set_enabled(prev_c)
        cascade.set_run_domain_enabled(prev_rd)
        _filters.set_device_bitmap_enabled(prev_bmp)


# ---------------------------------------------------------------------------
# pool accounting + monitors
# ---------------------------------------------------------------------------

def test_pool_cascade_accounting(fresh_pool):
    segs = rollup_segments(1, rows=2048)
    q = dict(GROUPBY, granularity="hour")
    QueryExecutor(segs).run_json(q)
    s = fresh_pool.snapshot()
    assert s.cascade_bytes > 0
    assert s.cascade_logical_bytes > s.cascade_bytes
    assert s.cascade_ratio > 1.0
    assert s.cascade_bytes <= s.resident_bytes
    # the walker counts cascade-marked leaves only
    import jax
    rc = cascade.RleColumn(jax.device_put(np.zeros(8, np.int32)),
                           jax.device_put(np.zeros(8, np.int32)), 64, 1024)
    actual, logical = entry_cascade_bytes({"a": rc, "b": np.zeros(16)})
    assert (actual, logical) == (64, 4096)


def test_code_domain_monitor_emits_cataloged_names(fresh_pool):
    from druid_tpu.obs import catalog
    from druid_tpu.utils.emitter import InMemoryEmitter, ServiceEmitter
    sink = InMemoryEmitter()
    em = ServiceEmitter("s", "h", sink)
    mon = cascade.CodeDomainMonitor(cascade.CodeDomainStats())
    mon.source.record(1234)
    mon.do_monitor(em)
    names = {e.metric for e in sink.events}
    assert names == {"query/codeDomain/hits", "query/codeDomain/rows"}
    assert catalog.validate_emitted(names) == []


# ---------------------------------------------------------------------------
# hyperUnique/cardinality at non-default registers (log2m != 11 rider)
# ---------------------------------------------------------------------------

def test_hyperunique_log2m12_parity(fresh_pool):
    from druid_tpu.engine import batching
    segs = rollup_segments(4, rows=1500, card=8)
    q = {"queryType": "groupBy", "dataSource": "casc",
         "intervals": [str(IV)], "granularity": "all",
         "dimensions": ["d0"],
         "aggregations": [
             {"type": "count", "name": "n"},
             {"type": "hyperUnique", "name": "u", "fieldName": "d1",
              "log2m": 12}]}
    oracle, casc_rows = _run_modes(q, segs)
    assert oracle == casc_rows
    prev = batching.set_enabled(False)
    try:
        per_seg = QueryExecutor(segs).run_json(q)
    finally:
        batching.set_enabled(prev)
    assert per_seg == oracle


# ---------------------------------------------------------------------------
# run-domain over uniform granularities (bucket boundaries join the joint
# run partition — the ROADMAP item-3 follow-on rung)
# ---------------------------------------------------------------------------

HOUR_MS = 3_600_000


def hour_run_segments(n=2, rows=2048, card=8):
    """Rollup shape whose TIME advances one hour per dimension block: the
    hour-granularity bucket boundaries provably align with the run
    boundaries of every referenced column."""
    reps = -(-rows // card)
    segs = []
    for si in range(n):
        b = SegmentBuilder("casc", IV, version="v0", partition=si)
        dims = {f"d{i}": np.repeat(
            [f"v{i}_{j:03d}" for j in range(card)], reps)[:rows].tolist()
            for i in range(2)}
        mets = {"cnt": np.ones(rows, dtype=np.int64),
                "m0": np.repeat((np.arange(card) * 7) % 13,
                                reps)[:rows].astype(np.int64),
                "m1": np.repeat((np.arange(card) * 8) % 13,
                                reps)[:rows].astype(np.int64)}
        time = IV.start + (np.arange(rows, dtype=np.int64) // reps) * HOUR_MS
        b.add_columns(time, dims, mets)
        segs.append(b.build())
    return segs


def test_run_domain_uniform_granularity_parity_zero_unpack(fresh_pool):
    """Hour-granularity execution over hour-aligned runs goes fully
    code-domain: bit-identical to the decoded oracle, zero unpack, one
    runDomain dispatch per segment — per-bucket rows now ride run space,
    not just granularity-'all' covering-interval queries."""
    from druid_tpu.obs import dispatch as dispatch_mod
    segs = hour_run_segments()
    q = dict(RUN_GROUPBY, granularity="hour")
    oracle, _ = _run_modes(q, segs)
    fresh_pool.clear()
    cascade.reset_decode_stats()
    h0 = cascade.code_domain_stats().snapshot()
    d0 = dispatch_mod.stats().snapshot().get("runDomain", 0)
    got = QueryExecutor(segs).run_json(q)
    assert got == oracle
    assert cascade.decode_stats() == {}
    h1 = cascade.code_domain_stats().snapshot()
    assert h1["hits"] - h0["hits"] == len(segs)
    assert dispatch_mod.stats().snapshot()["runDomain"] - d0 == len(segs)
    # timeseries rides the same rung (no dims: key = the run's bucket id)
    ts = {"queryType": "timeseries", "dataSource": "casc",
          "intervals": [str(IV)], "granularity": "hour",
          "aggregations": RUN_GROUPBY["aggregations"]}
    o2, c2 = _run_modes(ts, segs)
    assert o2 == c2
    assert cascade.code_domain_stats().snapshot()["hits"] > h1["hits"]


def test_run_domain_uniform_eligibility_boundaries(fresh_pool):
    """The alignment proof is the joint run count: bucket boundaries that
    split runs too fine price the segment out of run space (row program,
    still bit-identical); a non-covering interval likewise."""
    segs = hour_run_segments()

    # minute granularity over hour-blocked time: bucket ids change every
    # row block of 1 minute... time advances in whole hours, so minute
    # buckets ALIGN; break alignment with per-row minute steps instead
    reps = -(-2048 // 8)
    b = SegmentBuilder("casc", IV, version="vx", partition=9)
    n = 2048
    dims = {"d0": np.repeat([f"v0_{j:03d}" for j in range(8)],
                            reps)[:n].tolist(),
            "d1": np.repeat([f"v1_{j:03d}" for j in range(8)],
                            reps)[:n].tolist()}
    mets = {"cnt": np.ones(n, dtype=np.int64),
            "m0": np.repeat((np.arange(8) * 7) % 13, reps)[:n].astype(
                np.int64),
            "m1": np.repeat((np.arange(8) * 8) % 13, reps)[:n].astype(
                np.int64)}
    b.add_columns(IV.start + np.arange(n, dtype=np.int64) * 60_000,
                  dims, mets)
    fine = b.build()

    h0 = cascade.code_domain_stats().snapshot()["hits"]
    q = dict(RUN_GROUPBY, granularity="minute")
    oracle, got = _run_modes(q, [fine])
    assert oracle == got
    # per-row bucket changes -> joint runs == rows -> priced out
    assert cascade.code_domain_stats().snapshot()["hits"] == h0

    # a query interval that does NOT cover the segment keeps the row
    # program (the time mask is not all-true), results identical
    half = Interval(IV.start, IV.start + 4 * HOUR_MS)
    qh = dict(RUN_GROUPBY, granularity="hour", intervals=[str(half)])
    h1 = cascade.code_domain_stats().snapshot()["hits"]
    oracle, got = _run_modes(qh, segs)
    assert oracle == got
    assert cascade.code_domain_stats().snapshot()["hits"] == h1

    # and the aligned shape DOES run code-domain under the same budget
    qa = dict(RUN_GROUPBY, granularity="hour")
    oracle, got = _run_modes(qa, segs)
    assert oracle == got
    assert cascade.code_domain_stats().snapshot()["hits"] > h1
