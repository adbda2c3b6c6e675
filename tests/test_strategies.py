"""Reduction-strategy equivalence: mm / windowed / blocked / mixed must all
produce identical results (reference semantics are strategy-independent —
GroupByQueryEngineV2 vs vectorized engines return the same rows)."""
import collections

import numpy as np
import pytest

from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine import QueryExecutor
from druid_tpu.engine import grouping
from druid_tpu.query.aggregators import (CountAggregator, DoubleSumAggregator,
                                         FloatMaxAggregator,
                                         FloatSumAggregator,
                                         LongMinAggregator, LongSumAggregator)
from druid_tpu.query.filters import BoundFilter
from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery
from druid_tpu.utils.intervals import Interval

INTERVAL = Interval.of("2026-01-01", "2026-01-02")


def _gen(sort_by_dims, card_a=30, card_b=200, n=40_000, lo=-500, hi=9_000):
    schema = (
        ColumnSpec("dimA", "string", cardinality=card_a),
        ColumnSpec("dimB", "string", cardinality=card_b, distribution="zipf"),
        ColumnSpec("metLong", "long", low=lo, high=hi),
        ColumnSpec("metFloat", "float", distribution="normal", mean=10.0,
                   std=400.0),
    )
    gen = DataGenerator(schema, seed=77)
    return gen.segments(2, n // 2, INTERVAL, sort_by_dims=sort_by_dims)


AGGS = [CountAggregator("rows"),
        LongSumAggregator("lsum", "metLong"),
        FloatSumAggregator("fsum", "metFloat"),
        FloatMaxAggregator("fmax", "metFloat"),
        LongMinAggregator("lmin", "metLong")]

MM_AGGS = AGGS[:3]   # sum-decomposable only


def _run(segments, aggs, dims, flt=None, force=None, monkeypatch=None,
         mesh=None):
    if force is not None:
        orig = grouping.select_strategy

        def fake(spec, kernels, col_dtypes, padded_rows, windowed_w):
            s, w = orig(spec, kernels, col_dtypes, padded_rows, windowed_w)
            if force == "mixed":
                return "mixed", 0
            assert s == force, f"expected strategy {force}, selected {s}"
            return s, w
        monkeypatch.setattr(grouping, "select_strategy", fake)
    try:
        q = GroupByQuery.of(
            "bench", [INTERVAL], [DefaultDimensionSpec(d) for d in dims],
            aggs, granularity="all", filter=flt)
        ex = QueryExecutor(segments, mesh=mesh)
        rows = ex.run(q)
    finally:
        if force is not None:
            monkeypatch.setattr(grouping, "select_strategy", orig)
    out = {}
    for r in rows:
        e = r["event"]
        out[tuple(e[d] for d in dims)] = {
            k: e[k] for k in e if k not in dims}
    return out


def _compare(a, b, float_keys=("fsum", "fmax")):
    assert set(a) == set(b)
    for k in a:
        for m in a[k]:
            va, vb = a[k][m], b[k][m]
            if m in float_keys:
                assert va == pytest.approx(vb, rel=1e-4, abs=1e-2), (k, m)
            else:
                assert va == vb, (k, m)


def test_mm_matches_mixed_small_group(monkeypatch):
    segments = _gen(sort_by_dims=False, card_b=40)
    flt = BoundFilter("metLong", lower=-100, upper=8_000, ordering="numeric")
    got = _run(segments, MM_AGGS, ["dimB"], flt)          # auto → mm
    want = _run(segments, MM_AGGS, ["dimB"], flt, force="mixed",
                monkeypatch=monkeypatch)
    _compare(got, want)


def test_mm_negative_longs_exact(monkeypatch):
    segments = _gen(sort_by_dims=False, card_b=40, lo=-4_000, hi=-1)
    got = _run(segments, MM_AGGS, ["dimB"])
    want = _run(segments, MM_AGGS, ["dimB"], force="mixed",
                monkeypatch=monkeypatch)
    _compare(got, want)


def test_windowed_matches_mixed_big_group(monkeypatch):
    segments = _gen(sort_by_dims=True)
    # 30 x 200 = 6000 groups > 2048 → windowed on the sorted layout
    flt = BoundFilter("metLong", lower=0, upper=8_500, ordering="numeric")
    got = _run(segments, AGGS, ["dimA", "dimB"], flt, force="windowed",
               monkeypatch=monkeypatch)
    want = _run(segments, AGGS, ["dimA", "dimB"], flt, force="mixed",
                monkeypatch=monkeypatch)
    _compare(got, want)


def test_windowed_ineligible_on_unsorted():
    segments = _gen(sort_by_dims=False)
    spec = grouping.make_group_spec(
        segments[0], [INTERVAL],
        __import__("druid_tpu.utils.granularity",
                   fromlist=["Granularity"]).Granularity.of("all"),
        [grouping.KeyDim("dimA", 30, None),
         grouping.KeyDim("dimB", 200, None)])
    from druid_tpu.utils.granularity import Granularity
    w = grouping.windowed_window(segments[0], [INTERVAL],
                                 Granularity.of("all"), spec)
    assert w == 0


def test_windowed_eligible_on_sorted():
    segments = _gen(sort_by_dims=True)
    from druid_tpu.utils.granularity import Granularity
    spec = grouping.make_group_spec(
        segments[0], [INTERVAL], Granularity.of("all"),
        [grouping.KeyDim("dimA", 30, None),
         grouping.KeyDim("dimB", 200, None)])
    w = grouping.windowed_window(segments[0], [INTERVAL],
                                 Granularity.of("all"), spec)
    assert w in grouping.WINDOW_CHOICES


def test_mm_float_nan_confined_to_its_group():
    """A single NaN float row must only NaN its OWN group (reference
    FloatSumAggregator semantics) — the mm one-hot contraction would spread
    it to every group, so non-finite columns must be mm-ineligible."""
    segments = _gen(sort_by_dims=False, card_b=40)
    s0 = segments[0]
    vals = s0.metrics["metFloat"].values
    poison_row = 7
    vals[poison_row] = np.nan
    poison_group = None
    col = s0.dims["dimB"]
    poison_group = col.dictionary.values[col.ids[poison_row]]

    got = _run(segments, MM_AGGS, ["dimB"])
    assert np.isnan(got[(poison_group,)]["fsum"])
    for k, v in got.items():
        if k != (poison_group,):
            assert np.isfinite(v["fsum"]), k


def test_mm_float_nan_column_not_mm(monkeypatch):
    segments = _gen(sort_by_dims=False, card_b=40)
    segments[0].metrics["metFloat"].values[3] = np.inf
    seen = []
    orig = grouping.select_strategy

    def spy(spec, kernels, col_dtypes, padded_rows, windowed_w):
        s, w = orig(spec, kernels, col_dtypes, padded_rows, windowed_w)
        seen.append(s)
        return s, w
    monkeypatch.setattr(grouping, "select_strategy", spy)
    _run(segments, MM_AGGS, ["dimB"])
    assert seen and all(s != "mm" for s in seen)


def test_mesh_forced_mm_matches_mixed(monkeypatch):
    from druid_tpu.parallel import make_mesh
    # card 200 pads to 256: above the ≤64 blocked cut, inside mm range
    segments = _gen(sort_by_dims=False, card_b=200)
    flt = BoundFilter("metLong", lower=-100, upper=8_000, ordering="numeric")
    mesh = make_mesh(2)
    got = _run(segments, MM_AGGS, ["dimB"], flt, force="mm",
               monkeypatch=monkeypatch, mesh=mesh)
    want = _run(segments, MM_AGGS, ["dimB"], flt, force="mixed",
                monkeypatch=monkeypatch, mesh=mesh)
    _compare(got, want)


def test_mesh_forced_windowed_matches_mixed(monkeypatch):
    from druid_tpu.parallel import make_mesh
    segments = _gen(sort_by_dims=True)
    flt = BoundFilter("metLong", lower=0, upper=8_500, ordering="numeric")
    mesh = make_mesh(2)
    got = _run(segments, AGGS, ["dimA", "dimB"], flt, force="windowed",
               monkeypatch=monkeypatch, mesh=mesh)
    want = _run(segments, AGGS, ["dimA", "dimB"], flt, force="mixed",
                monkeypatch=monkeypatch, mesh=mesh)
    _compare(got, want)


def _spy_strategies(monkeypatch):
    seen = []
    orig = grouping.select_strategy

    def spy(spec, kernels, col_dtypes, padded_rows, windowed_w):
        s, w = orig(spec, kernels, col_dtypes, padded_rows, windowed_w)
        seen.append(s)
        return s, w
    monkeypatch.setattr(grouping, "select_strategy", spy)
    return seen


def test_projection_pallas_interpret_matches_mixed(monkeypatch):
    """The fused pallas kernel (via the interpreter on CPU) must agree with
    the mixed path exactly — count, exact int64 sums through the lo/hi limb
    pair, float sums, and min/max."""
    from druid_tpu.engine import pallas_agg
    segments = _gen(sort_by_dims=False)   # 30 x 200 = 6000 > MM_GROUP_LIMIT
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)
    inner = []
    orig_inner = grouping._projection_strategy

    def spy(proj, kernels, col_dtypes, num_total):
        s, w = orig_inner(proj, kernels, col_dtypes, num_total)
        inner.append(s)
        return s, w
    monkeypatch.setattr(grouping, "_projection_strategy", spy)
    got = _run(segments, AGGS, ["dimA", "dimB"])
    assert inner and all(s == "pallas" for s in inner)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", False)
    want = _run(segments, AGGS, ["dimA", "dimB"], force="mixed",
                monkeypatch=monkeypatch)
    _compare(got, want)


def test_projection_windowed_matches_mixed(monkeypatch):
    """With pallas gated off, the projection strategy reduces through the XLA
    windowed path over the sorted layout; results must match mixed."""
    monkeypatch.setenv("DRUID_TPU_PALLAS", "0")
    segments = _gen(sort_by_dims=False)
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    inner = []
    orig_inner = grouping._projection_strategy

    def spy(proj, kernels, col_dtypes, num_total):
        s, w = orig_inner(proj, kernels, col_dtypes, num_total)
        inner.append(s)
        return s, w
    monkeypatch.setattr(grouping, "_projection_strategy", spy)
    flt = BoundFilter("metLong", lower=0, upper=8_500, ordering="numeric")
    got = _run(segments, AGGS, ["dimA", "dimB"], flt)
    assert inner and all(s == "windowed" for s in inner)
    want = _run(segments, AGGS, ["dimA", "dimB"], flt, force="mixed",
                monkeypatch=monkeypatch)
    _compare(got, want)


def test_pallas_limb_sum_exact_across_flushes(monkeypatch):
    """int32 long sums ride a lo/hi limb pair flushed every K blocks; with
    values near the chunk_rows bound and >> chunk_rows rows per group the
    total exceeds int32 and must still be bit-exact int64."""
    from druid_tpu.engine import pallas_agg
    segments = _gen(sort_by_dims=False, card_a=2, card_b=3, n=40_000,
                    lo=200_000, hi=260_000)
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)
    orig = grouping.select_strategy

    def force_proj(spec, kernels, col_dtypes, padded_rows, windowed_w):
        return "projection", 0
    monkeypatch.setattr(grouping, "select_strategy", force_proj)
    aggs = [CountAggregator("rows"), LongSumAggregator("lsum", "metLong")]
    got = _run(segments, aggs, ["dimA", "dimB"])
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", False)
    monkeypatch.setattr(grouping, "select_strategy", orig)
    want = _run(segments, aggs, ["dimA", "dimB"], force="mixed",
                monkeypatch=monkeypatch)
    # per-group totals ~ 40000/6 * 230000 ≈ 1.5e9, sums overflow across limbs
    assert any(v["lsum"] > 2**30 for v in want.values())
    _compare(got, want)


def test_pallas_fully_masked_blocks(monkeypatch):
    """A selective filter leaves whole sorted blocks masked; those blocks
    must contribute nothing (their keys read as the sentinel)."""
    from druid_tpu.engine import pallas_agg
    from druid_tpu.query.filters import SelectorFilter
    segments = _gen(sort_by_dims=False)
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)
    flt = SelectorFilter("dimA", "v00000003")
    got = _run(segments, AGGS, ["dimA", "dimB"], flt)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", False)
    want = _run(segments, AGGS, ["dimA", "dimB"], flt, force="mixed",
                monkeypatch=monkeypatch)
    _compare(got, want)


def test_pallas_compile_failure_falls_back(monkeypatch):
    """A Mosaic compile failure must not fail the query: the executor latches
    pallas off and re-runs the same plan on the XLA windowed/mixed path."""
    from druid_tpu.engine import pallas_agg
    segments = _gen(sort_by_dims=False)
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pallas_agg, "_BROKEN", None)
    monkeypatch.setattr(grouping, "_JIT_CACHE", collections.OrderedDict())

    def boom(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")
    monkeypatch.setattr(pallas_agg, "pallas_reduce", boom)
    got = _run(segments, AGGS, ["dimA", "dimB"])
    assert "Mosaic failed to compile" in pallas_agg.broken_reason()
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", False)
    want = _run(segments, AGGS, ["dimA", "dimB"], force="mixed",
                monkeypatch=monkeypatch)
    _compare(got, want)


def test_pallas_run_failure_propagates_unlatched(monkeypatch):
    """The latch covers the kernel's BUILD only: a program that built and
    then fails while it RUNS raises to the caller and leaves pallas live —
    a run-time fault must never be hidden behind an XLA re-run."""
    from druid_tpu.engine import pallas_agg
    segments = _gen(sort_by_dims=False)
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pallas_agg, "_BROKEN", None)
    monkeypatch.setattr(grouping, "_JIT_CACHE", collections.OrderedDict())
    real_build = grouping._build_device_fn

    class RunFails:
        def __init__(self, fn):
            self.lower = fn.lower           # builds fine

        def __call__(self, *a, **k):
            raise RuntimeError("device fault while running")

    def build(spec, *a, **k):
        fn = real_build(spec, *a, **k)
        return RunFails(fn) if spec.strategy == "pallas" else fn
    monkeypatch.setattr(grouping, "_build_device_fn", build)
    with pytest.raises(RuntimeError, match="device fault while running"):
        _run(segments, AGGS, ["dimA", "dimB"])
    assert pallas_agg.broken_reason() is None


def test_mm_double_sum_falls_back(monkeypatch):
    # doubleSum has no mm decomposition → strategy must not be "mm"
    segments = _gen(sort_by_dims=False, card_b=40)
    aggs = [CountAggregator("rows"), DoubleSumAggregator("dsum", "metFloat")]
    seen = []
    orig = grouping.select_strategy

    def spy(spec, kernels, col_dtypes, padded_rows, windowed_w):
        s, w = orig(spec, kernels, col_dtypes, padded_rows, windowed_w)
        seen.append(s)
        return s, w
    monkeypatch.setattr(grouping, "select_strategy", spy)
    _run(segments, aggs, ["dimB"])
    assert seen and all(s != "mm" for s in seen)


def test_force_strategy_override_equivalence(segments, monkeypatch):
    """DRUID_TPU_STRATEGY / grouping.FORCE_STRATEGY forces an eligible
    strategy (the chip-suite measurement hook); results stay identical."""
    from druid_tpu.engine import QueryExecutor, grouping
    from druid_tpu.query.aggregators import CountAggregator, LongSumAggregator
    from druid_tpu.query.model import DefaultDimensionSpec, GroupByQuery
    from druid_tpu.utils.intervals import Interval
    iv = Interval.of("2026-01-01", "2026-01-08")
    q = GroupByQuery.of(
        "test", [iv],
        [DefaultDimensionSpec("dimA"), DefaultDimensionSpec("dimB")],
        [CountAggregator("n"), LongSumAggregator("s", "metLong")],
        granularity="all")
    base = QueryExecutor(segments).run(q)
    key = lambda rows: {(r["event"]["dimA"], r["event"]["dimB"]):
                        (r["event"]["n"], r["event"]["s"]) for r in rows}
    want = key(base)
    real_select = grouping.select_strategy
    chosen = []

    def spy(*a, **kw):
        out = real_select(*a, **kw)
        chosen.append(out[0])
        return out

    monkeypatch.setattr(grouping, "select_strategy", spy)
    # mixed/projection are always eligible; windowed may legitimately fall
    # through when the span check refuses (results must still match)
    for strat, strict in (("mixed", True), ("projection", True),
                          ("windowed", False)):
        chosen.clear()
        monkeypatch.setattr(grouping, "FORCE_STRATEGY", strat)
        got = key(QueryExecutor(segments).run(q))
        assert got == want, f"strategy {strat} diverged"
        assert chosen
        if strict:
            # the force must actually select it, not fall through
            assert all(c == strat for c in chosen), (strat, chosen)
