"""merge_partials aligns partials in the dense merged key space when that
space is small enough, through a sort otherwise (ISSUE 25). The two
alignments must return the same five values bit for bit — buckets, the
value array of every dimension, counts, every kernel's state (dtypes, the
spelling of every value and the order of groups included) — so every case
here runs both on the same partials and compares bytes; the path that
`merge_partials` itself chose is read from the trace span it stamps."""
import dataclasses
from unittest import mock

import numpy as np
import pytest

from druid_tpu.data.segment import SegmentBuilder, ValueType
from druid_tpu.engine import QueryExecutor, engines, grouping, merge
from druid_tpu.engine.grouping import pad_pow2
from druid_tpu.obs import trace as qtrace
from druid_tpu.query.aggregators import (CountAggregator, DoubleMaxAggregator,
                                         DoubleMinAggregator,
                                         DoubleSumAggregator,
                                         FilteredAggregator, FirstAggregator,
                                         FloatMaxAggregator,
                                         FloatMinAggregator,
                                         FloatSumAggregator,
                                         HyperUniqueAggregator,
                                         LastAggregator, LongMaxAggregator,
                                         LongMinAggregator, LongSumAggregator)
from druid_tpu.query.filters import SelectorFilter
from druid_tpu.query.model import (DefaultDimensionSpec, GroupByQuery,
                                   TimeseriesQuery, TopNQuery)
from druid_tpu.utils.intervals import Interval, parse_ts

T0 = parse_ts("2026-05-01")
DAY = 86_400_000
IV = Interval(T0, T0 + 4 * DAY)

#: every kernel kind the engine has
ALL_AGGS = [
    CountAggregator("n"),
    LongSumAggregator("ls", "ml"), FloatSumAggregator("fs", "mf"),
    DoubleSumAggregator("ds", "md"),
    LongMinAggregator("lmin", "ml"), LongMaxAggregator("lmax", "ml"),
    FloatMinAggregator("fmin", "mf"), FloatMaxAggregator("fmax", "mf"),
    DoubleMinAggregator("dmin", "md"), DoubleMaxAggregator("dmax", "md"),
    FirstAggregator("first", "md", "double"),
    LastAggregator("last", "ml", "long"),
    FilteredAggregator("filt", LongSumAggregator("filt", "ml"),
                       SelectorFilter("b", "b1")),
    HyperUniqueAggregator("hll", "u"),
]
#: what the sorted projection (and so a host-keyed partial) supports
PROJ_AGGS = [CountAggregator("n"), LongSumAggregator("ls", "ml"),
             LongMinAggregator("lmin", "ml"), FloatMaxAggregator("fmax", "mf")]


def _seg(day, a_vals, b_vals, rows=240, seed=0, with_b=True, partition=0):
    """One day segment whose `a` and `b` dictionaries are exactly the given
    values (every value occurs), timestamps spread over the day. Partitions
    of one day share dimensions and timestamps and differ in the metrics."""
    rng = np.random.default_rng([seed, day])
    a = list(a_vals) + list(rng.choice(a_vals, rows - len(a_vals)))
    b = list(b_vals) + list(rng.choice(b_vals, rows - len(b_vals)))
    rng.shuffle(a)
    dims = {"a": a, "u": [f"u{v}" for v in rng.integers(0, 40, rows)]}
    if with_b:
        dims["b"] = b
    sb = SegmentBuilder("md", Interval(T0 + day * DAY, T0 + (day + 1) * DAY),
                        version="v1", partition=partition)
    times = T0 + day * DAY + np.sort(rng.integers(0, DAY, rows))
    rng = np.random.default_rng([seed, day, partition])
    sb.add_columns(
        times, dims=dims,
        metrics={"ml": rng.integers(-50, 50, rows).astype(np.int64),
                 "mf": rng.normal(0, 9, rows).astype(np.float32),
                 "md": rng.normal(0, 9, rows),
                 "mn": rng.integers(day, day + 4, rows).astype(np.int64)},
        metric_types={"ml": ValueType.LONG, "mf": ValueType.FLOAT,
                      "md": ValueType.DOUBLE, "mn": ValueType.LONG})
    return sb.build()


def _names(prefix, ids):
    return [f"{prefix}{i}" for i in ids]


def _groupby(dims=("a", "b"), aggs=ALL_AGGS, granularity="all", flt=None):
    return GroupByQuery.of("md", [IV], [DefaultDimensionSpec(d) for d in dims],
                           aggs, granularity=granularity, filter=flt)


def _partials(query, segments):
    """One producer per segment, as data nodes answer a broker: nothing
    unifies the segments' dictionaries before the merge."""
    ap = engines.AggregatePartials.concat(
        [engines.make_aggregate_partials(query, [s], clamp=False)
         for s in segments])
    return ap.partials, ap.dim_values


# ---------------------------------------------------------------------------
# rewrites of a partial that keep its meaning
# ---------------------------------------------------------------------------

def _rebuilt(p, spec, slots, total):
    """`p` with its arrays re-laid: slot i of the new arrays is slot
    slots[i] of the old, the rest up to `total` holds the identity (in the
    dtype the partial came with)."""
    def place(dst, src):
        if isinstance(dst, dict):
            return {n: place(dst[n], src[n]) for n in dst}
        src = np.asarray(src)
        dst = dst.astype(src.dtype)
        dst[:len(slots)] = src[slots]
        return dst
    states = {k.name: place(k.empty_state(total), p.states[k.name])
              for k in p.kernels}
    counts = np.zeros(total, dtype=np.int64)
    counts[:len(slots)] = p.counts[slots]
    return dataclasses.replace(p, spec=spec, counts=counts, states=states)


def _own_total(p):
    n = max(p.spec.num_buckets, 1)
    for d in p.spec.dims:
        n *= d.cardinality
    return n


def as_host(p, dead=True):
    """A dense-keyed partial as the projection path would hand it over:
    compact slots over the keys that occur (and, with `dead`, a few that the
    filter left without a row)."""
    n = _own_total(p)
    keep = p.counts[:n] > 0
    if dead:
        keep[::3] = True
    slots = np.flatnonzero(keep)
    spec = dataclasses.replace(p.spec, key_mode="host",
                               host_unique=slots.astype(np.int64),
                               num_total=pad_pow2(max(len(slots), 1)))
    return _rebuilt(p, spec, slots, spec.num_total)


def repadded(p, factor=4):
    """The same dense-keyed partial under a larger padding."""
    total = p.spec.num_total * factor
    spec = dataclasses.replace(p.spec, num_total=total)
    return _rebuilt(p, spec, np.arange(_own_total(p)), total)


def reordered(p, vals, d, perm):
    """Dimension d's dictionary in another order: local id j now means
    what id perm[j] meant."""
    cards = [k.cardinality for k in p.spec.dims]
    shape = (max(p.spec.num_buckets, 1), *cards)
    grid = np.arange(_own_total(p)).reshape(shape)
    slots = np.take(grid, perm, axis=d + 1).ravel()
    new_vals = list(vals)
    new_vals[d] = [vals[d][i] for i in perm]
    return _rebuilt(p, p.spec, slots, p.spec.num_total), new_vals


# ---------------------------------------------------------------------------
# the cases
# ---------------------------------------------------------------------------

A10, B4 = _names("a", range(10)), _names("b", range(4))


def case_identical():
    # the benchmark cell's shape, scaled down: every segment holds every
    # value of both dimensions
    segs = [_seg(d, A10, B4, seed=1) for d in range(4)]
    return _partials(_groupby(), segs)


def case_overlapping(rows=240):
    segs = [_seg(0, _names("a", range(0, 8)), B4, rows, seed=2),
            _seg(1, _names("a", range(4, 12)), _names("b", range(2, 6)),
                 rows, seed=2),
            _seg(2, _names("a", range(6, 9)), B4, rows, seed=2)]
    return _partials(_groupby(), segs)


def case_disjoint():
    segs = [_seg(0, _names("a", range(0, 5)), _names("b", range(0, 2)),
                 seed=3),
            _seg(1, _names("a", range(5, 9)), _names("b", range(2, 5)),
                 seed=3)]
    return _partials(_groupby(), segs)


def case_reordered_one_list():
    # every partial shares ONE list that is not in merged order
    parts, vals = case_identical()
    perm = np.random.default_rng(4).permutation(len(A10))
    out = [reordered(p, v, 0, perm) for p, v in zip(parts, vals)]
    shared = out[0][1]
    return [p for p, _ in out], [shared for _ in out]


def case_reordered_differently(rows=240):
    parts, vals = case_overlapping(rows)
    rng = np.random.default_rng(5)
    out = [reordered(p, v, 1, rng.permutation(len(v[1])))
           for p, v in zip(parts, vals)]
    return [p for p, _ in out], [v for _, v in out]


def case_absent_dimension():
    # `b` is no column of the middle segment: KeyDim(column=None) -> ""
    segs = [_seg(0, A10, B4, seed=6), _seg(1, A10, B4, seed=6, with_b=False),
            _seg(2, A10, B4, seed=6)]
    aggs = [a for a in ALL_AGGS if a.name != "filt"]
    return _partials(_groupby(aggs=aggs), segs)


def case_numeric_dimension():
    # a query-time numeric dictionary (different in every segment) beside a
    # string dimension
    segs = [_seg(d, A10, B4, seed=7) for d in range(3)]
    return _partials(_groupby(dims=("mn", "a")), segs)


def case_hour_buckets():
    segs = [_seg(0, A10, B4, seed=8), _seg(1, _names("a", range(3, 12)), B4,
                                           seed=8)]
    return _partials(_groupby(dims=("a",), granularity="hour"), segs)


def case_timeseries_hour():
    segs = [_seg(d, A10, B4, seed=9) for d in range(3)]
    q = TimeseriesQuery.of("md", [IV], ALL_AGGS, granularity="hour")
    return _partials(q, segs)


def case_timeseries_all():
    segs = [_seg(d, A10, B4, seed=9) for d in range(3)]
    return _partials(TimeseriesQuery.of("md", [IV], ALL_AGGS,
                                        granularity="all"), segs)


def case_topn():
    segs = [_seg(0, A10, B4, seed=10), _seg(1, _names("a", range(5, 14)), B4,
                                            seed=10)]
    q = TopNQuery.of("md", [IV], DefaultDimensionSpec("a"), "ls", 5, ALL_AGGS,
                     granularity="all")
    return _partials(q, segs)


def case_empty_partial():
    # the filter's value is in one segment's dictionary only and matches no
    # row of the others' — and a const-false partial among them
    segs = [_seg(0, A10 + ["only"], B4, seed=11), _seg(1, A10, B4, seed=11),
            _seg(2, A10, B4, seed=11)]
    parts, vals = _partials(_groupby(flt=SelectorFilter("a", "only")), segs)
    assert any(not p.counts.any() for p in parts)
    return parts, vals


def case_all_empty():
    segs = [_seg(d, A10, B4, seed=12) for d in range(2)]
    return _partials(_groupby(flt=SelectorFilter("a", "nowhere")), segs)


def case_single_partial():
    return _partials(_groupby(), [_seg(0, A10, B4, seed=13)])


def case_single_partial_minus_zero():
    # a float sum of -0.0 stays -0.0 when nothing is added to it
    (p,), vals = case_single_partial()
    states = dict(p.states)
    states["fs"] = np.where(np.arange(len(p.counts)) % 2 == 0,
                            np.float32(-0.0), p.states["fs"])
    return [dataclasses.replace(p, states=states)], vals


def case_minus_zero_meets_the_identity():
    # a float sum of -0.0 in EVERY partial stays -0.0; where some partial
    # lacks the group the sorted alignment adds that partial's 0.0 to it
    parts, vals = case_overlapping()
    out = []
    for p in parts:
        states = dict(p.states)
        states["fs"] = np.full_like(p.states["fs"], -0.0)
        states["ds"] = np.full_like(p.states["ds"], -0.0)
        out.append(dataclasses.replace(p, states=states))
    return out, vals


def case_first_last_ties():
    # two partitions of one day: every group's first and last row carry the
    # same timestamp in both, so the order of the combines decides
    segs = [_seg(0, A10, B4, seed=16, partition=i) for i in range(3)]
    return _partials(_groupby(), segs)


def _junk(shape, dtype, rng):
    if dtype == bool:
        return rng.integers(0, 2, shape).astype(bool)
    return rng.integers(1, 100, shape).astype(dtype)


def case_dead_slots_hold_garbage():
    # what a slot holds that no row reached is nobody's business
    parts, vals = case_overlapping(rows=20)
    rng = np.random.default_rng(17)
    out = []
    for p in parts:
        dead = p.counts == 0
        assert dead.any()

        def spoil(leaf):
            if isinstance(leaf, dict):
                return {k: spoil(v) for k, v in leaf.items()}
            leaf = np.array(leaf, copy=True)
            leaf[dead] = _junk(leaf[dead].shape, leaf.dtype, rng)
            return leaf
        out.append(dataclasses.replace(
            p, states={k: spoil(v) for k, v in p.states.items()}))
    return out, vals


def case_state_wider_than_identity():
    # a float sum handed over in float64: the sorted path narrows it to the
    # identity's float32 BEFORE it adds
    parts, vals = case_identical()
    out = []
    for i, p in enumerate(parts):
        states = dict(p.states)
        states["fs"] = p.states["fs"].astype(np.float64) * (1 + 2.0 ** -30) \
            + i * 1e-7
        out.append(dataclasses.replace(p, states=states))
    return out, vals


def case_host_keys_unsorted():
    # nothing here needs host_unique in key order
    parts, vals = case_identical()
    rng = np.random.default_rng(18)
    out = []
    for p in parts:
        n = _own_total(p)
        # first and last in place: the ends alone look consecutive
        slots = np.concatenate([[0], 1 + rng.permutation(n - 2), [n - 1]])
        spec = dataclasses.replace(p.spec, key_mode="host",
                                   host_unique=slots.astype(np.int64),
                                   num_total=pad_pow2(n))
        out.append(_rebuilt(p, spec, slots, spec.num_total))
    return out, vals


def case_padding_differs():
    parts, vals = case_overlapping()
    return [repadded(parts[0]), parts[1], repadded(parts[2], 2)], vals


def case_sparse_filter():
    # few live groups in every partial: the placement goes by live slots
    segs = [_seg(d, A10, B4, seed=14) for d in range(3)]
    return _partials(_groupby(flt=SelectorFilter("b", "b2")), segs)


def case_host_keys_synthetic():
    # compact host-keyed partials over shared dictionaries; the first holds
    # every key (consecutive), the others lack some, with and without slots
    # that no row reached
    segs = [_seg(d, A10, B4, rows=50, seed=15) for d in range(4)]
    parts, vals = _partials(_groupby(), segs)
    assert all(0 < np.count_nonzero(p.counts) < 40 for p in parts)
    full = parts[0]
    n = _own_total(full)
    spec = dataclasses.replace(full.spec, key_mode="host",
                               host_unique=np.arange(n, dtype=np.int64),
                               num_total=pad_pow2(n))
    counts = full.counts.copy()
    counts[:n] = np.maximum(counts[:n], 1)
    first = _rebuilt(dataclasses.replace(full, counts=counts), spec,
                     np.arange(n), spec.num_total)
    return [first] + [as_host(p, dead=i % 2 == 0)
                      for i, p in enumerate(parts[1:])], vals


def case_host_among_dense():
    parts, vals = case_overlapping()
    return [parts[0], as_host(parts[1]), parts[2]], vals


def case_host_keys_reordered():
    parts, vals = case_reordered_differently(rows=20)
    return [as_host(p, dead=i % 2 == 0) for i, p in enumerate(parts)], vals


def _projection_partials(monkeypatch, segs):
    # the sorted-projection strategy, as 5M-row segments take it: the engine
    # itself hands over key_mode == "host" partials
    monkeypatch.setattr(grouping, "FORCE_STRATEGY", "projection")
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 1)
    parts, vals = _partials(_groupby(aggs=PROJ_AGGS), segs)
    assert {p.spec.key_mode for p in parts} == {"host"}
    return parts, vals


DENSE_CASES = {
    "identical-dictionaries": case_identical,
    "overlapping-dictionaries": case_overlapping,
    "disjoint-dictionaries": case_disjoint,
    "one-list-out-of-order": case_reordered_one_list,
    "differently-ordered-dictionaries": case_reordered_differently,
    "dimension-absent-from-a-segment": case_absent_dimension,
    "numeric-and-string-dimension": case_numeric_dimension,
    "hour-buckets": case_hour_buckets,
    "timeseries-hour": case_timeseries_hour,
    "timeseries-all": case_timeseries_all,
    "topn": case_topn,
    "a-partial-with-every-count-0": case_empty_partial,
    "every-partial-empty": case_all_empty,
    "single-partial": case_single_partial,
    "single-partial-minus-zero": case_single_partial_minus_zero,
    "minus-zero-meets-the-identity": case_minus_zero_meets_the_identity,
    "first-last-ties": case_first_last_ties,
    "dead-slots-hold-garbage": case_dead_slots_hold_garbage,
    "state-wider-than-identity": case_state_wider_than_identity,
    "host-keys-unsorted": case_host_keys_unsorted,
    "padding-differs": case_padding_differs,
    "sparse-filter": case_sparse_filter,
    "host-keys-shared-dictionaries": case_host_keys_synthetic,
    "host-keyed-among-dense": case_host_among_dense,
    "host-keys-reordered-dictionaries": case_host_keys_reordered,
}


def _same(got, ref, where):
    if isinstance(ref, dict):
        assert isinstance(got, dict) and list(got) == list(ref), where
        for k in ref:
            _same(got[k], ref[k], f"{where}/{k}")
        return
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.dtype == ref.dtype, (where, got.dtype, ref.dtype)
    assert got.shape == ref.shape, (where, got.shape, ref.shape)
    if ref.dtype == object:
        assert [(type(v), v) for v in got.tolist()] \
            == [(type(v), v) for v in ref.tolist()], where
    else:
        assert got.tobytes() == ref.tobytes(), where


def _same_merge(got, ref):
    """Two results of merge_partials, equal in every bit."""
    _same(got[0], ref[0], "buckets")
    assert len(got[1]) == len(ref[1])
    for d, (g, r) in enumerate(zip(got[1], ref[1])):
        _same(g, r, f"dim{d}")
    _same(got[2], ref[2], "counts")
    _same(got[3], ref[3], "states")
    assert got[4] is ref[4]


def _sorted_merge(parts, vals):
    """merge_partials held to the sorted alignment, whatever the partials
    show."""
    with mock.patch.object(merge, "_dense_space", lambda *a: None):
        return merge.merge_partials(parts, vals)


def _check(parts, vals, path):
    """merge_partials took `path`, said so, and returned what the sorted
    alignment returns."""
    with qtrace.root_span("test", store=qtrace.TraceStore()) as sp:
        got = merge.merge_partials(parts, vals)
    ref = _sorted_merge(parts, vals)
    assert sp.attrs["mergePath"] == path
    assert sp.attrs["groups"] == len(ref[2])
    _same_merge(got, ref)
    assert (ref[2] > 0).all()
    return ref


@pytest.mark.parametrize("moved_as", ("runs-or-index", "runs"))
@pytest.mark.parametrize("case", sorted(DENSE_CASES))
def test_dense_equals_sorted(case, moved_as, monkeypatch):
    if moved_as == "runs":
        # every run of consecutive slots a pair of slices, however short
        # (partials this small move through index arrays otherwise)
        monkeypatch.setattr(merge, "_RUN_MIN_SLOTS", 0)
    parts, vals = DENSE_CASES[case]()
    ref = _check(parts, vals, "dense")
    if "empty" not in case:
        assert len(ref[2]) > 0


def test_dense_equals_sorted_on_projection_partials(monkeypatch):
    segs = [_seg(0, A10, B4, seed=20),
            _seg(1, _names("a", range(2, 12)), B4, seed=20),
            _seg(2, A10, B4, seed=20)]
    parts, vals = _projection_partials(monkeypatch, segs)
    _check(parts, vals, "dense")


def test_a_few_missing_keys_move_as_slices():
    """What the benchmark's cell hands over: host-keyed partials of a shared
    key space, some lacking a key or two. Those move as a few pairs of
    slices; one that lacks every other key moves through index arrays."""
    a, b = _names("a", range(64)), _names("b", range(64))
    segs = [_seg(d, a, b, rows=5000, seed=21) for d in range(3)]
    parts, vals = _partials(_groupby(aggs=PROJ_AGGS), segs)
    n = 64 * 64

    def host(p, missing):
        keep = np.setdiff1d(np.arange(n), missing)
        counts = p.counts.copy()
        counts[:n] = np.maximum(counts[:n], 1)
        spec = dataclasses.replace(p.spec, key_mode="host",
                                   host_unique=keep.astype(np.int64),
                                   num_total=pad_pow2(len(keep)))
        return _rebuilt(dataclasses.replace(p, counts=counts), spec, keep,
                        spec.num_total)

    parts = [host(parts[0], []), host(parts[1], [7, 3000]),
             host(parts[2], np.arange(0, n, 2))]
    space = merge._dense_space(parts, vals)
    whole, few, many = (merge._partial_placement(p, space, luts)
                        for p, luts in zip(parts, space.luts))
    assert whole == [(slice(0, n), slice(0, n))]
    assert few == [(slice(0, 7), slice(0, 7)),
                   (slice(7, 2999), slice(8, 3000)),
                   (slice(2999, n - 2), slice(3001, n))]
    (sel, pos), = many
    assert sel == slice(0, n // 2) and isinstance(pos, np.ndarray)
    _check(parts, vals, "dense")


def test_every_kernel_kind_is_covered():
    from druid_tpu.engine import kernels as K
    parts, _ = case_identical()
    kinds = {type(k) for k in parts[0].kernels}
    assert kinds == {K.CountKernel, K.SumKernel, K.MinMaxKernel,
                     K.FirstLastKernel, K.FilteredKernel, K.HllKernel}
    assert any(isinstance(s, dict) for s in parts[0].states.values())


# ---------------------------------------------------------------------------
# where the sorted alignment stays
# ---------------------------------------------------------------------------

def case_past_the_limit(monkeypatch):
    parts, vals = case_overlapping()
    # 12 × 6 merged values
    monkeypatch.setattr(merge, "DENSE_GROUP_LIMIT", 71)
    return parts, vals


def case_host_keys_past_the_limit(monkeypatch):
    # the engine's own reason for host keys: 1,500 × 1,500 > DENSE_GROUP_LIMIT
    n = 1500
    rng = np.random.default_rng(30)
    segs = []
    for day in range(2):
        sb = SegmentBuilder("md", Interval(T0 + day * DAY,
                                           T0 + (day + 1) * DAY), version="v1")
        sb.add_columns(
            T0 + day * DAY + np.arange(2 * n) * 1000,
            dims={"a": _names("a", rng.permutation(2 * n) % n),
                  "b": _names("b", rng.permutation(2 * n) % n)},
            metrics={"ml": rng.integers(0, 9, 2 * n).astype(np.int64)},
            metric_types={"ml": ValueType.LONG})
        segs.append(sb.build())
    parts, vals = _partials(_groupby(aggs=[CountAggregator("n"),
                                           LongSumAggregator("ls", "ml")]),
                            segs)
    assert {p.spec.key_mode for p in parts} == {"host"}
    return parts, vals


def case_value_twice_in_a_list(monkeypatch):
    parts, vals = case_identical()
    twice = [list(v) for v in vals[0]]
    twice[0][1] = twice[0][0]
    return parts, [twice for _ in vals]


def case_equal_across_types(monkeypatch):
    # 1 and 1.0 are one merged value; which spelling survives is the
    # sorted alignment's to say
    parts, vals = case_numeric_dimension()
    vals = [[list(v[0]), v[1]] for v in vals]
    vals[0][0] = [float(x) for x in vals[0][0]]
    return parts, vals


def case_equal_lists_of_other_types(monkeypatch):
    # the partials' lists compare equal ([0, 1, ..] == [0.0, 1.0, ..]) and
    # still spell their values differently
    segs = [_seg(0, A10, B4, seed=7, partition=i) for i in range(3)]
    parts, vals = _partials(_groupby(dims=("mn", "a")), segs)
    assert vals[0][0] == vals[1][0] and type(vals[0][0][0]) is int
    vals = [[list(v[0]), v[1]] for v in vals]
    vals[1][0] = [float(x) for x in vals[1][0]]
    assert vals[0][0] == vals[1][0]
    return parts, vals


def case_bucket_counts_differ(monkeypatch):
    parts, vals = case_hour_buckets()
    spec = dataclasses.replace(
        parts[1].spec, bucket_starts=parts[1].spec.bucket_starts[:-1])
    return [parts[0], dataclasses.replace(parts[1], spec=spec)], vals


SORTED_CASES = {
    "merged-space-past-the-limit": case_past_the_limit,
    "host-keys-past-the-limit": case_host_keys_past_the_limit,
    "value-twice-in-a-list": case_value_twice_in_a_list,
    "equal-across-types": case_equal_across_types,
    "equal-lists-of-other-types": case_equal_lists_of_other_types,
}


@pytest.mark.parametrize("case", sorted(SORTED_CASES))
def test_falls_back_to_sorted_and_says_so(case, monkeypatch):
    parts, vals = SORTED_CASES[case](monkeypatch)
    assert merge._dense_space(parts, vals) is None
    with qtrace.root_span("test", store=qtrace.TraceStore()) as sp:
        merge.merge_partials(parts, vals)
    assert sp.attrs["mergePath"] == "sorted"


def test_bucket_counts_differ_is_not_dense(monkeypatch):
    parts, vals = case_bucket_counts_differ(monkeypatch)
    assert merge._dense_space(parts, vals) is None


def test_untraced_merge_stamps_nothing():
    parts, vals = case_single_partial()
    assert qtrace.current_span() is None
    merge.merge_partials(parts, vals)          # no span: nothing to write to
    assert qtrace.current_span() is None


def test_eligibility_never_reads_a_group(monkeypatch):
    """_dense_space reads specs and value lists only: with every partial's
    arrays taken away it answers the same."""
    parts, vals = case_overlapping()
    bare = [dataclasses.replace(p, counts=None, states=None) for p in parts]
    space = merge._dense_space(bare, vals)
    assert space is not None and space.cards == [12, 6]
    assert all(lut is not None for row in space.luts for lut in row)
    shared, svals = case_identical()
    space = merge._dense_space(shared, svals)
    assert all(lut is None for row in space.luts for lut in row)


# ---------------------------------------------------------------------------
# the finished rows
# ---------------------------------------------------------------------------

def _finish_queries():
    return {
        "groupBy": _groupby(granularity="day"),
        "topN": TopNQuery.of("md", [IV], DefaultDimensionSpec("a"), "ls", 4,
                             ALL_AGGS, granularity="all"),
        "timeseries": TimeseriesQuery.of("md", [IV], ALL_AGGS,
                                         granularity="hour"),
    }


@pytest.mark.parametrize("kind", ("groupBy", "topN", "timeseries"))
def test_finished_rows_equal_before_and_after(kind, monkeypatch):
    segs = [_seg(0, A10, B4, seed=40),
            _seg(1, _names("a", range(4, 13)), B4, seed=40),
            _seg(2, A10, B4, seed=40, with_b=kind != "groupBy")]
    query = _finish_queries()[kind]
    after = QueryExecutor(segs).run(query)
    monkeypatch.setattr(engines, "merge_partials", _sorted_merge)
    before = QueryExecutor(segs).run(query)
    assert after and repr(after) == repr(before)
