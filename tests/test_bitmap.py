"""Bitmap algebra + selectivity-estimator edge cases (ROADMAP item 5
satellites): sparse containers survive AND/OR/XOR without densifying,
NOT-of-sparse / empty-dictionary / all-rows-match selectivities are EXACT,
and the packed-uint32 device representation round-trips bit-for-bit."""
import numpy as np
import pytest

import druid_tpu.engine  # noqa: F401  (x64 on before jax numerics)
from druid_tpu.data.bitmap import (Bitmap, BitmapIndex, SparseBitmap,
                                   bitmap_and, bitmap_or, bitmap_xor,
                                   leaf_rows, sparse_if_small,
                                   sparse_leaf_width, to_words32)
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine.filters import (bitmap_of, estimate_selectivity,
                                      filter_cardinality)
from druid_tpu.query import filters as F
from druid_tpu.utils.intervals import Interval

IV = Interval.of("2026-03-01", "2026-03-02")


def _segment(n_rows=3333, card=50, seed=3):
    """n_rows deliberately NOT a multiple of 32 (word-boundary coverage)."""
    gen = DataGenerator((ColumnSpec("d", "string", cardinality=card),
                        ColumnSpec("m", "long", low=0, high=9)), seed=seed)
    return gen.segment(n_rows, IV, datasource="bm")


@pytest.fixture()
def no_densify(monkeypatch):
    """Fail the test if ANY SparseBitmap is densified (words/_dense)."""
    def boom(self):
        raise AssertionError("SparseBitmap was densified")
    monkeypatch.setattr(SparseBitmap, "_dense", boom)
    monkeypatch.setattr(SparseBitmap, "words", property(boom))


# ---------------------------------------------------------------------------
# representation-aware algebra
# ---------------------------------------------------------------------------

def test_sparse_sparse_algebra_stays_sparse(no_densify):
    n = 3333
    a = SparseBitmap(np.array([1, 5, 40, 999, 3332], np.int32), n)
    b = SparseBitmap(np.array([5, 40, 100], np.int32), n)
    both = a & b
    assert isinstance(both, SparseBitmap)
    assert list(both.ids) == [5, 40]
    either = a | b
    assert isinstance(either, SparseBitmap)
    assert list(either.ids) == [1, 5, 40, 100, 999, 3332]
    diff = a ^ b
    assert isinstance(diff, SparseBitmap)
    assert list(diff.ids) == [1, 100, 999, 3332]


def test_sparse_dense_and_probes_words_without_densify(no_densify):
    n = 3333
    dense = Bitmap.from_indices(np.arange(0, n, 2), n)   # even rows
    sp = SparseBitmap(np.array([0, 1, 2, 31, 32, 33, 3332], np.int32), n)
    out = bitmap_and(sp, dense)
    assert isinstance(out, SparseBitmap)
    assert list(out.ids) == [0, 2, 32, 3332]
    # operator form (either operand order) routes the same way
    assert list((dense & sp).ids) == [0, 2, 32, 3332]


def test_sparse_dense_or_xor_fold_ids_into_words():
    n = 100
    dense = Bitmap.from_indices(np.array([0, 1, 2]), n)
    sp = SparseBitmap(np.array([2, 50, 99], np.int32), n)
    assert sorted((sp | dense).to_indices()) == [0, 1, 2, 50, 99]
    assert sorted((sp ^ dense).to_indices()) == [0, 1, 50, 99]
    assert sorted((dense ^ sp).to_indices()) == [0, 1, 50, 99]


def test_not_of_sparse_is_dense_and_exact():
    n = 3333
    sp = SparseBitmap(np.array([0, 5, 3332], np.int32), n)
    inv = ~sp
    assert inv.cardinality() == n - 3
    assert not inv.test_ids(np.array([0, 5, 3332])).any()


def test_sparse_if_small_demotes():
    n = 32 * 40
    few = Bitmap.from_indices(np.array([3, 700]), n)
    assert isinstance(sparse_if_small(few), SparseBitmap)
    many = Bitmap.from_indices(np.arange(0, n, 2), n)
    assert isinstance(sparse_if_small(many), Bitmap)


# ---------------------------------------------------------------------------
# selectivity / bitmap_of edge cases
# ---------------------------------------------------------------------------

def test_not_of_sparse_selectivity_exact_without_densify(monkeypatch):
    seg = _segment(n_rows=3333, card=400)   # ~8 rows/value: sparse leaves
    val = seg.dims["d"].dictionary.values[0]
    leaf = F.SelectorFilter("d", val)
    lb = bitmap_of(leaf, seg)
    assert isinstance(lb, SparseBitmap)
    k = lb.cardinality()
    # NOT computes as n - |child|: neither the complement words nor the
    # sparse child's words materialize
    def boom(self):
        raise AssertionError("SparseBitmap was densified")
    monkeypatch.setattr(SparseBitmap, "_dense", boom)
    monkeypatch.setattr(SparseBitmap, "words", property(boom))
    assert filter_cardinality(F.NotFilter(leaf), seg) == seg.n_rows - k
    assert estimate_selectivity(F.NotFilter(leaf), seg) == \
        (seg.n_rows - k) / seg.n_rows


def test_empty_dictionary_dim_exact():
    seg = _segment()
    # IN over values absent from the dictionary: the empty id set
    flt = F.InFilter("d", ("no-such-value", "also-missing"))
    bm = bitmap_of(flt, seg)
    assert bm.cardinality() == 0
    assert estimate_selectivity(flt, seg) == 0.0
    # and its complement is exactly everything
    assert filter_cardinality(F.NotFilter(flt), seg) == seg.n_rows
    assert estimate_selectivity(F.NotFilter(flt), seg) == 1.0


def test_zero_cardinality_index_and_empty_segment():
    idx = BitmapIndex.build(np.zeros(0, dtype=np.int32), 0)
    assert idx.union_of(np.array([], dtype=np.int64)).cardinality() == 0
    assert idx.union_of(np.array([0, 3])).cardinality() == 0  # out of range


def test_all_rows_match_exact():
    seg = _segment(card=1)                   # every row holds the one value
    val = seg.dims["d"].dictionary.values[0]
    flt = F.SelectorFilter("d", val)
    assert filter_cardinality(flt, seg) == seg.n_rows
    assert estimate_selectivity(flt, seg) == 1.0
    assert estimate_selectivity(F.TrueFilter(), seg) == 1.0
    assert estimate_selectivity(F.FalseFilter(), seg) == 0.0


def test_bitmap_of_matches_host_truth_on_mixed_tree():
    seg = _segment(n_rows=3333, card=30)
    vals = seg.dims["d"].dictionary.values
    flt = F.OrFilter((
        F.AndFilter((F.InFilter("d", tuple(vals[:3])),
                     F.NotFilter(F.SelectorFilter("d", vals[1])))),
        F.SelectorFilter("d", vals[7]),
    ))
    from druid_tpu.engine.filters import host_mask
    want = host_mask(flt, seg)
    got = bitmap_of(flt, seg)
    assert np.array_equal(got.to_bool(), want)
    assert filter_cardinality(flt, seg) == int(want.sum())


# ---------------------------------------------------------------------------
# packed uint32 device words
# ---------------------------------------------------------------------------

def test_words32_round_trip_lsb_first():
    n, padded = 3333, 3584          # padded: multiple of 32, not of 1024
    rng = np.random.default_rng(5)
    mask = rng.random(n) < 0.3
    bm = Bitmap.from_bool(mask)
    w = to_words32(bm, padded)
    assert w.dtype == np.uint32 and w.shape == (padded // 32,)
    rows = np.arange(padded)
    bits = (w[rows // 32] >> (rows % 32).astype(np.uint32)) & 1
    assert np.array_equal(bits[:n].astype(bool), mask)
    assert not bits[n:].any()       # padding rows stay clear


def test_leaf_rows_density_split():
    n = 4096
    width = sparse_leaf_width(n)
    assert width == n // 256 and sparse_leaf_width(1024) == 8   # the floor
    kind, rows = leaf_rows([SparseBitmap(np.array([1, 2, 3], np.int32), n)],
                           n)
    assert kind == "sparse" and rows.dtype == np.int32
    # THE sparse width, padded with the out-of-range sentinel
    assert rows.shape == (1, width) and (rows[0, 3:] == n).all()
    assert rows[0, :3].tolist() == [1, 2, 3]
    dense_bm = Bitmap.from_indices(np.arange(0, n, 3), n)
    kind, rows = leaf_rows([dense_bm], n)
    assert kind == "dense" and rows.dtype == np.uint32
    assert np.array_equal(rows[0], to_words32(dense_bm, n))


@pytest.mark.parametrize("m", [0, 1, 15, 16, 17, 64])
@pytest.mark.parametrize("dense_type", [False, True])
def test_leaf_rows_has_one_sparse_shape(m, dense_type):
    """A leaf's cardinality picks between TWO shapes — ids at the one
    width, or words — never a shape of its own: each shape is a program.
    Whether the host index hands out an id list or packed words (a
    persisted segment's) does not change the row."""
    n, rows = 4096, 4000
    ids = np.arange(m, dtype=np.int32) * 7
    bm = Bitmap.from_indices(ids, rows) if dense_type \
        else SparseBitmap(ids, rows)
    kind, got = leaf_rows([bm], n)
    if m <= sparse_leaf_width(n):
        assert kind == "sparse" and got.shape == (1, sparse_leaf_width(n))
        assert np.array_equal(got[0, :m], ids) and (got[0, m:] == n).all()
    else:
        assert kind == "dense"
        assert np.array_equal(got[0], to_words32(bm, n))


def test_leaf_rows_converts_a_position_together():
    """Bitmaps of both host types and of three row counts in one call:
    every row is what the bitmap alone gives; one that does not fit the
    width ships the whole position as words."""
    n = 2048
    rng = np.random.default_rng(5)
    small = [np.sort(rng.permutation(r)[:k]).astype(np.int32)
             for r, k in ((2000, 3), (2000, 8), (1999, 0), (1500, 5))]
    bms = [Bitmap.from_indices(small[0], 2000), SparseBitmap(small[1], 2000),
           Bitmap.from_indices(small[2], 1999),
           Bitmap.from_indices(small[3], 1500)]
    kind, got = leaf_rows(bms, n)
    assert kind == "sparse" and got.shape == (4, 8)
    for row, ids in zip(got, small):
        assert np.array_equal(row[:ids.shape[0]], ids)
        assert (row[ids.shape[0]:] == n).all()
    big = Bitmap.from_bool(rng.random(2000) < 0.4)
    kind, got = leaf_rows(bms + [big], n)
    assert kind == "dense" and got.shape == (5, n // 32)
    for row, bm in zip(got, bms + [big]):
        assert np.array_equal(row, to_words32(bm, n))


def test_union_of_stays_sparse_and_exact():
    seg = _segment(n_rows=4000, card=500, seed=11)
    col = seg.dims["d"]
    idx = col.bitmap_index()
    bm = idx.union_of(np.array([0, 1]))
    assert isinstance(bm, SparseBitmap)
    truth = np.isin(col.ids, [0, 1])
    assert np.array_equal(bm.to_bool(), truth)
    assert bm.cardinality() == int(truth.sum())
