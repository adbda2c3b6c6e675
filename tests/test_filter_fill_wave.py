"""A wave's filter leaves cross to the device as ONE buffer and fill through
ONE program a layout (engine/filters.py `_pack_wave`, `_fill_wave`).

Bit parity: the wave's words equal the single-pair fill's and the packed
bits of `host_mask`, for every leaf kind (sparse ids at every cardinality
the one width takes, dense words, run tables), for waves that mix kinds,
for AND / OR / NOT structures, with duplicates, resident segments and
padding slots. Hand-overs: `jax.device_put` calls of a wave do not depend
on how many pairs are cold. Programs: the cache key is the wave's layout —
cold counts and leaf cardinalities inside it build nothing new. Threads:
overlapping waves staged concurrently agree with the serial result.
"""
import sys
import threading

import numpy as np
import pytest

import druid_tpu.engine  # noqa: F401  (x64 on before jax numerics)
from druid_tpu.data.bitmap import sparse_leaf_width
from druid_tpu.data.segment import SegmentBuilder
from druid_tpu.engine import filters as filters_mod
from druid_tpu.engine.filters import (DeviceBitmapNode, filter_bitmap_stats,
                                      host_mask, plan_filter, simplify_node,
                                      stage_device_bitmaps_multi)
from druid_tpu.obs import dispatch as dispatch_mod
from druid_tpu.query import filters as F
from druid_tpu.utils.intervals import Interval

IV = Interval.of("2026-06-01", "2026-06-02")
R = 4096                   # padded rows: 128 words, sparse width 16
N = 4000                   # real rows: not a multiple of 32
WIDTH = sparse_leaf_width(R)
#: value "cN" of dim `d` holds exactly N rows of every segment
CARDS = (1, 8, WIDTH - 1, WIDTH, WIDTH + 1, 300)


def _segment(seed: int, m_rows: int = 5, m_sorted: bool = False):
    """4,000 rows: `d` holds exact cardinalities (CARDS, then "rest"), `e`
    four random values (dense leaves), `r` ten sorted runs (a run-table
    leaf), `m` the value "x" on `m_rows` rows — scattered, or in one run
    when `m_sorted` (so "x" is sparse, dense or a run table by segment)."""
    rng = np.random.default_rng(seed)
    d = np.full(N, "rest", dtype=object)
    at = rng.permutation(N)
    lo = 0
    for c in CARDS:
        d[at[lo:lo + c]] = f"c{c}"
        lo += c
    m = np.full(N, "y", dtype=object)
    if m_sorted:
        m[:m_rows] = "x"
    else:
        m[rng.permutation(N)[:m_rows]] = "x"
    b = SegmentBuilder("fw", IV, version="v0", partition=seed)
    b.add_columns(
        IV.start + np.arange(N, dtype=np.int64),
        {"d": d.tolist(),
         "e": rng.choice(["e0", "e1", "e2", "e3"], N).tolist(),
         "r": np.repeat([f"r{j}" for j in range(10)], N // 10).tolist(),
         "m": m.tolist()},
        {"met": rng.integers(0, 100, N).astype(np.int64)})
    return b.build()


def _node(flt, seg) -> DeviceBitmapNode:
    node = simplify_node(plan_filter(flt, seg, device_bitmap=True))
    assert isinstance(node, DeviceBitmapNode), node
    return node


def _truth(flt, seg) -> np.ndarray:
    return host_mask(flt, seg)


def _rows(words) -> np.ndarray:
    """The real rows' bits of staged words (a NOT sets the padding rows'
    too, as it always did: the stacked program's validity masks them)."""
    words = np.asarray(words)
    assert words.dtype == np.uint32 and words.shape == (R // 32,)
    return np.unpackbits(words.view(np.uint8), bitorder="little")[:N] \
        .astype(bool)


def _stage(flt, segs):
    """One wave over `segs`: [(words as numpy, node)] a segment."""
    nodes = [_node(flt, s) for s in segs]
    out = stage_device_bitmaps_multi(list(zip(segs, nodes)), R)
    return [(o[n.col], n) for o, n in zip(out, nodes)]


def _assert_wave_parity(flt, segs):
    for seg, (words, node) in zip(segs, _stage(flt, segs)):
        assert np.array_equal(_rows(words), _truth(flt, seg)), (flt, seg.id)
        single = filters_mod._fill_single(seg, node, R)
        assert np.array_equal(np.asarray(words), np.asarray(single))


def _blocks(flt, segs):
    """The blocks each leaf position of the wave ships (one structure)."""
    layout, _, _ = filters_mod._pack_wave(
        [(s, _node(flt, s)) for s in segs], R)
    (_, _, blocks), = layout
    return blocks


LEAVES = {
    "absent": (F.SelectorFilter("d", "no-such-value"), "sparse"),
    **{f"c{c}": (F.SelectorFilter("d", f"c{c}"),
                 "sparse" if c <= WIDTH else "dense") for c in CARDS},
    "rest": (F.SelectorFilter("d", "rest"), "dense"),
    "in-sparse": (F.InFilter("d", ("c1", "c8")), "sparse"),
    "in-dense": (F.InFilter("d", ("c8", f"c{WIDTH}")), "dense"),
    "low-card": (F.SelectorFilter("e", "e2"), "dense"),
    "runs": (F.SelectorFilter("r", "r3"), "runs"),
    "runs-in": (F.InFilter("r", ("r0", "r4", "r9")), "runs"),
}


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_wave_words_equal_single_fill_and_host_mask(name):
    """Every leaf kind, and sparse leaves at every cardinality the one
    width takes (0, 1, width - 1, width; width + 1 ships as words)."""
    flt, kind = LEAVES[name]
    segs = [_segment(100 + i) for i in range(3)]
    assert _blocks(flt, segs) == ((kind,),)
    _assert_wave_parity(flt, segs)


STRUCTURES = {
    "and": F.AndFilter((F.SelectorFilter("d", "c300"),
                        F.SelectorFilter("e", "e1"))),
    "or": F.OrFilter((F.SelectorFilter("d", "c8"),
                      F.SelectorFilter("r", "r7"))),
    "not": F.NotFilter(F.SelectorFilter("d", f"c{WIDTH}")),
    "not-dense": F.NotFilter(F.SelectorFilter("e", "e0")),
    "and-not-or": F.AndFilter((
        F.NotFilter(F.SelectorFilter("r", "r2")),
        F.OrFilter((F.SelectorFilter("d", "c1"),
                    F.SelectorFilter("e", "e3"),
                    F.SelectorFilter("m", "x"))))),
}


@pytest.mark.parametrize("name", sorted(STRUCTURES))
def test_wave_structures_combine_word_wise(name):
    segs = [_segment(200 + i) for i in range(4)]
    _assert_wave_parity(STRUCTURES[name], segs)


def test_wave_that_mixes_kinds_ships_one_block_a_kind():
    """The literal is sparse in two segments, dense in one and a run table
    in one: id lists ride as words beside the dense leaf (one block, one
    program fewer), the run table in a block of its own; the words are the
    host's bit for bit."""
    flt = F.SelectorFilter("m", "x")
    segs = [_segment(300, m_rows=WIDTH - 4), _segment(301, m_rows=WIDTH),
            _segment(302, m_rows=900), _segment(303, m_rows=400,
                                                m_sorted=True)]
    assert _blocks(flt, segs) == (("dense", "runs"),)
    assert _blocks(flt, segs[:2]) == (("sparse",),)
    assert _blocks(flt, segs[1:3]) == (("dense",),)
    _assert_wave_parity(flt, segs)


def _persisted(seg, directory):
    """The segment as a historical serves it: written by the default (V2)
    writer and loaded back — its bitmap index hands out packed words."""
    from druid_tpu.storage.format import load_segment
    from druid_tpu.storage.format_v2 import persist_segment_auto
    persist_segment_auto(seg, str(directory))
    return load_segment(str(directory))


@pytest.mark.parametrize("name", ["c1", f"c{WIDTH}", f"c{WIDTH + 1}", "rest",
                                  "runs", "and-not-or"])
def test_wave_over_persisted_and_in_memory_segments(name, tmp_path):
    """A wave whose segments are loaded from disk (dense host bitmaps,
    converted together) beside one built in memory (id lists): the words
    are the host's bit for bit. (Bitmaps of several row counts in one
    call: tests/test_bitmap.py.)"""
    flt = LEAVES[name][0] if name in LEAVES else STRUCTURES[name]
    segs = [_persisted(_segment(1000 + i), tmp_path / f"s{i}")
            for i in range(3)] + [_segment(1003)]
    _assert_wave_parity(flt, segs)


def test_wave_with_duplicates_residents_and_padding_slots():
    """Five items — one a duplicate, one resident — fill three cold pairs
    in the rung of the wave's five nodes (8 slots): padding slots are
    dropped, the duplicate shares its twin's array, the resident one is
    not refilled."""
    flt = F.SelectorFilter("d", "c8")
    segs = [_segment(400 + i) for i in range(4)]
    nodes = [_node(flt, s) for s in segs]
    stage_device_bitmaps_multi([(segs[2], nodes[2])], R)    # resident
    items = [(segs[0], nodes[0]), (segs[1], nodes[1]),
             (segs[1], nodes[1]), (segs[2], nodes[2]), (segs[3], nodes[3])]
    cache0 = set(filters_mod._FBMP_JIT_CACHE)
    s0, d0 = filter_bitmap_stats().snapshot(), dispatch_mod.stats().count()
    out = stage_device_bitmaps_multi(items, R)
    s1 = filter_bitmap_stats().snapshot()
    assert s1["misses"] - s0["misses"] == 3
    assert s1["hits"] - s0["hits"] == 2          # the twin, the resident
    assert dispatch_mod.stats().count() - d0 == 1
    (layout, rows), = set(filters_mod._FBMP_JIT_CACHE) - cache0
    assert rows == R and [K for _, K, _ in layout] == [8]
    assert out[1][nodes[1].col] is out[2][nodes[1].col]
    for (seg, node), o in zip(items, out):
        assert np.array_equal(_rows(o[node.col]), _truth(flt, seg))


def test_two_structures_fill_in_one_dispatch():
    """A wave whose items carry different structures (chunk-mates of two
    queries) is still one buffer and one program: a slot group a
    structure."""
    a = F.SelectorFilter("d", "c1")
    b = F.AndFilter((F.SelectorFilter("d", "c300"),
                     F.NotFilter(F.SelectorFilter("e", "e1"))))
    segs = [_segment(500 + i) for i in range(3)]
    items = [(s, _node(f, s)) for s in segs for f in (a, b)]
    layout, _, index = filters_mod._pack_wave(items, R)
    assert [K for _, K, _ in layout] == [4, 4]
    assert sorted(index) == [0, 1, 2, 4, 5, 6]
    d0 = dispatch_mod.stats().count()
    out = stage_device_bitmaps_multi(items, R)
    assert dispatch_mod.stats().count() - d0 == 1
    for (seg, node), o, f in zip(items, out, (a, b) * 3):
        assert np.array_equal(_rows(o[node.col]), _truth(f, seg))


@pytest.fixture
def device_puts(monkeypatch):
    """Counts `jax.device_put` calls (the hand-overs) while it is active."""
    import jax
    calls = []
    real = jax.device_put

    def counting(x, *a, **kw):
        calls.append(getattr(x, "nbytes", 0))
        return real(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counting)
    return calls


@pytest.mark.parametrize("cold", [1, 3, 8, 16])
@pytest.mark.parametrize("name", ["c8", "rest", "runs", "and-not-or"])
def test_hand_overs_of_a_wave_do_not_depend_on_its_cold_pairs(
        device_puts, cold, name):
    flt = LEAVES[name][0] if name in LEAVES else STRUCTURES[name]
    segs = [_segment(600 + i) for i in range(cold)]
    items = [(s, _node(flt, s)) for s in segs]
    del device_puts[:]
    stage_device_bitmaps_multi(items, R)
    assert len(device_puts) == 1, device_puts        # "two at most": one
    stage_device_bitmaps_multi(items, R)             # all resident
    assert len(device_puts) == 1


def test_cold_counts_and_leaf_sizes_inside_a_layout_build_no_program():
    """Twenty waves of eight segments, each with another literal (1 to
    `width` rows a segment) and another subset already resident: after the
    first, no entry joins the fill program's cache and nothing compiles."""
    rng = np.random.default_rng(7)
    segs = [_segment(700 + i) for i in range(8)]
    literals = [F.SelectorFilter("d", f"c{c}") for c in CARDS if c <= WIDTH] \
        + [F.InFilter("d", ("c1", "c8")), F.SelectorFilter("d", "nope")]

    def wave(flt, resident):
        items = [(s, _node(flt, s)) for s in segs]
        for i in resident:          # waves of one: their own (warm) rung
            stage_device_bitmaps_multi([items[i]], R)
        out = stage_device_bitmaps_multi(items, R)
        for (seg, node), o in zip(items, out):
            assert np.array_equal(_rows(o[node.col]), _truth(flt, seg))

    # the first wave of each rung builds its program (8 slots, 1 slot)
    wave(F.SelectorFilter("m", "x"), resident=[0])
    keys0 = set(filters_mod._FBMP_JIT_CACHE)
    c0 = dispatch_mod.stats().snapshot()["backend_compiles"]
    pendings = set()
    for k in range(20):
        flt = literals[k % len(literals)]
        if k >= len(literals):      # a literal again: other segments
            segs = [_segment(800 + 10 * k + i) for i in range(8)]
        resident = rng.permutation(8)[:int(rng.integers(0, 8))].tolist()
        pendings.add(8 - len(resident))
        wave(flt, resident)
    assert len(pendings) >= 5, pendings
    assert set(filters_mod._FBMP_JIT_CACHE) == keys0
    assert dispatch_mod.stats().snapshot()["backend_compiles"] == c0


def test_threads_staging_overlapping_waves_agree_with_serial():
    """Eight threads, each staging a window of 8 of 15 segments slid by
    one: every thread's words are the host's, whatever the interleaving of
    their pool inserts."""
    flt = F.OrFilter((F.SelectorFilter("d", "c8"),
                      F.SelectorFilter("e", "e1")))
    segs = [_segment(900 + i) for i in range(15)]
    nodes = [_node(flt, s) for s in segs]
    truth = [_truth(flt, s) for s in segs]
    stage_device_bitmaps_multi(                      # the program, once
        [(s, n) for s, n in zip(segs[:8], nodes[:8])], R)
    from druid_tpu.data.devicepool import device_pool
    device_pool().clear()
    barrier = threading.Barrier(8)
    got, errors = {}, []

    def run(t):
        try:
            barrier.wait(timeout=30)
            items = list(zip(segs[t:t + 8], nodes[t:t + 8]))
            out = stage_device_bitmaps_multi(items, R)
            got[t] = [_rows(o[n.col]) for o, (_, n) in zip(out, items)]
        except Exception as e:      # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(t,)) for t in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # hand the lock over mid-insert
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(th.is_alive() for th in threads), errors
    for t in range(8):
        for words, want in zip(got[t], truth[t:t + 8]):
            assert np.array_equal(words, want)
