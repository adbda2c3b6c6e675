"""Segment format V2 (storage/format_v2.py): the cascade-native on-disk
layout. Persist → deep-storage push/pull → mmap load → query must be
BIT-identical to the in-memory segment (floats included: NaN and -0.0
compare via integer bit views), with ZERO host decodes for the
run-domain-eligible shape — the compressed-from-disk-to-wire-to-HBM
contract of the format.

Golden fixtures (tests/golden/segment_v{1,2}): tiny checked-in segment
directories, regenerated by `python tests/test_format_v2.py regen`.
They pin the bytes a past release wrote; both must keep loading and
answering queries identically to a freshly built twin forever.
"""
import os
import shutil
import struct
import sys
from pathlib import Path

import numpy as np
import pytest

from druid_tpu.data import cascade
from druid_tpu.data.segment import SegmentBuilder
from druid_tpu.engine import engines
from druid_tpu.engine.executor import QueryExecutor
from druid_tpu.query.aggregators import (CountAggregator,
                                         DoubleSumAggregator,
                                         LongSumAggregator)
from druid_tpu.query.model import (DefaultDimensionSpec, GroupByQuery,
                                   TimeseriesQuery, TopNQuery)
from druid_tpu.storage.format import (FORMAT_VERSION, FORMAT_VERSION_V2,
                                      load_segment, persist_segment,
                                      read_format_version,
                                      read_segment_meta)
from druid_tpu.storage.format_v2 import (load_segment_v2,
                                         persist_segment_auto,
                                         persist_segment_v2,
                                         segment_load_stats)
from druid_tpu.storage.smoosh import CorruptSegmentError
from druid_tpu.utils.intervals import Interval

IV = Interval.of("2026-01-01", "2026-01-02")
GOLDEN_DIR = Path(__file__).resolve().parent / "golden"


def _mixed_segment(rows: int = 4096, datasource: str = "fmt"):
    """One segment exercising every V2 column encoding at once:
    a sorted dim (rle), a shuffled dim (pack/block fallback), a
    run-aligned long (rle), a random small-range long (pack), a
    repetitive double carrying NaN and -0.0 (lz4), and an
    incompressible random double (block fallback)."""
    card = 16
    reps = -(-rows // card)
    b = SegmentBuilder(datasource, IV, version="v0", partition=0)
    time = IV.start + (np.arange(rows, dtype=np.int64) // 64)
    dim_sorted = np.repeat([f"k{i:02d}" for i in range(card)], reps)[:rows]
    rng = np.random.default_rng(7)
    dim_rand = rng.integers(0, 500, rows)
    b.add_columns(
        time,
        {"d": dim_sorted.tolist(),
         "r": [f"x{v:03d}" for v in dim_rand]},
        {"cnt": np.ones(rows, dtype=np.int64),
         "val": np.repeat((np.arange(card) * 37) % 1000, reps)[:rows]
                .astype(np.int64),
         "sm": rng.integers(0, 50, rows).astype(np.int64),
         "dbl": np.tile(np.array([1.5, -0.0, np.nan, 2.0]),
                        -(-rows // 4))[:rows].astype(np.float64),
         "noise": rng.random(rows)})
    return b.build()


def _rollup_segment(rows: int = 4096, datasource: str = "roll"):
    """The RLE-friendly run-domain-eligible shape: dimension-sorted rows,
    constant rollup count, run-aligned value metric."""
    card = 16
    reps = -(-rows // card)
    b = SegmentBuilder(datasource, IV, version="v0", partition=0)
    b.add_columns(
        IV.start + (np.arange(rows, dtype=np.int64) // 64),
        {"dimA": np.repeat([f"a{i:02d}" for i in range(card)],
                           reps)[:rows].tolist()},
        {"cnt": np.ones(rows, dtype=np.int64),
         "val": np.repeat((np.arange(card) * 37) % 1000, reps)[:rows]
                .astype(np.int64)})
    return b.build()


def _rows_equal(a, b) -> bool:
    """Result-row equality where NaN == NaN (a doubleSum over a column
    carrying NaN is still a deterministic result)."""
    import math

    def norm(x):
        if isinstance(x, float) and math.isnan(x):
            return "__nan__"
        if isinstance(x, dict):
            return {k: norm(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return [norm(v) for v in x]
        return x
    return norm(a) == norm(b)


def _assert_bit_identical(a, b):
    """Segment equality down to the float BITS (NaN payloads, -0.0)."""
    assert a.id == b.id and a.n_rows == b.n_rows
    np.testing.assert_array_equal(a.time_ms, b.time_ms)
    assert set(a.dims) == set(b.dims)
    for name, col in a.dims.items():
        np.testing.assert_array_equal(b.dims[name].ids, col.ids)
        assert b.dims[name].dictionary == col.dictionary
    assert set(a.metrics) == set(b.metrics)
    for name, m in a.metrics.items():
        assert b.metrics[name].type == m.type
        x, y = np.asarray(m.values), np.asarray(b.metrics[name].values)
        assert x.dtype == y.dtype
        if np.issubdtype(x.dtype, np.floating):
            iw = np.dtype(f"<i{x.dtype.itemsize}")
            np.testing.assert_array_equal(x.view(iw), y.view(iw))
        else:
            np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# Round trip + encodings
# ---------------------------------------------------------------------------

def test_v2_roundtrip_bit_identical(tmp_path):
    seg = _mixed_segment()
    d = str(tmp_path / "v2")
    size = persist_segment_v2(seg, d)
    assert size > 0
    assert read_format_version(d) == FORMAT_VERSION_V2
    loaded = load_segment(d)       # version tag routes to load_segment_v2
    _assert_bit_identical(seg, loaded)


def test_v2_specs_cover_every_encoding(tmp_path):
    seg = _mixed_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    meta = read_segment_meta(d)
    assert meta["format"] == 2
    specs = meta["v2"]["columns"]
    assert specs["d"]["enc"] == "rle"        # sorted dim
    assert specs["cnt"]["enc"] == "rle"      # constant long
    assert specs["val"]["enc"] == "rle"      # run-aligned long
    assert specs["sm"]["enc"] == "pack"      # random small-range long
    assert specs["dbl"]["enc"] == "lz4"      # repetitive double
    assert specs["noise"]["enc"] == "block"  # incompressible double
    # staging descriptors persisted so load-time planning reads metadata
    assert meta["v2"]["staging"]["cascades"]


def test_v2_load_is_lazy_and_counts_decodes(tmp_path):
    seg = _mixed_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    loaded = load_segment_v2(d)
    lazy = [loaded.dims["d"], loaded.metrics["cnt"], loaded.metrics["sm"],
            loaded.metrics["dbl"]]
    assert all(not c.materialized() for c in lazy)
    cascade.reset_decode_stats()
    np.testing.assert_array_equal(loaded.dims["d"].ids, seg.dims["d"].ids)
    _ = loaded.metrics["sm"].values
    _ = loaded.metrics["dbl"].values
    stats = cascade.decode_stats()
    assert stats.get("host:rle") == 1
    assert stats.get("host:packed") == 1
    assert stats.get("host:lz4") == 1
    cascade.reset_decode_stats()


def test_v2_column_subset(tmp_path):
    seg = _mixed_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    loaded = load_segment_v2(d, columns=["d", "sm"])
    assert list(loaded.dims) == ["d"]
    assert list(loaded.metrics) == ["sm"]


def test_v2_bitmap_index_matches(tmp_path):
    seg = _mixed_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    loaded = load_segment_v2(d)
    for name in seg.dims:
        np.testing.assert_array_equal(
            loaded.dims[name].bitmap_index().bitmap(1).to_bool(),
            seg.dims[name].bitmap_index().bitmap(1).to_bool())


def test_persist_auto_routes_on_env(tmp_path, monkeypatch):
    seg = _rollup_segment()
    d2 = str(tmp_path / "auto_v2")
    persist_segment_auto(seg, d2)
    assert read_format_version(d2) == FORMAT_VERSION_V2
    monkeypatch.setenv("DRUID_TPU_SEGMENT_FORMAT", "1")
    d1 = str(tmp_path / "auto_v1")
    persist_segment_auto(seg, d1)
    assert read_format_version(d1) == FORMAT_VERSION
    _assert_bit_identical(load_segment(d1), load_segment(d2))


# ---------------------------------------------------------------------------
# Deep-storage push/pull + query parity + zero host decodes
# ---------------------------------------------------------------------------

def _queries(datasource):
    aggs = [CountAggregator("rows"), LongSumAggregator("s", "val")]
    return [
        GroupByQuery.of(datasource, [IV], [DefaultDimensionSpec("dimA")],
                        aggs, granularity="hour"),
        TimeseriesQuery.of(datasource, [IV], aggs, granularity="hour"),
        TopNQuery.of(datasource, [IV], "dimA", "s", 5, aggs,
                     granularity="all"),
    ]


def test_v2_push_pull_query_parity(tmp_path):
    from druid_tpu.cluster.view import descriptor_for
    from druid_tpu.storage.deep import LocalDeepStorage

    seg = _rollup_segment()
    deep = LocalDeepStorage(str(tmp_path / "deep"))
    desc = deep.push(seg, descriptor_for(seg))
    pulled = deep.pull(desc)
    _assert_bit_identical(seg, pulled)
    for q in _queries("roll"):
        assert QueryExecutor([pulled]).run(q) == QueryExecutor([seg]).run(q)


def test_v2_mixed_query_parity_with_floats(tmp_path):
    seg = _mixed_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    loaded = load_segment(d)
    q = GroupByQuery.of(
        "fmt", [IV], [DefaultDimensionSpec("d")],
        [CountAggregator("rows"), LongSumAggregator("s", "sm"),
         DoubleSumAggregator("x", "noise")], granularity="all")
    assert QueryExecutor([loaded]).run(q) == QueryExecutor([seg]).run(q)


def test_v2_run_domain_query_zero_host_decodes(tmp_path):
    """The headline contract: on the run-domain-eligible shape a V2 load
    plus a granularity-all groupBy longSum touches NO decompressed rows —
    planning reads persisted metadata, aggregation rides the seeded run
    tables, and the lazy columns never materialize."""
    seg = _rollup_segment(datasource="rd")
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    loaded = load_segment_v2(d)
    q = GroupByQuery.of(
        "rd", [IV], [DefaultDimensionSpec("dimA")],
        [CountAggregator("rows"), LongSumAggregator("c", "cnt"),
         LongSumAggregator("v", "val")], granularity="all")
    expected = QueryExecutor([seg]).run(q)
    cascade.reset_decode_stats()
    got = QueryExecutor([loaded]).run(q)
    stats = cascade.decode_stats()
    cascade.reset_decode_stats()
    assert got == expected
    assert stats == {}, f"decodes on the zero-decode path: {stats}"
    assert not loaded.dims["dimA"].materialized()
    assert not loaded.metrics["cnt"].materialized()
    assert not loaded.metrics["val"].materialized()


def test_v2_load_stats_accumulate(tmp_path):
    seg = _rollup_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    before = segment_load_stats().snapshot()
    load_segment_v2(d)
    after = segment_load_stats().snapshot()
    assert after["bytes"] > before["bytes"]
    assert after["compressedBytes"] > before["compressedBytes"]
    assert after["time_ms"] >= before["time_ms"]


# ---------------------------------------------------------------------------
# Corruption: typed errors, historical skip-and-log
# ---------------------------------------------------------------------------

def test_corrupt_truncated_chunk(tmp_path):
    seg = _rollup_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    chunk = os.path.join(d, "chunk_00000.bin")
    with open(chunk, "r+b") as f:
        f.truncate(os.path.getsize(chunk) // 2)
    with pytest.raises(CorruptSegmentError) as ei:
        load_segment(d)
    assert d in str(ei.value)


def test_corrupt_part_size_mismatch(tmp_path):
    """A spec/part disagreement names the damaged PART, not just the
    directory — the operator-facing half of the typed error."""
    seg = _rollup_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    # shrink the rle-values part's extent in the smoosh index: the spec
    # now promises more runs than the part holds
    meta_path = os.path.join(d, "meta.smoosh")
    lines = open(meta_path).read().splitlines()
    out = []
    for ln in lines:
        cols = ln.split(",")
        if cols[0] == "col.dimA.rle.values":
            cols[-1] = str(int(cols[-1]) - 4)   # drop one int32
            ln = ",".join(cols)
        out.append(ln)
    open(meta_path, "w").write("\n".join(out) + "\n")
    with pytest.raises(CorruptSegmentError) as ei:
        load_segment(d)
    assert "col.dimA.rle.values" in str(ei.value)


def test_corrupt_bad_version(tmp_path):
    seg = _rollup_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    with open(os.path.join(d, "version.bin"), "wb") as f:
        f.write(struct.pack("<I", 99))
    with pytest.raises(CorruptSegmentError):
        load_segment(d)


def test_corrupt_garbage_index_json(tmp_path):
    seg = _rollup_segment()
    d = str(tmp_path / "v2")
    persist_segment_v2(seg, d)
    meta_path = os.path.join(d, "meta.smoosh")
    open(meta_path, "w").write("v1,2048,1\nnot,a,valid,line\n")
    with pytest.raises(CorruptSegmentError):
        read_segment_meta(d)


def test_historical_skips_and_logs_corrupt_segment(tmp_path, capsys):
    """One damaged directory must not keep a historical from serving its
    healthy segments (satellite: skip-and-log on preload)."""
    from druid_tpu.cli import build_historical

    good = _rollup_segment(datasource="good")
    bad = _rollup_segment(datasource="bad")
    persist_segment_v2(good, str(tmp_path / "s0"))
    bad_dir = str(tmp_path / "s1")
    persist_segment_v2(bad, bad_dir)
    chunk = os.path.join(bad_dir, "chunk_00000.bin")
    with open(chunk, "r+b") as f:
        f.truncate(16)
    node, srv, loaded = build_historical("h-corrupt", str(tmp_path), port=0)
    try:
        assert loaded == 1
        assert len(node._segments) == 1
    finally:
        srv.stop()
    assert "skipping corrupt segment" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Compressed wire partials
# ---------------------------------------------------------------------------

def test_wire_compressed_partials_parity_and_ratio():
    """Compressed partials merge bit-identically and the wire bytes drop
    by a large factor on the RLE-friendly rollup shape (the repeated
    per-bucket states the wire rle/narrow encodings exist for)."""
    from druid_tpu.cluster import wire

    seg = _rollup_segment(rows=8192)
    q = GroupByQuery.of(
        "roll", [IV], [DefaultDimensionSpec("dimA")],
        [CountAggregator("rows"), LongSumAggregator("c", "cnt")],
        granularity="hour")
    # clamp=False is how data nodes actually run (the broker pre-bounds
    # intervals): the per-bucket state arrays span the query interval, and
    # the mostly-empty buckets are exactly what the wire rle encoding eats
    ap = engines.make_aggregate_partials(q, [seg], clamp=False)
    raw = wire.dumps_partials(ap, served=[str(seg.id)], compress=False)
    comp = wire.dumps_partials(ap, served=[str(seg.id)], compress=True)
    assert len(comp) < len(raw)
    assert len(raw) / len(comp) >= 4.0, (len(raw), len(comp))
    ap_raw, served_raw, _ = wire.loads_partials(raw)
    ap_comp, served_comp, _ = wire.loads_partials(comp)
    assert served_raw == served_comp == {str(seg.id)}
    assert engines.finish_groupby(q, ap_comp) \
        == engines.finish_groupby(q, ap_raw) \
        == engines.finish_groupby(q, ap)


def test_wire_uncompressed_payload_unchanged():
    """compress=False must stay wire VERSION 1 — old brokers keep
    decoding new nodes' raw payloads."""
    from druid_tpu.cluster import wire

    seg = _rollup_segment(rows=1024)
    q = TimeseriesQuery.of("roll", [IV], [CountAggregator("n")],
                           granularity="all")
    ap = engines.make_aggregate_partials(q, [seg])
    raw = wire.dumps_partials(ap, compress=False)
    assert raw[:4] == wire.MAGIC
    assert raw[4] == wire.VERSION


# ---------------------------------------------------------------------------
# Golden fixtures: bytes a past release wrote must keep loading
# ---------------------------------------------------------------------------

def _golden_segment():
    """Fully deterministic tiny segment — NO RNG, so regeneration is
    byte-stable across numpy versions."""
    rows, card = 256, 8
    b = SegmentBuilder("golden", IV, version="v0", partition=0)
    b.add_columns(
        IV.start + (np.arange(rows, dtype=np.int64) // 16),
        {"dim": np.repeat([f"g{i}" for i in range(card)],
                          rows // card).tolist()},
        {"cnt": np.ones(rows, dtype=np.int64),
         "lng": (np.arange(rows, dtype=np.int64) * 7) % 13,
         "dbl": np.tile(np.array([0.5, -0.0, np.nan, 8.0]),
                        rows // 4).astype(np.float64)})
    return b.build()


def regen_golden():
    for name, persist in (("segment_v1", persist_segment),
                          ("segment_v2", persist_segment_v2)):
        d = GOLDEN_DIR / name
        if d.exists():
            shutil.rmtree(d)
        persist(_golden_segment(), str(d))
        print(f"regenerated {d}")


@pytest.mark.parametrize("name,version", [("segment_v1", FORMAT_VERSION),
                                          ("segment_v2", FORMAT_VERSION_V2)])
def test_golden_fixture_loads_and_answers(name, version):
    d = str(GOLDEN_DIR / name)
    assert os.path.isdir(d), \
        f"{d} missing — run `python tests/test_format_v2.py regen`"
    assert read_format_version(d) == version
    loaded = load_segment(d)
    fresh = _golden_segment()
    _assert_bit_identical(fresh, loaded)
    q = GroupByQuery.of(
        "golden", [IV], [DefaultDimensionSpec("dim")],
        [CountAggregator("rows"), LongSumAggregator("s", "lng"),
         DoubleSumAggregator("x", "dbl")], granularity="all")
    assert _rows_equal(QueryExecutor([loaded]).run(q),
                       QueryExecutor([fresh]).run(q))


def test_golden_v2_raw_parts_byte_stable(tmp_path):
    """The V2 raw parts (rle tables, pack words — everything but the
    zlib/lz4-compressed block parts, whose bytes may legally vary with
    codec version) must persist byte-identically today: the format is
    frozen, not merely readable."""
    from druid_tpu.storage.smoosh import SmooshedFileMapper

    d = str(tmp_path / "fresh")
    persist_segment_v2(_golden_segment(), d)
    with SmooshedFileMapper(str(GOLDEN_DIR / "segment_v2")) as golden, \
            SmooshedFileMapper(d) as fresh:
        assert set(golden.names()) == set(fresh.names())
        for part in golden.names():
            if ".rle." in part or part.endswith((".pack", ".lz4")):
                assert bytes(golden.part(part)) == bytes(fresh.part(part)), \
                    f"raw part {part} drifted"


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "regen":
        regen_golden()
    else:
        print("usage: python tests/test_format_v2.py regen", file=sys.stderr)
        sys.exit(2)
