"""Soak gate (tier-1 fast mode): repeated query waves and full server
start/stop cycles must return the process to its resource baseline —
stable project-thread set, stable open-fd table, device-pool resident
bytes back where they started. The leak witness is the measurement
substrate.

The point is the millions-of-cycles story: a service absorbing heavy
traffic does exactly this loop forever, so ANY per-cycle residue — a
serve_forever thread stop() never reaped, a segment whose device blocks
outlive it, an emitter file handle — is a linear leak in production. The
wedged bench runs (rc=124) are this failure class at full size.
"""
import gc
import sys
import urllib.request
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from tools.druidlint.leakwitness import LeakWitness  # noqa: E402

from druid_tpu.cluster.dataserver import DataNodeServer  # noqa: E402
from druid_tpu.cluster.view import DataNode  # noqa: E402
from druid_tpu.data import devicepool  # noqa: E402
from druid_tpu.data.generator import ColumnSpec, DataGenerator  # noqa: E402
from druid_tpu.engine import QueryExecutor  # noqa: E402
from druid_tpu.query.aggregators import (CountAggregator,  # noqa: E402
                                         LongSumAggregator)
from druid_tpu.query.model import (DefaultDimensionSpec,  # noqa: E402
                                   GroupByQuery, TimeseriesQuery)
from druid_tpu.utils.intervals import Interval

DAY = Interval.of("2026-01-01", "2026-01-02")
SCHEMA = (ColumnSpec("d", "string", cardinality=8),
          ColumnSpec("m", "long", low=0, high=100))


def _segments(n=2, rows=512):
    return DataGenerator(SCHEMA, seed=7).segments(
        n, rows, DAY, datasource="soak")


def _queries():
    return [
        TimeseriesQuery.of("soak", [DAY],
                           [CountAggregator("n"),
                            LongSumAggregator("s", "m")],
                           granularity="all"),
        GroupByQuery.of("soak", [DAY], [DefaultDimensionSpec("d")],
                        [CountAggregator("n")], granularity="all"),
    ]


@pytest.fixture()
def witness():
    w = LeakWitness(str(REPO_ROOT)).install()
    try:
        yield w
    finally:
        w.uninstall()


def test_server_start_stop_cycles_return_to_baseline(witness):
    """N full DataNodeServer lifecycles (serve thread, handler requests,
    scheduler-less stop path) + query waves leave no thread, fd, or pool
    residue. This is the exact loop whose per-cycle thread leak the
    leakguard burn-clean pass fixed in five server classes."""
    queries = _queries()

    def cycle():
        segments = _segments()
        node = DataNode("soak-node")
        for s in segments:
            node.load_segment(s)
        srv = DataNodeServer(node).start()
        try:
            # one real HTTP round-trip so the handler path runs too
            with urllib.request.urlopen(f"{srv.url}/status", timeout=10) \
                    as resp:
                resp.read()
            sids = [str(s.id) for s in segments]
            for q in queries:
                node.run_partials(q, sids)
        finally:
            srv.stop()

    cycle()                               # warmup: lazy init + compiles
    base = witness.snapshot()
    for _ in range(3):
        cycle()
    assert witness.leaks(base, grace_s=10.0) == []


def test_query_waves_return_pool_to_baseline(witness, monkeypatch):
    """Repeated executor waves over FRESH segments each wave: when the
    wave's segments die, their device-pool entries must die with them
    (weakref purge + drain) — resident bytes return to baseline instead
    of compounding wave over wave."""
    pool = devicepool.DeviceSegmentPool(budget_bytes=1 << 30)
    monkeypatch.setattr(devicepool, "_POOL", pool)
    queries = _queries()

    def wave():
        segments = _segments()
        ex = QueryExecutor(segments)
        for q in queries:
            ex.run(q)
        assert pool.snapshot().resident_bytes > 0, (
            "wave staged nothing — the measurement is vacuous")

    wave()                                # warmup wave
    gc.collect()
    base = witness.snapshot()
    assert base.pool_resident == 0, (
        "warmup wave's segments still resident at baseline")
    for _ in range(3):
        wave()
    assert witness.leaks(base, grace_s=10.0) == []
    stats = pool.snapshot()
    assert stats.resident_bytes == 0 and stats.entries == 0


def test_release_device_caches_unpins_stacked_segments(witness,
                                                       monkeypatch):
    """The sharded stack cache DELIBERATELY pins whole segment sets in
    HBM (the mmap analog) — which also pins their device-pool entries
    long after the view dropped the segments. That is cache policy, not a
    leak, but a months-long process still needs a way to reclaim it:
    engine.release_device_caches() is that surface, and the session-wide
    leak witness calls it so pinned cache state and real leaks stay
    distinguishable (the full-suite witness first flagged 19MB / 177
    entries of exactly this shape)."""
    from druid_tpu.engine import release_device_caches
    from druid_tpu.parallel import make_mesh

    pool = devicepool.DeviceSegmentPool(budget_bytes=1 << 30)
    monkeypatch.setattr(devicepool, "_POOL", pool)
    base = witness.snapshot()
    segments = _segments()
    # non-mesh wave stages pool entries; mesh wave pins the set in the
    # stack cache
    QueryExecutor(segments).run(_queries()[1])
    QueryExecutor(segments, mesh=make_mesh(2)).run(_queries()[1])
    assert pool.snapshot().resident_bytes > 0
    del segments
    gc.collect()
    assert pool.snapshot().resident_bytes > 0, (
        "expected the stack cache to pin the segments' pool entries — "
        "if this now self-clears, the witness workaround can go too")
    dropped = release_device_caches()
    assert dropped["stack_entries"] >= 1
    assert witness.leaks(base, grace_s=10.0) == []
    assert pool.snapshot().resident_bytes == 0


def test_thread_count_is_stable_across_cycles(witness):
    """Belt-and-braces on the coarsest axis: the absolute thread count
    after the cycles equals the post-warmup baseline (the witness's
    per-site attribution is the diagnostic; this is the invariant)."""
    import threading

    def cycle():
        segments = _segments()
        node = DataNode("soak-node")
        for s in segments:
            node.load_segment(s)
        srv = DataNodeServer(node).start()
        try:
            node.run_partials(_queries()[0], [str(segments[0].id)])
        finally:
            srv.stop()

    cycle()
    base = witness.snapshot()
    base_count = threading.active_count()
    for _ in range(3):
        cycle()
    assert witness.leaks(base, grace_s=10.0) == []
    assert threading.active_count() <= base_count, (
        f"thread count grew {base_count} -> {threading.active_count()}")
