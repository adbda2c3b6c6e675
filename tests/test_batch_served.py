"""The batched path as a deployment serves it (ISSUE 30): broker HTTP → data
node → `batching.run_with_batching`, against a plain numpy reference.

One request over 29 small segments of unequal row counts — 26 on one rung of
the row ladder, 3 on the next — is two shape buckets, a 16 + 8 + 2 chunking
of the first, a 2 of the second and ONE straggler (what the K ladder
leaves). topN, a day timeseries (whose first bucket lies more than int32
milliseconds before the last segments' rows) and a small groupBy answer
exactly; `batching.stats()` and the `engine/batch/*`, `datanode/query` and
`broker/query` spans say what ran; a repeat is answered by a cache; a request
whose segments are partly past BLOCKED_GROUP_LIMIT falls back for those and
says why.
"""
import ast
import inspect
import json
import urllib.request

import numpy as np
import pytest

from druid_tpu.cluster import (Broker, DataNode, DataNodeServer,
                               InventoryView, LruCache, RemoteDataNodeClient,
                               descriptor_for)
from druid_tpu.data.dictionary import Dictionary
from druid_tpu.data.segment import (NumericColumn, Segment, SegmentId,
                                    StringDimColumn, ValueType)
from druid_tpu.engine import batching, contracts, grouping
from druid_tpu.obs import trace as qtrace
from druid_tpu.server import QueryHttpServer, QueryLifecycle
from druid_tpu.utils.intervals import Interval

DAY_MS = 86_400_000
FIRST_MS = 1_767_225_600_000                 # 2026-01-01T00:00:00Z
DATASOURCE = "small"
#: 26 segments on the 2,048-row rung, then 3 on the 4,096-row rung
ROWS = [1100 + 31 * i for i in range(26)] + [2500, 3000, 3500]


def _raw(index: int, rows: int, wide: bool = False) -> dict:
    """Raw columns of day segment `index`: numbers, as a reference reads
    them. `dimC` has 3,000 values in a `wide` segment, else 30."""
    rng = np.random.default_rng([30, index])
    return {"__time": FIRST_MS + index * DAY_MS
            + np.sort(rng.integers(0, DAY_MS, size=rows)).astype(np.int64),
            "dimA": (np.arange(rows) + index) % 40,
            "dimB": rng.integers(0, 7, size=rows),
            "dimC": np.arange(rows) % (3000 if wide else 30),
            "metLong": rng.integers(0, 100, size=rows)}


def _segment(index: int, raw: dict) -> Segment:
    dims = {}
    for name in ("dimA", "dimB", "dimC"):
        values = sorted({str(v) for v in raw[name].tolist()})
        lut = {v: i for i, v in enumerate(values)}
        dims[name] = StringDimColumn(
            np.asarray([lut[str(v)] for v in raw[name].tolist()],
                       dtype=np.int32), Dictionary(values))
    start = FIRST_MS + index * DAY_MS
    sid = SegmentId(DATASOURCE, Interval(start, start + DAY_MS), "v1", 0)
    return Segment(sid, raw["__time"], dims,
                   {"metLong": NumericColumn(raw["metLong"].astype(np.int64),
                                             ValueType.LONG)},
                   sorted_by_time=True)


class _Deployment:
    """One historical with the default segment cache, one broker with its
    result cache, both over real sockets — what `cli.build_historical` and
    `cli.build_broker` put together."""

    def __init__(self, raws):
        self.raws = raws
        self.node = DataNode("small-node", cache=LruCache())
        self.srv = DataNodeServer(self.node).start()
        self.view = InventoryView()
        self.view.register(RemoteDataNodeClient(self.node.name, self.srv.url))
        for index, raw in raws.items():
            seg = _segment(index, raw)
            self.node.load_segment(seg)
            self.view.announce(self.node.name, descriptor_for(seg))
        self.broker = Broker(self.view, cache=LruCache())
        self.uncached = Broker(self.view)      # no result cache in front
        self.http = QueryHttpServer(QueryLifecycle(self.broker)).start()

    def post(self, query: dict):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.http.port}/druid/v2",
            data=json.dumps(query).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read())

    def stop(self):
        self.http.stop()
        self.srv.stop()
        self.broker.stop()
        self.uncached.stop()


@pytest.fixture(scope="module")
def deployment():
    d = _Deployment({i: _raw(i, rows) for i, rows in enumerate(ROWS)})
    yield d
    d.stop()


def _days(first: int, end: int) -> list:
    return [f"{Interval(FIRST_MS + first * DAY_MS, FIRST_MS + end * DAY_MS)}"]


def _query(kind: str, qid: str, first: int = 0, end: int = len(ROWS)) -> dict:
    sums = [{"type": "count", "name": "rows"},
            {"type": "longSum", "name": "lsum", "fieldName": "metLong"}]
    base = {"dataSource": DATASOURCE, "intervals": _days(first, end),
            "context": {"queryId": qid}}
    if kind == "topN":
        return dict(base, queryType="topN", granularity="all",
                    dimension="dimA", metric="lsum", threshold=5,
                    aggregations=sums[1:] + [
                        {"type": "longMax", "name": "lmax",
                         "fieldName": "metLong"}],
                    filter={"type": "selector", "dimension": "dimB",
                            "value": "3"})
    if kind == "timeseries":
        return dict(base, queryType="timeseries", granularity="day",
                    aggregations=sums,
                    filter={"type": "selector", "dimension": "dimA",
                            "value": "17"})
    return dict(base, queryType="groupBy", granularity="all",
                dimensions=["dimB" if kind == "groupBy" else "dimC"],
                aggregations=sums,
                filter={"type": "selector", "dimension": "dimA",
                        "value": "17"})


def _reference(raws: dict, query: dict) -> list:
    """The answer in plain numpy over the raw columns."""
    lo, hi = (FIRST_MS + DAY_MS * d for d in query["_days"])
    flt = query["filter"]
    cols = {k: np.concatenate([r[k] for r in raws.values()])
            for k in next(iter(raws.values()))}
    keep = (cols["__time"] >= lo) & (cols["__time"] < hi) \
        & (cols[flt["dimension"]] == int(flt["value"]))
    met = cols["metLong"][keep]
    if query["queryType"] == "timeseries":
        day = (cols["__time"][keep] - lo) // DAY_MS
        return [{"timestamp": lo + d * DAY_MS,
                 "result": {"rows": int((day == d).sum()),
                            "lsum": int(met[day == d].sum())}}
                for d in range((hi - lo) // DAY_MS)]
    dim = query.get("dimension") or query["dimensions"][0]
    key = cols[dim][keep]
    groups = {}
    for v in np.unique(key).tolist():
        m = met[key == v]
        groups[str(v)] = {"rows": int(len(m)), "lsum": int(m.sum()),
                          "lmax": int(m.max())}
    if query["queryType"] == "topN":
        top = sorted(groups.items(), key=lambda kv: -kv[1]["lsum"])[:5]
        return [{"timestamp": lo, "result": [
            {dim: v, "lsum": g["lsum"], "lmax": g["lmax"]} for v, g in top]}]
    return [{"version": "v1", "timestamp": lo,
             "event": {dim: v, "rows": g["rows"], "lsum": g["lsum"]}}
            for v, g in sorted(groups.items())]


def _answer(d: _Deployment, kind: str, qid: str, first=0, end=len(ROWS)):
    query = _query(kind, qid, first, end)
    got = d.post(query)
    want = _reference(d.raws, dict(query, _days=(first, end)))
    return got, want


def _spans(qid: str, name: str) -> list:
    return [s for s in qtrace.trace_store().spans(qid) if s["name"] == name]


def _delta(before: dict) -> dict:
    after = batching.stats().snapshot()
    return {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("kind", ["topN", "timeseries", "groupBy"])
def test_two_buckets_a_16_8_2_chunking_and_a_straggler(deployment, kind):
    before = batching.stats().snapshot()
    qid = f"served-{kind}"
    got, want = _answer(deployment, kind, qid)
    assert got == want
    if kind == "timeseries":
        # every day has its segment's rows, the days past int32 ms included
        assert len(want) == len(ROWS)
        assert all(r["result"]["rows"] > 0 for r in want)
    moved = _delta(before)
    assert (moved["batches"], moved["batchedSegments"],
            moved["fallbackSegments"]) == (4, 28, 1)
    rungs = [batching.row_rung(n) for n in ROWS]
    assert moved["stackedSlots"] == sum(rungs[:26]) + sum(rungs[26:28])
    assert moved["stackedRows"] == sum(ROWS[:28])

    plan, = _spans(qid, "engine/batch/plan")
    assert {k: plan["attrs"][k] for k in ("segments", "eligible", "buckets",
                                          "chunks", "stragglers", "reason")} \
        == {"segments": 29, "eligible": 29, "buckets": 2, "chunks": 4,
            "stragglers": 1, "reason": "ladder_remainder"}
    dispatches = _spans(qid, "engine/batch/dispatch")
    assert sorted(s["attrs"]["segments"] for s in dispatches) == [2, 2, 8, 16]
    for s in dispatches:
        a = s["attrs"]
        assert a["paddedRows"] == a["segments"] * a["rows"]
        assert 0 < a["realRows"] < a["paddedRows"]
        assert a["program"] == f"batch_agg_{a['strategy']}"
        assert a["program"] in contracts.PROGRAM_NAMES
    assert sum(s["attrs"]["realRows"] for s in dispatches) == sum(ROWS[:28])
    # the straggler ran alone, through the plan made for it
    alone, = _spans(qid, "engine/segment")
    assert alone["attrs"]["rows"] == ROWS[28]
    merge, = _spans(qid, "datanode/merge")
    assert merge["attrs"]["partialsIn"] == 29
    root, = _spans(qid, "broker/query")
    assert root["attrs"]["resultCacheHit"] == 0


def test_a_repeat_is_answered_by_a_cache_and_equals_the_first(deployment):
    first, want = _answer(deployment, "groupBy", "repeat-0", 2, 28)
    assert first == want
    before = batching.stats().snapshot()
    again, _ = _answer(deployment, "groupBy", "repeat-1", 2, 28)
    assert again == first
    # the broker's result cache: no request reached the node
    root, = _spans("repeat-1", "broker/query")
    assert root["attrs"]["resultCacheHit"] == 1
    assert not _spans("repeat-1", "datanode/query")
    # a broker without one: the node's per-segment cache holds every partial
    query = _query("groupBy", "repeat-2", 2, 28)
    assert deployment.uncached.run_json(query) == first
    assert _spans("repeat-2", "datanode/query")
    assert not _spans("repeat-2", "engine/batch/plan")
    merge, = _spans("repeat-2", "datanode/merge")
    assert merge["attrs"]["partialsIn"] == 26
    assert not any(_delta(before).values())
    # the key holds the query's intervals: another window of the same
    # segments is computed again
    other, want = _answer(deployment, "groupBy", "repeat-3", 2, 27)
    assert other == want
    plan, = _spans("repeat-3", "engine/batch/plan")
    assert plan["attrs"]["segments"] == 25


def test_segments_past_the_group_limit_fall_back_and_say_why():
    """Four segments whose `dimC` has 3,000 values (4,096 dense groups, past
    BLOCKED_GROUP_LIMIT) beside four with 30: the four small ones batch,
    the four wide ones run alone, and the plan span says why."""
    assert grouping.BLOCKED_GROUP_LIMIT < 3000
    d = _Deployment({i: _raw(i, 3500, wide=i % 2 == 1) for i in range(8)})
    try:
        before = batching.stats().snapshot()
        got, want = _answer(d, "groupBy-wide", "wide-0", 0, 8)
        assert got == want and len(want) > 30
        moved = _delta(before)
        assert (moved["batches"], moved["batchedSegments"],
                moved["fallbackSegments"]) == (1, 4, 4)
        plan, = _spans("wide-0", "engine/batch/plan")
        assert {k: plan["attrs"][k] for k in ("eligible", "buckets", "chunks",
                                              "stragglers", "reason")} \
            == {"eligible": 4, "buckets": 1, "chunks": 1, "stragglers": 4,
                "reason": "group_space_over_limit"}
        assert len(_spans("wide-0", "engine/segment")) == 4
        # none batches: every segment of the batch-planned request counts
        before = batching.stats().snapshot()
        got, want = _answer(d, "groupBy-wide", "wide-1", 1, 2)
        assert got == want
        assert not _spans("wide-1", "engine/batch/plan")   # one segment
        got, want = _answer(d, "groupBy-wide", "wide-2", 1, 4)
        assert got == want
        moved = _delta(before)
        assert (moved["batches"], moved["fallbackSegments"]) == (0, 3)
        plan, = _spans("wide-2", "engine/batch/plan")
        assert (plan["attrs"]["stragglers"], plan["attrs"]["chunks"]) == (3, 0)
        assert plan["attrs"]["reason"] == "group_space_over_limit"
    finally:
        d.stop()


def test_every_fall_back_reason_is_documented_and_used():
    """`contracts.BATCH_FALLBACK_REASONS` is the closed set: every `_alone`
    call of engine/batching.py names one, and each is named somewhere."""
    tree = ast.parse(inspect.getsource(batching))
    used = [n.args[1].value for n in ast.walk(tree)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
            and n.func.id == "_alone"]
    assert sorted(set(used)) == sorted(contracts.BATCH_FALLBACK_REASONS)
    assert len(used) == len(set(used))
    with pytest.raises(ValueError, match="not a documented"):
        contracts.batch_fallback_reason("because")
