"""A span says how long its thread WORKED, and a trace keeps its root
(ISSUE 36).

(a) `cpuMs` on every finished span: CPU the opening thread burned between
open and close — near `durationMs` around a busy loop, near 0 around a
sleep or a lock another thread holds; on children, roots, late spans and
the spans of an `attach`ed worker; summed by `phase_breakdown`.
(b) ONE cap policy for a trace in the store and a root's response
collector: past the cap leaves are dropped and counted, ancestors kept, so
the metrics that read `query`, `datanode/query` and `engine/partials`
still read; the store is bounded by the spans it holds in all; the request
that sank PR 33 (480 per-segment programs) is whole under the default cap.
(c) the fetch in three parts, the batched enqueue and the async-copy start
under names: exactly the new spans, on a batched and a per-segment request.

Counts and orderings on the CPU, never a rate."""
import threading
import time
import types

import pytest

from benchmark.harness import layers
from benchmark.tests.util import BENCH
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine import QueryExecutor, grouping
from druid_tpu.obs import dispatch as dispatch_mod
from druid_tpu.obs import trace as qtrace
from druid_tpu.utils.intervals import Interval
from tests.test_batch_served import ROWS, _raw, _segment
from tests.test_batch_served import _query as _small_query
from tests.test_deferred_fetch import _named, _traced
from tests.test_qtrace_phases import _Cluster

# ---------------------------------------------------------------------------
# (a) cpuMs
# ---------------------------------------------------------------------------


def _burn(ms: float) -> None:
    end = time.thread_time() + ms / 1000.0
    while time.thread_time() < end:
        sum(range(200))


def _wait_sleep() -> None:
    time.sleep(0.06)


def _wait_lock() -> None:
    """Block on a lock another thread holds for 60 ms."""
    lock, held = threading.Lock(), threading.Event()

    def holder():
        with lock:
            held.set()
            time.sleep(0.06)
    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(5.0)
    with lock:
        pass
    t.join(5.0)
    assert not t.is_alive()


def _one_span(body) -> dict:
    store = qtrace.TraceStore()
    with qtrace.root_span("query", service="svc", store=store) as root:
        with qtrace.span("phase"):
            body()
    phase, = _named(store.spans(root.trace_id), "phase")
    return phase


def test_cpu_ms_of_a_busy_span_is_near_its_duration():
    # a shared machine can take the core away mid-loop (wall grows, CPU
    # does not): the best of a few attempts has to be within 30%
    gaps = []
    for _ in range(6):
        s = _one_span(lambda: _burn(60.0))
        assert s["attrs"]["cpuMs"] >= 59.0
        gaps.append(abs(s["durationMs"] - s["attrs"]["cpuMs"])
                    / s["durationMs"])
        if gaps[-1] <= 0.3:
            break
    assert min(gaps) <= 0.3, gaps


@pytest.mark.parametrize("wait", [_wait_sleep, _wait_lock],
                         ids=["sleep", "lock"])
def test_cpu_ms_of_a_waiting_span_is_near_zero(wait):
    s = _one_span(wait)
    assert s["durationMs"] >= 50.0
    assert 0 <= s["attrs"]["cpuMs"] < 5.0


def _every_kind_of_span() -> dict:
    """{kind: finished span dict}: a root with a collector, a child, the
    child of an `attach`ed worker thread, two late spans."""
    store = qtrace.TraceStore()
    with qtrace.root_span("datanode/query", service="svc", store=store,
                          collect=True) as root:
        with qtrace.span("child"):
            _burn(2.0)

        def worker():
            with qtrace.attach(root), qtrace.span("attached"):
                _burn(2.0)
        t = threading.Thread(target=worker)
        t.start()
        t.join(5.0)
        assert not t.is_alive()
    with qtrace.late_span(root, "late", sibling=True):
        _burn(2.0)
    with qtrace.late_span(root, "late-child"):
        pass
    spans = store.spans(root.trace_id)
    assert len(spans) == 5
    assert {s["spanId"] for s in root.collected()} < \
        {s["spanId"] for s in spans}
    return {s["name"]: s for s in spans}


@pytest.mark.parametrize("name", ["datanode/query", "child", "attached",
                                  "late", "late-child"])
def test_cpu_ms_is_on_every_kind_of_span(name):
    s = _every_kind_of_span()[name]
    assert 0 <= s["attrs"]["cpuMs"] <= s["durationMs"] + 1.0
    if name in ("child", "attached", "late"):
        assert s["attrs"]["cpuMs"] >= 1.9
    if name == "datanode/query":
        # the root's thread did the child's work, not the worker's
        assert 1.9 <= s["attrs"]["cpuMs"]
        assert "droppedSpans" not in s["attrs"]


def test_phase_breakdown_sums_cpu_beside_wall():
    spans = [{"name": "engine/fetch", "durationMs": 10.0,
              "attrs": {"cpuMs": 2.5}},
             {"name": "engine/fetch", "durationMs": 5.0,
              "attrs": {"cpuMs": 0.5}},
             # a node of an older build: no cpuMs, and no key made up for it
             {"name": "datanode/encode", "durationMs": 4.0, "attrs": {}},
             {"name": "datanode/encode", "durationMs": 1.0},
             {"durationMs": 3.0, "attrs": {"cpuMs": 3.0}},
             {"name": "open", "durationMs": None, "attrs": {"cpuMs": 1.0}}]
    assert qtrace.phase_breakdown(spans) == {
        "engine/fetch": 15.0, "engine/fetch:cpu": 3.0,
        "datanode/encode": 5.0}
    live = _every_kind_of_span().values()
    got = qtrace.phase_breakdown(live)
    assert {f"{s['name']}:cpu" for s in live} < set(got)
    assert all(got[f"{s['name']}:cpu"] == s["attrs"]["cpuMs"] for s in live)


# ---------------------------------------------------------------------------
# (b) the cap
# ---------------------------------------------------------------------------

#: an `engine/segment` of the per-segment path: seven leaves under it
SEGMENT_LEAVES = ("engine/plan", "engine/filter/words", "engine/stage",
                  "engine/filter/words", "engine/build", "engine/dispatch",
                  "engine/fetch/start")
ANCESTORS = ["query", "broker/node", "datanode/query", "engine/partials"]


def _emit_request(segments: int, leaves=SEGMENT_LEAVES) -> None:
    """Spans in the shape and ORDER the engine emits them, under the root
    open on this thread: `engine/partials` over `segments` ×
    (`engine/segment` + its leaves), every child closed before its parent."""
    with qtrace.span("engine/partials"):
        for _ in range(segments):
            with qtrace.span("engine/segment"):
                for name in leaves:
                    with qtrace.span(name):
                        pass


def _request_spans(sink: str, segments: int, cap: int):
    """One synthetic request through a store of `cap` spans a trace (None:
    the default); returns (what the sink kept, what it says it dropped)."""
    store = qtrace.TraceStore() if cap is None \
        else qtrace.TraceStore(max_spans_per_trace=cap)
    if sink == "store":
        with qtrace.root_span("query", service="b", store=store) as root:
            with qtrace.span("broker/node"):
                with qtrace.span("datanode/query"):
                    _emit_request(segments)
        got = store.get(root.trace_id)
        assert got["spanCount"] == len(got["spans"])
        return got["spans"], got["droppedSpans"]
    # the node's side: its root re-rooted under the broker's span, the
    # request's spans collected for the response payload
    with qtrace.root_span("query", service="b",
                          store=qtrace.TraceStore()) as outer:
        with qtrace.span("broker/node") as bn:
            pass
    posted = types.SimpleNamespace(
        query_type="groupBy", datasource="x",
        context_map={qtrace.TRACEPARENT_KEY: f"{bn.trace_id}:{bn.span_id}"})
    with qtrace.root_span("datanode/query", posted, service="n", store=store,
                          collect=True) as root:
        _emit_request(segments)
    assert (root.trace_id, root.parent_id) == (bn.trace_id, bn.span_id)
    kept = root.collected()
    # the broker's own two spans close AFTER the payload arrived
    kept += [bn.to_json(), outer.to_json()]
    return kept, root._collector.dropped


@pytest.mark.parametrize("sink", ["store", "collector"])
@pytest.mark.parametrize("segments,cap", [(375, 2048), (480, 2048),
                                          (480, None)],
                         ids=["3000-spans", "480x8-at-2048", "480x8"])
def test_a_capped_trace_keeps_its_ancestors(sink, segments, cap):
    before = dispatch_mod.stats().snapshot()["trace_dropped_spans"]
    spans, dropped = _request_spans(sink, segments, cap)
    counted = dispatch_mod.stats().snapshot()["trace_dropped_spans"] - before
    emitted = segments * (1 + len(SEGMENT_LEAVES)) + 1 \
        + (3 if sink == "store" else 1)
    names = [s["name"] for s in spans]
    for name in ANCESTORS:
        assert names.count(name) == 1, name
    # every segment span is an ancestor of leaves: all kept, whatever the cap
    assert names.count("engine/segment") == segments
    assert len({s["spanId"] for s in spans}) == len(spans)
    if cap is None:
        assert dropped == 0
    else:
        assert dropped > 0
        assert all(s["name"] in SEGMENT_LEAVES for s in spans
                   if s["name"] not in ANCESTORS + ["engine/segment"])
    # what is gone is leaves, each counted once; process-wide a drop each:
    # a collecting root's spans also land in its node's store, which drops
    # the same leaves
    have = len(spans) - (2 if sink == "collector" else 0)
    assert dropped == emitted - have
    assert counted == dropped * (2 if sink == "collector" else 1)
    root, = _named(spans, "query" if sink == "store" else "datanode/query")
    assert root["attrs"].get("droppedSpans", 0) == dropped
    # the metrics that read the ancestors still read
    specs = layers.load_layers(BENCH)
    requests = [{"record": {"send_s": 0.0, "due_s": 0.0, "done_s": 60.0},
                 "spans": spans}]
    for metric in ("http.outside_ms", "engine.partials_ms",
                   "datanode.self_ms", "broker.self_ms"):
        value = layers.evaluate(specs[metric], requests, {}, {}, None)
        assert value is not None and value >= 0, metric


def test_a_parent_that_closes_past_the_cap_is_kept_only_if_named():
    """Leaves past the cap are dropped whether or not they came with a
    parent id; a span is an ancestor only by an EARLIER arrival's word."""
    store = qtrace.TraceStore(max_spans_per_trace=4)

    def add(sid, parent):
        store.add_json({"traceId": "t", "spanId": sid, "parentId": parent,
                        "name": sid, "startMs": 0})
    for sid, parent in [("a", "p1"), ("b", "p1"), ("c", "p2"), ("d", "p2")]:
        add(sid, parent)
    add("e", "p3")          # dropped leaf, but it names p3
    add("x", None)          # nobody named x: dropped
    add("p1", "root")
    add("p2", "root")
    add("p3", "root")       # kept on a DROPPED child's word
    add("root", None)
    add("p1", "root")       # a duplicate of a kept span is not a drop
    got = store.get("t")
    assert [s["spanId"] for s in got["spans"]] == [
        "a", "b", "c", "d", "p1", "p2", "p3", "root"]
    assert got["droppedSpans"] == 2 == store.dropped("t")
    assert store.dropped("unknown") == 0


def test_the_parent_id_set_is_bounded():
    """A runaway producer of spans that each name a new parent cannot grow
    a trace without bound: the set stops at the cap, so a buffer holds at
    most 2 x cap spans."""
    store = qtrace.TraceStore(max_spans_per_trace=8)
    for i in range(100):
        store.add_json({"traceId": "t", "spanId": f"s{i}",
                        "parentId": f"s{i + 1}", "name": "x", "startMs": i})
    got = store.get("t")
    assert got["spanCount"] <= 16
    assert got["spanCount"] + got["droppedSpans"] == 100


def test_the_store_is_bounded_by_the_spans_it_holds():
    store = qtrace.TraceStore(max_traces=4, max_spans_per_trace=5000)
    assert store.max_total_spans == 4 * qtrace.SPANS_PER_SLOT
    assert qtrace.TraceStore().max_total_spans == 256 * 2048
    assert qtrace.TraceStore().max_spans_per_trace == 8192

    def fill(tid, n):
        for i in range(n):
            store.add_json({"traceId": tid, "spanId": f"{tid}-{i}",
                            "name": "x", "startMs": i})
    fill("t0", 3000)
    fill("t1", 3000)
    assert store.trace_ids() == ["t0", "t1"]
    fill("t2", 3000)                    # 9,000 > 8,192: the oldest goes
    assert store.trace_ids() == ["t1", "t2"]
    assert store.get("t0") is None
    assert store.get("t2")["spanCount"] == 3000
    fill("t3", 100)
    fill("t4", 100)
    assert store.trace_ids() == ["t1", "t2", "t3", "t4"]
    fill("t5", 1)                       # the ring's own bound still holds
    assert store.trace_ids() == ["t2", "t3", "t4", "t5"]
    # one trace alone may pass the total: it is never evicted for itself
    lone = qtrace.TraceStore(max_traces=1, max_spans_per_trace=5000)
    for i in range(3000):
        lone.add_json({"traceId": "t", "spanId": f"s{i}", "name": "x",
                       "startMs": i})
    assert lone.get("t")["spanCount"] == 3000


def test_get_sorts_a_copy_outside_the_lock():
    """`get` hands out a sorted COPY: a later span does not show in it, and
    the store's own list keeps arrival order."""
    store = qtrace.TraceStore()
    for i in (3, 1, 2):
        store.add_json({"traceId": "t", "spanId": f"s{i}", "name": "x",
                        "startMs": i})
    got = store.get("t")
    store.add_json({"traceId": "t", "spanId": "s0", "name": "x",
                    "startMs": 0})
    assert [s["spanId"] for s in got["spans"]] == ["s1", "s2", "s3"]
    assert [s["spanId"] for s in store.get("t")["spans"]] == \
        ["s0", "s1", "s2", "s3"]
    assert not store._lock.locked()


# ---------------------------------------------------------------------------
# the request that sank PR 33, rehearsed
# ---------------------------------------------------------------------------

WIDE_SCHEMA = (ColumnSpec("dimSequential", "string", cardinality=125),
               ColumnSpec("dimZipf", "string", cardinality=40,
                          distribution="zipf"),
               ColumnSpec("metLongSequential", "long", low=0, high=10_000),
               ColumnSpec("metFloatNormal", "float", distribution="normal",
                          mean=5000.0, std=1.0))
WIDE_DAYS = Interval.of("2026-03-01", "2026-03-21")


def test_480_per_segment_programs_leave_a_whole_trace():
    """`analyst-groupby`'s shape — two string dimensions whose group space
    (scaled: 125 x 40 = 5,000, still past the stacked program's limit) puts
    every segment on the per-segment path, count + longSum + floatMax under
    a numeric bound — over 480 small segments, broker → HTTP → node: ~4,400
    spans. At the old cap of 2,048 the trace had no `query`, no
    `datanode/query`, no `engine/partials`."""
    segs = DataGenerator(WIDE_SCHEMA, seed=36).segments(
        480, 64, WIDE_DAYS, datasource="phases")
    cluster = _Cluster(segs, own_store=True)
    cluster.tag = "pr33"
    qid = "pr33-480-segments"
    query = {"queryType": "groupBy", "dataSource": "phases",
             "intervals": [str(WIDE_DAYS)], "granularity": "all",
             "dimensions": ["dimSequential", "dimZipf"],
             "aggregations": [
                 {"type": "count", "name": "rows"},
                 {"type": "longSum", "name": "lsum",
                  "fieldName": "metLongSequential"},
                 {"type": "floatMax", "name": "fmax",
                  "fieldName": "metFloatNormal"}],
             "filter": {"type": "bound", "dimension": "metLongSequential",
                        "lower": "100", "upper": "9900",
                        "ordering": "numeric"},
             "context": {"queryId": qid}}
    before = dispatch_mod.stats().snapshot()
    try:
        send = time.monotonic()
        rows = cluster.post(query)
        done = time.monotonic()
        spans = cluster.trace(qid)
        node_side = cluster.node_store.get(qid)
    finally:
        cluster.stop()
    after = dispatch_mod.stats().snapshot()
    assert len(rows) > 3000
    assert after["total"] - before["total"] == 480
    assert after["trace_dropped_spans"] == before["trace_dropped_spans"]
    plan, = _named(spans, "engine/batch/plan")
    assert plan["attrs"]["stragglers"] == 480
    assert plan["attrs"]["reason"] == "group_space_over_limit"
    assert len(_named(spans, "engine/segment")) == 480 \
        == len(_named(spans, "engine/fetch/start"))
    assert 4000 < len(spans) <= qtrace.trace_store().max_spans_per_trace
    got = qtrace.trace_store().get(qid)
    assert got["droppedSpans"] == 0 == node_side["droppedSpans"]
    for name in ANCESTORS + ["http/respond", "datanode/encode"]:
        assert len(_named(spans, name)) == 1, name
    assert all("droppedSpans" not in s["attrs"] for s in spans)
    assert all(0 <= s["attrs"]["cpuMs"] <= s["durationMs"] + 1.0
               for s in spans)
    specs = layers.load_layers(BENCH)
    requests = [{"record": {"send_s": send, "due_s": send, "done_s": done},
                 "spans": spans}]
    outside = layers.evaluate(specs["http.outside_ms"], requests, {}, {},
                              None)
    assert outside is not None and 0 <= outside < (done - send) * 1000.0
    assert layers.evaluate(specs["engine.partials_ms"], requests, {}, {},
                           None) > 0


# ---------------------------------------------------------------------------
# (c) the new spans, by name and by count
# ---------------------------------------------------------------------------

FETCH_PARTS = ["engine/fetch/d2h", "engine/fetch/post", "engine/fetch/wait"]


@pytest.fixture(scope="module")
def small_segments():
    """tests/test_batch_served.py's 29 segments: four chunks (16 + 8 + 2,
    2) and one straggler."""
    return [_segment(i, _raw(i, rows)) for i, rows in enumerate(ROWS)]


def _check_fetches(spans, fetches: int) -> float:
    """Every `engine/fetch` of the trace has exactly the three parts, one
    after another and inside it; returns the largest share of a fetch that
    lies outside its parts."""
    by_id = {s["spanId"]: s for s in spans}
    found = _named(spans, "engine/fetch")
    assert len(found) == fetches
    outside = 0.0
    for fetch in found:
        parts = sorted((s for s in spans if s["parentId"] == fetch["spanId"]),
                       key=lambda s: s["startMs"])
        assert [s["name"] for s in parts] == ["engine/fetch/wait",
                                              "engine/fetch/d2h",
                                              "engine/fetch/post"]
        total = sum(s["durationMs"] for s in parts)
        assert total <= fetch["durationMs"] + 0.01
        outside = max(outside, (fetch["durationMs"] - total)
                      / max(fetch["durationMs"], 1e-9))
        assert fetch["attrs"]["bytes"] > 0 and fetch["attrs"]["programs"] > 0
        assert all("bytes" not in s["attrs"] for s in parts)
    assert sorted(s["name"] for s in spans
                  if s["name"].startswith("engine/fetch/")
                  and s["name"] not in ("engine/fetch/start",
                                        "engine/fetch/release")) == \
        sorted(FETCH_PARTS * fetches)
    for s in _named(spans, "engine/fetch/start"):
        assert by_id[s["parentId"]]["name"] == "engine/partials"
        assert s["attrs"]["leaves"] > 0 and s["attrs"]["bytes"] > 0
    # every fetch is followed by the release of what it fetched: a sibling,
    # not a part (the fetch's three parts stay the whole of it)
    releases = sorted(_named(spans, "engine/fetch/release"),
                      key=lambda s: s["startMs"])
    assert len(releases) == fetches
    for fetch, release in zip(sorted(found, key=lambda s: s["startMs"]),
                              releases):
        assert release["parentId"] == fetch["parentId"]
        assert release["startMs"] >= fetch["startMs"] + fetch["durationMs"] \
            - 0.5
        assert release["attrs"]["segments"] > 0
    return outside


def test_the_three_parts_are_the_whole_fetch(small_segments, monkeypatch):
    """Nothing of a fetch lies outside its three children: they sum to it
    within 2%. A CPU fetch of these segments is ~2 ms and the bookkeeping
    between two spans ~10 us, so the fetch is made long enough to carry it
    (a `post` that sleeps), and a machine that takes the core away between
    two spans gets a few attempts."""
    ex = QueryExecutor(small_segments)
    real = grouping.fetch_partials

    def slow_post(kernel, state, segment):
        time.sleep(0.002)
        return grouping._host_post(kernel, state, segment)
    monkeypatch.setattr(
        grouping, "fetch_partials",
        lambda targets, outs, **kw: real(targets, outs, post=slow_post, **kw))
    shares = []
    for attempt in range(4):
        qid = f"ww-whole-{attempt}"
        query = _small_query("groupBy", qid)
        query["context"]["batchSegments"] = False
        _, spans = _traced(ex, query, qid)
        fetch, = _named(spans, "engine/fetch")
        assert fetch["durationMs"] > 100.0
        shares.append(_check_fetches(spans, 1))
        if shares[-1] <= 0.02:
            break
    assert min(shares) <= 0.02, shares


@pytest.mark.parametrize("kind", ["topN", "timeseries", "groupBy"])
def test_a_batched_request_carries_exactly_the_new_spans(small_segments,
                                                         kind):
    ex = QueryExecutor(small_segments)
    qid = f"ww-batched-{kind}"
    rows, spans = _traced(ex, _small_query(kind, qid), qid)
    assert rows
    dispatches = _named(spans, "engine/batch/dispatch")
    assert sorted(s["attrs"]["segments"] for s in dispatches) == [2, 2, 8, 16]
    partials, = _named(spans, "engine/partials")
    for name in ("engine/batch/blocks", "engine/batch/assemble"):
        found = _named(spans, name)
        assert len(found) == len(dispatches) == 4           # one a chunk
        assert all(s["parentId"] == partials["spanId"] for s in found)
    blocks = _named(spans, "engine/batch/blocks")
    assert sorted(s["attrs"]["segments"] for s in blocks) == [2, 2, 8, 16]
    assert sorted(s["attrs"]["rows"] for s in blocks) == \
        sorted(s["attrs"]["rows"] for s in dispatches)
    assert all(s["attrs"]["sigBytes"] > 0
               for s in _named(spans, "engine/batch/assemble"))
    # a chunk's four phases follow one another
    by_start = sorted((s for s in spans if s["parentId"]
                       == partials["spanId"]), key=lambda s: s["startMs"])
    order = [s["name"] for s in by_start
             if s["name"].startswith("engine/batch/")
             or s["name"] == "engine/filter/words"]
    assert order[1:17] == ["engine/batch/blocks", "engine/filter/words",
                           "engine/batch/assemble",
                           "engine/batch/dispatch"] * 4
    # one start a program enqueued: four chunks and the straggler
    starts = _named(spans, "engine/fetch/start")
    fetch, = _named(spans, "engine/fetch")
    assert len(starts) == 5 == fetch["attrs"]["programs"]
    assert sum(s["attrs"]["bytes"] for s in starts) == fetch["attrs"]["bytes"]
    _check_fetches(spans, 1)
    # the named children cover the partials span's inside: what is new is
    # 4 a chunk less the words it already had, 1 a program, 3 a fetch
    new = [s for s in spans if s["name"] in (
        "engine/batch/blocks", "engine/batch/assemble",
        "engine/fetch/start", "engine/fetch/release", *FETCH_PARTS)]
    assert len(new) == 2 * 4 + 5 + 3 + 1
    release, = _named(spans, "engine/fetch/release")
    assert release["attrs"]["segments"] == 29


def test_a_per_segment_request_carries_exactly_the_new_spans(
        small_segments, monkeypatch):
    ex = QueryExecutor(small_segments)
    query = _small_query("groupBy", "ww-alone")
    query["context"]["batchSegments"] = False
    rows, spans = _traced(ex, query, "ww-alone")
    assert rows
    assert not [s for s in spans if s["name"].startswith("engine/batch/")]
    assert len(_named(spans, "engine/fetch/start")) == 29 \
        == len(_named(spans, "engine/dispatch"))
    _check_fetches(spans, 1)
    # past the byte bound a wave: three parts each, the same rows
    monkeypatch.setattr(grouping, "PENDING_FETCH_BYTES", 1)
    query["context"]["queryId"] = "ww-alone-waves"
    again, spans = _traced(ex, query, "ww-alone-waves")
    assert again == rows
    _check_fetches(spans, 29)
    assert len(_named(spans, "engine/fetch/start")) == 29


def test_the_release_span_is_where_the_outputs_die(small_segments,
                                                   monkeypatch):
    """`run_grouped_aggregates` owns the device outputs alone: every one is
    alive when `engine/fetch/release` opens and gone when it closes, so the
    span times their destructors and nothing frees them later, unnamed."""
    import contextlib
    import weakref

    import jax
    refs, alive = [], {}
    real_fetch, real_span = grouping.fetch_partials, grouping.trace_span

    def spy(targets, outs, **kw):
        refs.extend(weakref.ref(leaf)
                    for leaf in jax.tree_util.tree_leaves(outs)
                    if isinstance(leaf, jax.Array))
        return real_fetch(targets, outs, **kw)

    @contextlib.contextmanager
    def span(name, **attrs):
        with real_span(name, **attrs) as sp:
            if name == "engine/fetch/release":
                alive["open"] = sum(r() is not None for r in refs)
            yield sp
        if name == "engine/fetch/release":
            alive["close"] = sum(r() is not None for r in refs)
    monkeypatch.setattr(grouping, "fetch_partials", spy)
    monkeypatch.setattr(grouping, "trace_span", span)
    ex = QueryExecutor(small_segments)
    rows, _ = _traced(ex, _small_query("topN", "ww-release"), "ww-release")
    assert rows
    assert len(refs) >= 29 * 2          # counts and a state a segment
    assert alive == {"open": len(refs), "close": 0}


def test_a_parents_cpu_holds_its_childrens(small_segments):
    """On one thread a parent's `cpuMs` holds its children's: the outermost
    span's is at least the sum of its direct children's (a clock tick of
    slack a child), at every level of a request's tree."""
    ex = QueryExecutor(small_segments)
    _, spans = _traced(ex, _small_query("topN", "ww-nesting"), "ww-nesting")
    kids = {}
    for s in spans:
        kids.setdefault(s["parentId"], []).append(s)
    checked = 0
    for s in spans:
        below = kids.get(s["spanId"], [])
        if below:
            checked += 1
            assert s["attrs"]["cpuMs"] + 0.01 * len(below) + 0.05 >= \
                sum(c["attrs"]["cpuMs"] for c in below), s["name"]
    assert checked >= 4
