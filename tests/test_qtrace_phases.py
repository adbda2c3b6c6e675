"""qtrace, every millisecond under a named span (ISSUE 24).

One groupBy over three segments through broker → HTTP → data node yields
ONE trace that holds the wire (`broker/node/read`, `datanode/encode`,
`broker/node/decode`), the inside of `engine/partials` per segment, and the
answer's way out (`http/respond`), nested as PERF.md §3 states; compiles are
counted at the backend; every jitted query-path callable carries a
documented name; a profiler session's host plane holds the spans."""
import glob
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from druid_tpu.cluster import (Broker, DataNode, DataNodeServer,
                               InventoryView, RemoteDataNodeClient,
                               descriptor_for)
from druid_tpu.cluster import dataserver
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine import contracts, grouping
from druid_tpu.obs import dispatch as dispatch_mod
from druid_tpu.obs import trace as qtrace
from druid_tpu.server import QueryHttpServer, QueryLifecycle
from druid_tpu.utils.intervals import Interval

DAYS = Interval.of("2026-03-01", "2026-03-04")
SCHEMA = (ColumnSpec("dimA", "string", cardinality=10),
          ColumnSpec("dimB", "string", cardinality=7),
          ColumnSpec("metLong", "long", low=0, high=100))

#: the consecutive phases of one `engine/segment` (grouping.py): the
#: enqueue side of a segment. The wait for the device is the request's ONE
#: `engine/fetch`, beside the segments under `engine/partials`
SEGMENT_PHASES = {"engine/plan", "engine/filter/words", "engine/stage",
                  "engine/build", "engine/dispatch"}


def _segments(n=3, rows=2000, seed=7, days=DAYS):
    return DataGenerator(SCHEMA, seed=seed).segments(n, rows, days,
                                                     datasource="phases")


def _groupby(qid, **ctx):
    return {"queryType": "groupBy", "dataSource": "phases",
            "intervals": ["2026-03-01/2026-03-04"], "granularity": "all",
            "dimensions": ["dimA", "dimB"],
            "aggregations": [{"type": "count", "name": "n"},
                             {"type": "longSum", "name": "s",
                              "fieldName": "metLong"}],
            "context": {"queryId": qid, **ctx}}


class _Cluster:
    """broker → HTTP → ONE data node over real sockets; `own_store` gives
    the node a TraceStore of its own, so its spans can reach the broker's
    only over the wire."""

    def __init__(self, segments, own_store):
        # the process-wide store outlives a test: ids differ per variant
        self.tag = "2s" if own_store else "1s"
        self.node_store = qtrace.TraceStore() if own_store else None
        self.node = DataNode("pnode")
        self.srv = DataNodeServer(self.node,
                                  trace_store=self.node_store).start()
        self.view = InventoryView()
        self.view.register(RemoteDataNodeClient(self.node.name,
                                                self.srv.url))
        for s in segments:
            self.node.load_segment(s)
            self.view.announce(self.node.name, descriptor_for(s))
        self.broker = Broker(self.view)
        self.http = QueryHttpServer(QueryLifecycle(self.broker)).start()

    def post(self, query):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.http.port}/druid/v2",
            data=json.dumps(query).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read())

    def trace(self, qid, until="http/respond"):
        """The assembled trace; `http/respond` closes after the client has
        read the last byte, so wait for it briefly."""
        deadline = time.monotonic() + 5.0
        while True:
            spans = qtrace.trace_store().spans(qid)
            if any(s["name"] == until for s in spans) \
                    or time.monotonic() > deadline:
                return spans
            time.sleep(0.01)

    def stop(self):
        self.http.stop()
        self.srv.stop()
        self.broker.stop()


@pytest.fixture(params=(True, False), ids=("two-stores", "one-store"))
def cluster(request):
    c = _Cluster(_segments(), own_store=request.param)
    yield c
    c.stop()


def _tree(spans):
    by_id = {s["spanId"]: s for s in spans}
    kids = {}
    for s in spans:
        kids.setdefault(s["parentId"], []).append(s)
    return by_id, kids


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


# ---------------------------------------------------------------------------
# (a) (b) (e): one trace, every phase, nested as stated
# ---------------------------------------------------------------------------

def test_one_trace_holds_every_phase(cluster):
    # batchSegments=false: the per-segment path, like 5M-row segments take
    before = dispatch_mod.count()
    qid = f"phases-1-{cluster.tag}"
    rows = cluster.post(_groupby(qid, batchSegments=False))
    dispatched = dispatch_mod.count() - before
    assert rows and dispatched == 3
    spans = cluster.trace(qid)
    by_id, kids = _tree(spans)
    assert len({s["traceId"] for s in spans}) == 1
    assert len(by_id) == len(spans)            # no span landed twice
    roots = [s for s in spans if s["parentId"] is None]
    assert [s["name"] for s in roots] == ["query"]

    # (a) the wire: read and decode under broker/node; the node's root and
    # its late encode span side by side under the same broker/node
    (node,) = _named(spans, "broker/node")
    (read,) = _named(spans, "broker/node/read")
    (decode,) = _named(spans, "broker/node/decode")
    (dn,) = _named(spans, "datanode/query")
    (enc,) = _named(spans, "datanode/encode")
    for s in (read, decode, dn, enc):
        assert s["parentId"] == node["spanId"], s["name"]
    assert enc["service"] == dn["service"] == "pnode"
    assert read["service"] == decode["service"] == node["service"]
    assert read["attrs"]["bytes"] == decode["attrs"]["bytes"] \
        == enc["attrs"]["wireBytes"] > 0
    assert enc["attrs"]["logicalBytes"] > 0
    # the node merged its three partials before the wire (ISSUE 29)
    assert enc["attrs"]["partials"] == decode["attrs"]["partials"] == 1
    (mrg,) = _named(spans, "datanode/merge")
    assert mrg["parentId"] == dn["spanId"] and mrg["service"] == "pnode"
    assert mrg["attrs"] == {"partialsIn": 3, "groups": len(rows),
                            "mergePath": "dense",
                            "cpuMs": mrg["attrs"]["cpuMs"]}
    (bm,) = _named(spans, "broker/merge")
    assert bm["attrs"]["partials"] == 1
    assert bm["attrs"]["groups"] == len(rows)
    assert isinstance(enc["attrs"]["compressed"], bool)
    # the encode starts after the node's root ended, and inside the read
    assert enc["startMs"] >= dn["startMs"] + dn["durationMs"] - 1.0
    assert dn["durationMs"] + enc["durationMs"] <= read["durationMs"] + 1.0
    assert read["durationMs"] + decode["durationMs"] \
        <= node["durationMs"] + 1.0

    # (b) the inside of engine/partials, per segment
    (partials,) = _named(spans, "engine/partials")
    segs = _named(spans, "engine/segment")
    assert len(segs) == 3
    assert len({s["attrs"]["segment"] for s in segs}) == 3
    for seg in segs:
        assert seg["parentId"] == partials["spanId"]
        assert seg["attrs"]["rows"] == 2000
        children = kids[seg["spanId"]]
        # one span a phase, filter words twice (the megakernel conversion
        # before staging, the words after): 7 spans a segment with its own,
        # every one of them the host's — no segment waits for the device
        assert sorted(c["name"] for c in children) == sorted(
            list(SEGMENT_PHASES) + ["engine/filter/words"])
        (plan_span,) = [c for c in children if c["name"] == "engine/plan"]
        assert plan_span["attrs"]["runDomainMs"] >= 0
        (disp,) = [c for c in children if c["name"] == "engine/dispatch"]
        (build,) = [c for c in children if c["name"] == "engine/build"]
        assert seg["attrs"]["strategy"] == disp["attrs"]["strategy"]
        assert disp["attrs"]["program"] == build["attrs"]["program"] \
            == "seg_agg_" + disp["attrs"]["strategy"]
        assert disp["attrs"]["program"] in contracts.PROGRAM_NAMES
        assert all(c["attrs"]["built"] == 0 for c in children
                   if c["name"] == "engine/filter/words")
        # consecutive phases: the children's sum stays under the parent
        assert sum(c["durationMs"] for c in children) \
            <= seg["durationMs"] + 0.5
    # the request enqueued its three programs, then fetched ONCE: the fetch
    # lies beside the segments, after the last of them, and says how many
    # programs it collected
    (fetch,) = _named(spans, "engine/fetch")
    assert fetch["parentId"] == partials["spanId"]
    assert fetch["attrs"]["programs"] == dispatched \
        == len(_named(spans, "engine/dispatch"))
    assert fetch["attrs"]["bytes"] > 0
    assert fetch["startMs"] >= max(
        s["startMs"] + s["durationMs"] for s in segs) - 0.5
    assert sum(s["durationMs"] for s in segs) + fetch["durationMs"] \
        <= partials["durationMs"] + 0.5

    # (e) the answer's way out, under the root whose extent it lies beyond
    (respond,) = _named(spans, "http/respond")
    (root,) = roots
    assert respond["parentId"] == root["spanId"]
    assert respond["startMs"] >= root["startMs"] + root["durationMs"] - 1.0
    assert respond["attrs"]["bytes"] > 0
    assert 0 <= respond["attrs"]["encodeMs"] <= respond["durationMs"] + 0.5

    # the node's own store holds the late span too, and nothing of the
    # broker's
    if cluster.node_store is not None:
        local = cluster.node_store.spans(qid)
        assert {"datanode/query", "datanode/encode"} <= \
            {s["name"] for s in local}
        assert all(s["service"] == "pnode" for s in local)


def test_trace_false_records_no_span_and_sends_no_header(cluster):
    qid = f"phases-off-{cluster.tag}"
    rows = cluster.post(_groupby(qid, trace=False))
    assert rows
    time.sleep(0.05)
    assert qtrace.trace_store().get(qid) is None
    if cluster.node_store is not None:
        assert cluster.node_store.get(qid) is None
    # straight at the node: no late-span header either
    body = json.dumps({"query": _groupby("phases-off-2", trace=False),
                       "segments": sorted(cluster.node.served_segment_ids())
                       }).encode()
    req = urllib.request.Request(cluster.srv.url + "/druid/v2/partials",
                                 data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        assert r.headers.get(dataserver.LATE_SPANS_HEADER) is None
        r.read()


# ---------------------------------------------------------------------------
# broker/merge says how the partials were aligned (ISSUE 25)
# ---------------------------------------------------------------------------

def test_broker_merge_says_dense_over_shared_dictionaries(cluster):
    """Three segments with the same dictionaries: the merge aligns them in
    the dense key space, and the span says so and counts the groups."""
    qid = f"phases-merge-{cluster.tag}"
    rows = cluster.post(_groupby(qid))
    (m,) = _named(cluster.trace(qid), "broker/merge")
    assert m["attrs"]["mergePath"] == "dense"
    assert m["attrs"]["groups"] == len(rows) == 70
    # one node, one merged partial: the node aligned its three the same way
    assert m["attrs"]["partials"] == 1
    (n,) = _named(cluster.trace(qid), "datanode/merge")
    assert n["attrs"] == {"partialsIn": 3, "groups": 70,
                          "mergePath": "dense", "cpuMs": n["attrs"]["cpuMs"]}
    # untraced: the same rows, and no span anywhere to hang an attribute on
    off = f"phases-merge-off-{cluster.tag}"
    assert cluster.post(_groupby(off, trace=False)) == rows
    assert qtrace.trace_store().get(off) is None


def test_no_node_merge_span_for_one_partial(cluster):
    """One day is one segment and one partial: nothing to merge on the
    node, so no `datanode/merge` span opens and the partial crosses as it
    was produced."""
    qid = f"phases-one-partial-{cluster.tag}"
    q = _groupby(qid)
    q["intervals"] = ["2026-03-02/2026-03-03"]
    assert cluster.post(q)
    spans = cluster.trace(qid)
    assert len(_named(spans, "engine/segment")) \
        + len(_named(spans, "engine/batch/dispatch")) == 1
    assert not _named(spans, "datanode/merge")
    (enc,) = _named(spans, "datanode/encode")
    (m,) = _named(spans, "broker/merge")
    assert enc["attrs"]["partials"] == m["attrs"]["partials"] == 1


def test_broker_merge_says_sorted_for_a_host_key_query():
    """Two dimensions of ~1,900 values each: the key space is past
    DENSE_GROUP_LIMIT, the engine keys on the host, the merge sorts."""
    wide = (ColumnSpec("dimX", "string", cardinality=2000),
            ColumnSpec("dimY", "string", cardinality=2000),
            ColumnSpec("metLong", "long", low=0, high=100))
    segs = DataGenerator(wide, seed=11).segments(2, 6000, DAYS,
                                                 datasource="phases")
    assert all(s.dims["dimX"].cardinality * s.dims["dimY"].cardinality
               > grouping.DENSE_GROUP_LIMIT for s in segs)
    c = _Cluster(segs, own_store=False)
    try:
        q = _groupby("phases-merge-host")
        q["dimensions"] = ["dimX", "dimY"]
        rows = c.post(q)
        spans = c.trace("phases-merge-host")
        (m,) = _named(spans, "broker/merge")
        (n,) = _named(spans, "datanode/merge")
        assert m["attrs"]["mergePath"] == n["attrs"]["mergePath"] == "sorted"
        assert m["attrs"]["groups"] == n["attrs"]["groups"] == len(rows) \
            > 6000
        assert (n["attrs"]["partialsIn"], m["attrs"]["partials"]) == (2, 1)
    finally:
        c.stop()


def test_late_span_header_on_the_wire(cluster):
    """The header is a JSON list of finished spans, siblings of the node's
    root under the caller's span; a mangled one costs the span only."""
    tid = f"phases-late-{cluster.tag}"
    q = _groupby(tid)
    q["context"]["traceparent"] = f"{tid}:cafe0001"
    body = json.dumps({"query": q, "wireCompress": True,
                       "segments": sorted(cluster.node.served_segment_ids())
                       }).encode()
    req = urllib.request.Request(cluster.srv.url + "/druid/v2/partials",
                                 data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        late = json.loads(r.headers.get(dataserver.LATE_SPANS_HEADER))
        data = r.read()
    (enc,) = late
    assert enc["name"] == "datanode/encode"
    assert enc["traceId"] == tid and enc["parentId"] == "cafe0001"
    assert enc["durationMs"] is not None
    assert enc["attrs"]["wireBytes"] == len(data)

    client = RemoteDataNodeClient("pnode", cluster.srv.url)
    real_post = client._post
    client._post = lambda *a: real_post(*a)[:2] + ("{not json",)
    from druid_tpu.query.model import query_from_json
    ap, served = client.run_partials(query_from_json(q),
                                     sorted(cluster.node.served_segment_ids()))
    assert len(ap.partials) == 1 and len(ap.spans) == len(served) == 3


def test_rows_path_carries_the_wire_spans(cluster):
    """A scan goes over /druid/v2/rows: the same three wire spans."""
    q = {"queryType": "scan", "dataSource": "phases",
         "intervals": ["2026-03-01/2026-03-04"], "columns": ["dimA"],
         "limit": 5, "context": {"queryId": f"phases-rows-{cluster.tag}"}}
    assert cluster.post(q)
    names = {s["name"] for s in
             cluster.trace(f"phases-rows-{cluster.tag}")}
    assert {"broker/node/read", "broker/node/decode", "datanode/encode",
            "http/respond"} <= names


def test_late_span_unit():
    store = qtrace.TraceStore()
    with qtrace.root_span("datanode/query", service="n", store=store,
                          collect=True) as root:
        pass
    with qtrace.late_span(root, "datanode/encode", sibling=True, k=1) as enc:
        assert enc.parent_id == root.parent_id and enc.attrs == {"k": 1}
    with qtrace.late_span(root, "http/respond") as out:
        assert out.parent_id == root.span_id
    assert out.trace_id == enc.trace_id == root.trace_id
    assert [s["name"] for s in store.spans(root.trace_id)] == \
        ["datanode/query", "datanode/encode", "http/respond"]
    # not in the anchor's collector: the payload was already handed over
    assert [s["name"] for s in root.collected()] == ["datanode/query"]
    with qtrace.late_span(None, "http/respond") as nothing:
        assert nothing is None


# ---------------------------------------------------------------------------
# the filter-word wave: its counters, and its compile span (ISSUE 35)
# ---------------------------------------------------------------------------

@pytest.fixture
def cluster4():
    c = _Cluster(_segments(4, seed=35), own_store=True)
    c.tag = "4seg"
    yield c
    c.stop()


def test_filter_words_span_counts_the_wave_and_its_compile(cluster4):
    """A batched request with a selector stages its four cold segments in
    ONE wave: `engine/filter/words` says `pending` 4, `handovers` 1 and the
    packed buffer's `leafBytes`; the fill program's first use opens
    `engine/compile` (kind `filterFill`), a warm one does not; an
    all-resident wave hands nothing over."""
    from druid_tpu.engine import filters as filters_mod
    cluster = cluster4
    with filters_mod._FBMP_JIT_CACHE_LOCK:
        filters_mod._FBMP_JIT_CACHE.clear()

    def run(k, value):
        qid = f"words-{k}-{cluster.tag}"
        q = _groupby(qid)
        q["filter"] = {"type": "selector", "dimension": "dimB",
                       "value": value}
        assert cluster.post(q)
        spans = cluster.trace(qid)
        words = _named(spans, "engine/filter/words")
        assert words and all(
            {"built", "pending", "handovers", "leafBytes"} <= set(w["attrs"])
            for w in words)
        fills = [s for s in _named(spans, "engine/compile")
                 if s["attrs"]["kind"] == "filterFill"]
        by_id, _ = _tree(spans)
        assert all(by_id[f["parentId"]]["name"] == "engine/filter/words"
                   for f in fills)
        return {a: sum(w["attrs"][a] for w in words)
                for a in ("built", "pending", "handovers", "leafBytes")}, \
            len(fills)

    cold, cold_compiles = run(1, "v00000003")
    assert cold["pending"] == cold["built"] == 4 and cold["handovers"] == 1
    assert cold["leafBytes"] > 0 and cold_compiles == 1
    warm, warm_compiles = run(2, "v00000005")    # another literal: cold words
    assert warm["pending"] == 4 and warm["handovers"] == 1
    assert warm["leafBytes"] == cold["leafBytes"] and warm_compiles == 0
    resident, compiles = run(3, "v00000005")
    assert resident == {"built": 0, "pending": 0, "handovers": 0,
                        "leafBytes": 0} and compiles == 0


# ---------------------------------------------------------------------------
# (d) compiles counted where they happen
# ---------------------------------------------------------------------------

def test_retrace_under_a_cached_fn_is_counted_and_named():
    """Same structure, another padded row count: the engine's program cache
    hits (no engine/compile span) and jax.jit retraces underneath — only
    the backend listener sees it, and names the span it happened under."""
    from druid_tpu.engine import QueryExecutor
    small, = _segments(1, 1000, seed=11, days=Interval.of("2026-03-01",
                                                          "2026-03-02"))
    large, = _segments(1, 9000, seed=12, days=Interval.of("2026-03-01",
                                                          "2026-03-02"))
    with grouping._JIT_CACHE_LOCK:
        grouping._JIT_CACHE.clear()
    store = qtrace.trace_store()

    def run(seg, qid):
        before = dispatch_mod.stats().snapshot()
        QueryLifecycle(QueryExecutor([seg])).run_json(_groupby(qid))
        after = dispatch_mod.stats().snapshot()
        return (after["backend_compiles"] - before["backend_compiles"],
                after["backend_compile_ms"] - before["backend_compile_ms"],
                store.spans(qid))

    n1, ms1, first = run(small, "retrace-1")
    assert n1 >= 1 and ms1 > 0
    assert _named(first, "engine/compile")
    n2, ms2, second = run(large, "retrace-2")
    assert n2 >= 1 and ms2 > 0                   # the retrace compiled
    assert not _named(second, "engine/compile")  # and no span said so
    (disp,) = _named(second, "engine/dispatch")
    assert disp["attrs"]["compile"] is False
    assert disp["attrs"]["backendCompiles"] >= 1
    # a third run of the warm shape builds nothing
    n3, _ms3, third = run(large, "retrace-3")
    assert n3 == 0
    assert all("backendCompiles" not in s["attrs"] for s in third)


def test_dispatch_snapshot_keys():
    snap = dispatch_mod.stats().snapshot()
    assert {"total", "backend_compiles", "backend_compile_ms",
            "cache_retrievals", "trace_dropped_spans"} <= set(snap)
    assert all(isinstance(v, (int, float)) for v in snap.values())
    stats = dispatch_mod.DispatchStats()
    stats.record_backend_compile(0.25)
    stats.record_cache_retrieval()
    # qtrace's count of spans dropped at a cap is process-wide: it rides on
    # every snapshot, a fresh scoreboard's too
    assert stats.snapshot() == {"total": 0, "backend_compiles": 1,
                                "backend_compile_ms": 250.0,
                                "cache_retrievals": 1,
                                "trace_dropped_spans":
                                    qtrace.dropped_spans()}


# ---------------------------------------------------------------------------
# (c) programs and kernels by stable name
# ---------------------------------------------------------------------------

def test_program_name_set_is_closed():
    assert contracts.program_name("seg_agg", "pallas") == "seg_agg_pallas"
    with pytest.raises(ValueError):
        contracts.program_name("seg_agg", "projection")
    with pytest.raises(ValueError):
        contracts.named_program(lambda: None, "fn")
    assert len(contracts.PROGRAM_NAMES) == 3 * 6 + 2
    # ONE fill program since PR 35: the single-pair fill (the permuted
    # layout, the evicted-after-probe race) is the wave's program at one
    # slot, so the per-filter name left the closed set with its builder
    assert {n for n in contracts.PROGRAM_NAMES if n.startswith("bitmap_")} \
        == {"bitmap_fill_wave"}
    # no shape, segment id or digest in a name
    assert all(n.replace("_", "").isalpha() for n in contracts.PROGRAM_NAMES)
    assert all(n.replace("_", "").isalpha()
               for n in contracts.PALLAS_KERNEL_NAMES)


def test_every_jit_site_is_named():
    """Source-level: a `jax.jit(` in druid_tpu/ wraps `named_program(` or
    follows one in the same builder."""
    import re
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "druid_tpu")
    sites = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as f:
            lines = f.read().splitlines()
        for i, line in enumerate(lines):
            if re.search(r"\bjax\.jit\(", line) and \
                    not line.lstrip().startswith("#"):
                window = "\n".join(lines[max(0, i - 8): i + 2])
                sites.append((os.path.relpath(path, root), i + 1,
                              "named_program(" in window))
    assert len(sites) >= 7
    assert [s for s in sites if not s[2]] == []


@pytest.fixture()
def jit_recorder(monkeypatch):
    """Every `jax.jit` a druid_tpu module makes while the fixture is live:
    (callable name, module name of the lowered program on its first call)."""
    import jax
    from druid_tpu import engine
    real = jax.jit
    seen = []

    class Recorded:
        def __init__(self, jitted, name):
            self._jitted, self._name, self._lowered = jitted, name, False

        def _note(self, args):
            if not self._lowered:
                self._lowered = True
                text = self._jitted.lower(*args).as_text()
                seen.append((self._name, text.split("module @", 1)[1]
                             .split(None, 1)[0]))

        def __call__(self, *args):
            self._note(args)
            return self._jitted(*args)

        def lower(self, *args):
            self._note(args)
            return self._jitted.lower(*args)

    def recording_jit(fun, **kw):
        caller = sys._getframe(1).f_globals.get("__name__", "")
        jitted = real(fun, **kw)
        if not caller.startswith("druid_tpu."):
            return jitted
        return Recorded(jitted, getattr(fun, "__name__", "?"))

    engine.release_device_caches()
    monkeypatch.setattr(jax, "jit", recording_jit)
    yield seen
    monkeypatch.undo()
    engine.release_device_caches()      # drop the recording wrappers


def test_lowered_module_names_are_documented(jit_recorder, monkeypatch):
    """Queries over every program family — per-segment XLA and Pallas
    (interpreted), megakernel, batched, sharded, run domain, bitmap fills —
    lower to modules named `jit_<documented name>`."""
    import numpy as np

    from druid_tpu.data.segment import SegmentBuilder
    from druid_tpu.engine import QueryExecutor, pallas_agg
    from druid_tpu.parallel import make_mesh, use_mesh
    segs = _segments(4, 3000, seed=21)
    ex = QueryExecutor(list(segs))
    sel = {"type": "in", "dimension": "dimA", "values": ["1", "2", "3"]}
    ex.run_json(_groupby("names-batched"))                    # batch_agg_*
    ex.run_json(_groupby("names-seg", batchSegments=False))   # seg_agg_*
    q = _groupby("names-fill", batchSegments=False)
    q["filter"] = sel
    ex.run_json(q)                                 # megakernel inline words
    from druid_tpu.engine import megakernel
    was = megakernel.set_enabled(False)
    try:
        q["context"]["queryId"] = "names-fill-2"
        q["filter"] = {"type": "selector", "dimension": "dimB", "value": "2"}
        ex.run_json(q)                             # bitmap_fill_wave
        q["context"] = {"queryId": "names-fill-3"}
        q["filter"] = {"type": "selector", "dimension": "dimB", "value": "4"}
        ex.run_json(q)                             # batched: one wave
    finally:
        megakernel.set_enabled(was)
    # the sorted projection's Pallas kernel, interpreted, and its
    # megakernel variant (mask as words)
    was_interp = pallas_agg._FORCE_INTERPRET
    pallas_agg.force_interpret(True)
    try:
        grouping_patch = monkeypatch.context()
        m = grouping_patch.__enter__()
        m.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
        m.setattr(grouping, "FORCE_STRATEGY", "projection")
        ex.run_json(_groupby("names-pallas", batchSegments=False))
        q = _groupby("names-mega", batchSegments=False)
        q["filter"] = sel
        ex.run_json(q)
        # the permuted layout stages its words one filter at a time
        was = megakernel.set_enabled(False)
        try:
            q["context"]["queryId"] = "names-fill-4"
            ex.run_json(q)                         # a wave of one pair
        finally:
            megakernel.set_enabled(was)
    finally:
        pallas_agg.force_interpret(was_interp)
        grouping_patch.__exit__(None, None, None)
    # one sharded program over the virtual mesh
    with use_mesh(make_mesh()):
        ex.run_json(_groupby("names-sharded"))
    # code domain: every referenced column constant within shared runs
    b = SegmentBuilder("rundom", Interval.of("2026-03-01", "2026-03-02"))
    n = 4096
    b.add_columns(np.arange(n, dtype=np.int64) // 64 * 1000
                  + Interval.of("2026-03-01", "2026-03-02").start,
                  {"d": np.repeat(np.arange(n // 64) % 5, 64).astype(str)},
                  {"cnt": np.ones(n, dtype=np.int64)})
    QueryExecutor([b.build()]).run_json({
        "queryType": "groupBy", "dataSource": "rundom",
        "intervals": ["2026-03-01/2026-03-02"], "granularity": "all",
        "dimensions": ["d"],
        "aggregations": [{"type": "longSum", "name": "c",
                          "fieldName": "cnt"}]})
    names = {n for n, _module in jit_recorder}
    assert names <= contracts.PROGRAM_NAMES, names - contracts.PROGRAM_NAMES
    assert all(module == "jit_" + n for n, module in jit_recorder)
    families = {n.rsplit("_", 1)[0] for n in names}
    assert {"seg_agg", "batch_agg", "sharded_agg"} <= families, names
    assert {"seg_agg_pallas", "seg_agg_megakernel", "run_domain_agg",
            "bitmap_fill_wave"} <= names, names


def test_pallas_kernel_is_named_in_the_lowered_program():
    import jax
    import jax.numpy as jnp

    from druid_tpu.engine import pallas_agg
    from druid_tpu.engine.kernels import make_kernel
    from druid_tpu.query.aggregators import LongSumAggregator
    seg, = _segments(1, 2048, seed=31, days=Interval.of("2026-03-01",
                                                        "2026-03-02"))
    kernels = [make_kernel(LongSumAggregator("s", "metLong"), seg)]
    was = pallas_agg._FORCE_INTERPRET
    pallas_agg.force_interpret(False)
    try:
        def prog(key, v):
            return pallas_agg.grouped_reduce(
                {"metLong": v}, key, None, kernels, 128, 128)[0]
        jaxpr = str(jax.make_jaxpr(prog)(
            jnp.zeros(2048, jnp.int32), jnp.zeros(2048, jnp.int32)))
    finally:
        pallas_agg.force_interpret(was)
    assert "proj_group_reduce" in jaxpr
    assert contracts.PALLAS_KERNEL_NAMES[0] == "proj_group_reduce"


# ---------------------------------------------------------------------------
# (f) one clock, one view
# ---------------------------------------------------------------------------

def test_profiler_host_plane_holds_the_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from druid_tpu.engine import QueryExecutor
    ex = QueryLifecycle(QueryExecutor(list(_segments(2, 1500, seed=41))))
    ex.run_json(_groupby("prof-warm", batchSegments=False))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        ex.run_json(_groupby("prof-1", batchSegments=False))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events[e.name] = events.get(e.name, 0) + 1
    assert events.get("engine/dispatch") == 2
    assert events.get("engine/fetch") == 1
    assert events.get("engine/segment") == 2
    assert events.get("query") == 1


def test_trace_module_imports_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "import druid_tpu.obs.trace as t\n"
            "with t.root_span('query', service='s') as r:\n"
            "    with t.span('child'):\n"
            "        pass\n"
            "assert len(t.trace_store().spans(r.trace_id)) == 2\n"
            "assert t._annotation('x') is None\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_trace_exports_are_the_ones_in_use():
    """(g): what nothing read is gone, the rest is exported once."""
    import druid_tpu.obs as obs
    assert not hasattr(qtrace.TraceStore, "add")
    assert not hasattr(qtrace.TraceStore, "clear")
    for name in obs.__all__:
        assert getattr(obs, name) is not None
    assert {"late_span", "span", "root_span", "attach", "trace_store",
            "TraceStore"} <= set(obs.__all__)


def test_span_ids_cost_no_system_call(monkeypatch):
    """A span id is a per-process prefix and a counter: `os.urandom` (which
    releases the interpreter lock — under two clients every span opened
    handed the lock away) is not called per span, and ids stay unique."""
    import os as _os
    store = qtrace.TraceStore(max_spans_per_trace=10_000)

    def no_urandom(_n):
        raise AssertionError("a span asked the kernel for randomness")

    monkeypatch.setattr(_os, "urandom", no_urandom)
    with qtrace.root_span("query", service="s", store=store,
                          queryId="ids-1") as root:
        for _ in range(5000):
            with qtrace.span("engine/fetch"):
                pass
    ids = [s["spanId"] for s in store.spans(root.trace_id)]
    assert len(set(ids)) == len(ids) == 5001
    assert all(len(i) == 16 and ":" not in i for i in ids)
    monkeypatch.undo()
    before = qtrace._ID_PREFIX
    qtrace._reseed_ids()            # what a forked child does
    assert qtrace._ID_PREFIX != before
