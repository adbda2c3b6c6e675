"""The mesh historical as `mesh4-analyst-groupby` serves it, on 4 of the
virtual CPU devices: the cell's query through a mesh node equals the plain
reference and the meshless node row for row; the sharded path's plan, stack
and put are under spans; a query the stacked program cannot run falls back
LOUDLY (`fallback` 1 and a `reason` from the closed set), never silently.
"""
import ast
import http.client
import json
import os

import numpy as np
import pytest

from druid_tpu.engine import QueryExecutor, contracts, grouping
from druid_tpu.obs import trace
from druid_tpu.parallel import distributed, make_mesh, use_mesh
from druid_tpu.utils.intervals import Interval

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
SEGMENTS, ROWS, DEVICES = 7, 20_000, 4          # K pads to 8
SHARDED = "engine/sharded/"


def _post(port: int, query: dict):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/druid/v2", body=json.dumps(query).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200, body[:400]
        assert resp.getheader("X-Druid-Response-Context") is None
        return json.loads(body)
    finally:
        conn.close()


def _sharded_spans(trace_id: str) -> dict:
    """{span name minus the prefix: [attrs]} of one request's trace: the
    attributes a site stamps, without the `cpuMs` every span carries (a
    clock reading, checked to be there)."""
    out: dict = {}
    for s in trace.trace_store().spans(trace_id):
        if s["name"].startswith(SHARDED):
            attrs = dict(s["attrs"])
            assert 0 <= attrs.pop("cpuMs") <= s["durationMs"] + 1.0
            out.setdefault(s["name"][len(SHARDED):], []).append(attrs)
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The cell's configuration cut to 7 × 20,000 rows (every value of both
    dimensions still occurs in every segment), its data from the
    benchmark's own generator, persisted, and served twice: by a historical
    over a 4-device mesh and by a meshless one, each behind its broker."""
    from benchmark.harness import deploy, traffic
    from benchmark.reference import engine as reference
    from druid_tpu import cli
    with open(os.path.join(BENCH, "configs", "basic-day5m-mesh4.json")) as f:
        config = dict(json.load(f), segments=SEGMENTS, rows_per_segment=ROWS)
    root = tmp_path_factory.mktemp("mesh4")
    seg_dir, raw_dir = str(root / "segments"), str(root / "raw")
    os.makedirs(seg_dir)
    os.makedirs(raw_dir)
    deploy.make_segments(config, 20261001, range(SEGMENTS), seg_dir, raw_dir)
    template = traffic.load_query(BENCH, "groupby-seq-zipf-bound")["query"]
    first = deploy.basic.segment_start_ms(config, 0)
    days = [f"{traffic.iso(first)}/"
            f"{traffic.iso(first + SEGMENTS * deploy.basic.DAY_MS)}"]

    def query(qid: str, lo: int, hi: int) -> dict:
        q = traffic.fill(template, {"intervals": days, "lo": lo, "hi": hi})
        return dict(q, context=dict(q.get("context", {}), queryId=qid))

    servers, ports = [], {}
    try:
        for name, mesh in (("mesh", make_mesh(DEVICES)), ("plain", None)):
            _node, historical, loaded = cli.build_historical(
                f"hist-{name}", segments_dir=seg_dir, port=0, mesh=mesh)
            servers.append(historical)
            assert loaded == SEGMENTS
            _view, broker, front = cli.build_broker([historical.url], port=0)
            servers += [front, broker]
            ports[name] = front.port
        yield {"ports": ports, "query": query,
               "data": reference.RawData(raw_dir, config)}
    finally:
        while servers:
            servers.pop().stop()
        distributed.clear_stack_cache()


def test_cell_query_on_a_mesh_node_equals_reference_and_meshless(served):
    from benchmark.reference import engine as reference
    q = served["query"]("mesh4-eq", 137, 9_731)
    want = reference.answer(served["data"], q)
    got = _post(served["ports"]["mesh"], q)
    plain = _post(served["ports"]["plain"], dict(
        q, context=dict(q["context"], queryId="mesh4-eq-plain")))
    assert len(want) > 40_000     # of the 100,000-slot group space
    assert got == want          # `==`: counts, long sums and float maxima
    assert got == plain
    assert _sharded_spans("mesh4-eq")["plan"][0]["fallback"] == 0
    # a meshless node opens no span of the sharded path
    assert _sharded_spans("mesh4-eq-plain") == {}


def test_sharded_path_is_under_spans_and_the_second_request_hits(served):
    before = distributed.sharded_stats().snapshot()
    distributed.clear_stack_cache()
    _post(served["ports"]["mesh"], served["query"]("mesh4-s1", 11, 9_900))
    _post(served["ports"]["mesh"], served["query"]("mesh4-s2", 402, 9_512))
    after = distributed.sharded_stats().snapshot()
    assert (after[0] - before[0], after[1] - before[1],
            after[2] - before[2]) == (2, 2 * SEGMENTS, 0)
    first, second = _sharded_spans("mesh4-s1"), _sharded_spans("mesh4-s2")
    for spans in (first, second):
        assert sorted(spans) == ["dispatch", "plan", "put", "stack"]
        assert all(len(v) == 1 for v in spans.values())
        plan, = spans["plan"]
        assert plan == {"segments": SEGMENTS, "fallback": 0,
                        "selected": plan["strategy"],
                        "strategy": spans["dispatch"][0]["strategy"]}
        assert spans["put"][0]["bytes"] > 0
    # one program, one fetch: what a meshless request's fetch collects from
    # many enqueued programs (`programs`) is 1 here
    for qid in ("mesh4-s1", "mesh4-s2"):
        fetch, = [s["attrs"] for s in trace.trace_store().spans(qid)
                  if s["name"] == "engine/fetch"]
        assert fetch["programs"] == 1 and fetch["segments"] == 8
        assert fetch["bytes"] > 0
    rows = -(-ROWS // 1024) * 1024
    stack, = first["stack"]
    assert stack.pop("builtBytes") > SEGMENTS * rows       # > 1 B a row
    # `validity` names the decode the program ran for `__valid`: the row
    # count's one compare, on the build and on the pool hit alike
    assert stack == {"segments": SEGMENTS, "devices": DEVICES, "hit": False,
                     "paddedSegments": 8, "rows": rows, "validity": "prefix"}
    assert second["stack"] == [{"segments": SEGMENTS, "devices": DEVICES,
                                "hit": True, "builtBytes": 0,
                                "paddedSegments": 8, "rows": rows,
                                "validity": "prefix"}]


def test_projection_override_is_visible_on_the_plan_span(served, monkeypatch):
    """At the cell's real size `select_strategy` picks the sorted projection
    and the stacked program runs the XLA scatter instead: both names are on
    the span."""
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    got = _post(served["ports"]["mesh"], served["query"]("mesh4-proj", 5, 9_999))
    spans = _sharded_spans("mesh4-proj")
    assert (spans["plan"][0]["selected"], spans["plan"][0]["strategy"]) == \
        ("projection", "mixed")
    assert spans["dispatch"][0]["program"] == "sharded_agg_mixed"
    assert len(got) > 40_000


# ---------------------------------------------------------------------------
# Fall-backs: one query per reason a query can reach
# ---------------------------------------------------------------------------

def _segment(day: int, dims: dict, metrics: dict, n: int = 64):
    from druid_tpu.data.segment import SegmentBuilder
    iv = Interval.of(f"2026-03-{day:02d}", f"2026-03-{day + 1:02d}")
    b = SegmentBuilder("fb", iv)
    b.add_columns(
        iv.start + np.arange(n, dtype=np.int64) * 1000,
        {k: [v[i % len(v)] for i in range(n)] for k, v in dims.items()},
        {k: np.asarray([v[i % len(v)] for i in range(n)])
         for k, v in metrics.items()})
    return b.build()


def _group_by(dims=("d",), aggs=None, flt=None, granularity="all", vcs=None):
    q = {"queryType": "groupBy", "dataSource": "fb",
         "intervals": ["2026-03-01/2026-03-10"], "granularity": granularity,
         "dimensions": list(dims),
         "aggregations": aggs or [{"type": "count", "name": "n"}]}
    if flt:
        q["filter"] = flt
    if vcs:
        q["virtualColumns"] = vcs
    return q


AB, XY, M = {"d": ["a", "b"], "e": ["x", "y"]}, ["x", "y"], {"m": [1, 2, 3]}
LONG_SUM = [{"type": "longSum", "name": "s", "fieldName": "m"}]
FALLBACKS = {
    # a long column as a dimension: per-segment query-time id dictionaries
    "numeric_dimension": (lambda: [_segment(1, AB, M), _segment(2, AB, M)],
                          _group_by(dims=("m",))),
    "key_dims_differ": (lambda: [
        _segment(1, AB, M), _segment(2, {"d": ["a", "b", "c"], "e": XY}, M)],
        _group_by()),
    # equal cardinality, other values: ids would decode through segment 0
    "dictionaries_differ": (lambda: [
        _segment(1, AB, M), _segment(2, {"d": ["a", "c"], "e": XY}, M)],
        _group_by()),
    "key_or_bucket_mode": (lambda: [_segment(1, AB, M), _segment(2, AB, M)],
                           _group_by(granularity="month")),
    "filter_plans_differ": (lambda: [
        _segment(1, AB, M), _segment(2, {"d": ["a", "b"]}, M)],
        _group_by(flt={"type": "selector", "dimension": "e", "value": "x"})),
    "kernel_plans_differ": (lambda: [
        _segment(1, AB, M), _segment(2, AB, {"m": [1.5, 2.5]})],
        _group_by(aggs=LONG_SUM)),
    "virtual_columns_differ": (lambda: [
        _segment(1, AB, M), _segment(2, {"d": ["a", "b"], "e": ["x", "z"]}, M)],
        _group_by(aggs=[{"type": "longSum", "name": "s", "fieldName": "v"}],
                  vcs=[{"type": "expression", "name": "v",
                        "expression": "if(e == 'z', 1, 0)",
                        "outputType": "LONG"}])),
}


@pytest.mark.parametrize("reason", sorted(FALLBACKS))
def test_ineligible_query_falls_back_exactly_and_says_why(reason):
    make, query = FALLBACKS[reason]
    segments = make()
    want = QueryExecutor(segments).run_json(query)
    before = distributed.sharded_stats().snapshot()
    with use_mesh(make_mesh(DEVICES)), \
            trace.root_span("test", service="t") as root:
        got = QueryExecutor(segments).run_json(query)
    after = distributed.sharded_stats().snapshot()
    assert got == want and len(got) > 0
    assert _sharded_spans(root.trace_id) == {
        "plan": [{"segments": 2, "fallback": 1, "reason": reason}]}
    assert (after[0] - before[0], after[2] - before[2]) == (0, 1)


def _own_returns(fn: ast.FunctionDef):
    """The function's `return`s, not those of functions nested in it."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Return):
            yield node
        elif not isinstance(node, (ast.FunctionDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


def test_fallback_reasons_are_the_closed_set_the_source_uses():
    """Every exit of `_plan_sharded` is a plan, the const-false zero or
    `sharded_fallback_reason(<one of the set>)`; each reason has its exit;
    `try_sharded` returns None twice: before the mesh check's span, and for
    a fall-back that the span has recorded."""
    with open(distributed.__file__) as f:
        tree = ast.parse(f.read())
    fns = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}
    used = []
    for node in _own_returns(fns["_plan_sharded"]):
        value = node.value
        assert isinstance(value, ast.Call), ast.dump(node)
        if value.func.id == "sharded_fallback_reason":
            arg, = value.args
            used.append(arg.value)
        else:
            assert value.func.id in ("_ShardedPlan", "SegmentPartial")
    assert sorted(used) == sorted(contracts.SHARDED_FALLBACK_REASONS)
    assert len(set(used)) == len(used) == 15
    assert set(FALLBACKS) <= set(used)
    nones = sorted(
        n.lineno for n in _own_returns(fns["try_sharded"])
        if isinstance(n.value, ast.Constant) and n.value.value is None)
    spans = [n.lineno for n in ast.walk(fns["try_sharded"])
             if isinstance(n, ast.With)]
    assert len(nones) == 2 and len(spans) == 1
    assert nones[0] < spans[0] < nones[1]
    with pytest.raises(ValueError, match="not a documented"):
        contracts.sharded_fallback_reason("because")


def test_monitor_emits_fallbacks_and_the_catalog_has_the_row():
    from druid_tpu.obs import catalog
    stats = distributed.ShardedStats()
    monitor = distributed.ShardedMonitor(stats=stats)
    stats.record(7)
    stats.record_fallback()
    stats.record_fallback()
    seen = {}

    class Emitter:
        def metric(self, name, value, **_dims):
            seen[name] = value

    monitor.do_monitor(Emitter())
    assert (seen["query/sharded/mergeDevice"],
            seen["query/sharded/fallback"]) == (1, 2)
    monitor.do_monitor(Emitter())
    assert seen["query/sharded/fallback"] == 0
    assert "query/sharded/fallback" in catalog.METRICS
