"""A data node merges its own partials before the wire (ISSUE 29): its
answer carries ONE merged partial wherever it produced two or more, and the
broker's finish step over that answer returns what it returned over the
node's per-segment partials, bit for bit. The merged partial is a
`SegmentPartial` like any other — it merges with other nodes', crosses the
wire, and decodes — and the segment cache keeps what it kept: one unmerged
entry a segment."""
import numpy as np
import pytest

from druid_tpu.cluster import (Broker, DataNode, InventoryView, LruCache,
                               descriptor_for, wire)
from druid_tpu.cluster.cache import query_cache_key
from druid_tpu.engine import engines, merge
from druid_tpu.engine.engines import AggregatePartials
from druid_tpu.obs import trace as qtrace
from druid_tpu.query.aggregators import (CountAggregator, FloatMaxAggregator,
                                         LongMinAggregator, LongSumAggregator)
from druid_tpu.query.model import (DefaultDimensionSpec, GroupByQuery,
                                   TimeseriesQuery, TopNQuery)
from druid_tpu.cluster.view import node_answer
from tests.test_merge_dense import (A10, ALL_AGGS, B4, IV, _names, _same,
                                    _same_merge, _seg, as_host)

#: exact in whatever order partials are combined
EXACT_AGGS = [CountAggregator("n"), LongSumAggregator("ls", "ml"),
              LongMinAggregator("lmin", "ml"), FloatMaxAggregator("fmax", "mf")]

FINISH = {"timeseries": engines.finish_timeseries,
          "topN": engines.finish_topn, "groupBy": engines.finish_groupby}


def _query(kind, aggs=ALL_AGGS):
    if kind == "timeseries":
        return TimeseriesQuery.of("md", [IV], aggs, granularity="hour")
    if kind == "topN":
        return TopNQuery.of("md", [IV], DefaultDimensionSpec("a"), "ls", 6,
                            aggs, granularity="all")
    return GroupByQuery.of("md", [IV], [DefaultDimensionSpec("a"),
                                        DefaultDimensionSpec("b")],
                           aggs, granularity="day")


def _segments(dictionaries, seed=50):
    if dictionaries == "shared":
        return [_seg(d, A10, B4, seed=seed) for d in range(4)]
    return [_seg(0, _names("a", range(0, 8)), B4, seed=seed),
            _seg(1, _names("a", range(4, 12)), _names("b", range(2, 6)),
                 seed=seed),
            _seg(2, _names("a", range(6, 9)), B4, seed=seed),
            _seg(3, A10, _names("b", range(1, 3)), seed=seed)]


def _produced(query, segs, keyed="dense"):
    """What a node holds before it answers: a partial a segment, nothing
    unified across them; `keyed` "host" re-lays each as the projection path
    hands it over."""
    ap = AggregatePartials.concat(
        [engines.make_aggregate_partials(query, [s], clamp=False)
         for s in segs])
    if keyed == "host":
        ap.partials = [as_host(p, dead=i % 2 == 0)
                       for i, p in enumerate(ap.partials)]
    return ap


def _merge_path(fn):
    with qtrace.root_span("test", store=qtrace.TraceStore()) as sp:
        out = fn()
    return out, sp.attrs.get("mergePath")


def _typed(rows):
    """Rows with the type of every leaf spelled out: 1 is not 1.0."""
    def leaf(v):
        if isinstance(v, dict):
            return {k: leaf(x) for k, x in v.items()}
        if isinstance(v, list):
            return [leaf(x) for x in v]
        return (type(v).__name__, repr(v))
    return leaf(rows)


# ---------------------------------------------------------------------------
# finish(merged) is finish(unmerged)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("keyed", ("dense", "host"))
@pytest.mark.parametrize("dictionaries", ("shared", "differing"))
@pytest.mark.parametrize("alignment", ("dense", "sorted"))
@pytest.mark.parametrize("kind", sorted(FINISH))
def test_finish_of_the_node_merged_equals_finish_of_the_unmerged(
        kind, alignment, dictionaries, keyed, monkeypatch):
    if alignment == "sorted":
        monkeypatch.setattr(merge, "DENSE_GROUP_LIMIT", 1)
    query = _query(kind)
    ap = _produced(query, _segments(dictionaries), keyed)
    assert len(ap.partials) == 4
    assert {p.spec.key_mode for p in ap.partials} == {keyed}
    kept = [(p.counts.tobytes(), p.spec.host_unique) for p in ap.partials]

    merged, path = _merge_path(ap.merged)
    assert path == alignment
    (p,) = merged.partials
    (values,) = merged.dim_values
    assert p.spec.key_mode == "host" and len(p.counts) == p.spec.num_total \
        == len(p.spec.host_unique)
    assert (p.counts > 0).all() and (np.diff(p.spec.host_unique) > 0).all()
    assert [d.cardinality for d in p.spec.dims] == [len(v) for v in values]
    assert all(d.remap is None for d in p.spec.dims)
    assert p.kernels is ap.partials[0].kernels
    assert p.spec.bucket_mode == ap.partials[0].spec.bucket_mode
    assert p.spec.bucket_starts is ap.partials[0].spec.bucket_starts
    # every span and the intervals: _covered_buckets reads them
    assert merged.spans is ap.spans and len(merged.spans) == 4
    assert merged.intervals is ap.intervals

    dims = (lambda a: [[] for _ in a.partials]) if kind == "timeseries" \
        else (lambda a: a.dim_values)
    _same_merge(merge.merge_partials(merged.partials, dims(merged)),
                merge.merge_partials(ap.partials, dims(ap)))
    rows, again = FINISH[kind](query, ap), FINISH[kind](query, merged)
    assert rows and _typed(again) == _typed(rows)
    # the inputs were read, never written
    assert kept == [(q.counts.tobytes(), q.spec.host_unique)
                    for q in ap.partials]


def test_a_sorted_node_merge_may_meet_a_dense_broker_merge(monkeypatch):
    """The node's merged space was past the limit, the live values' is not:
    the two alignments return the same bits, so who takes which is free."""
    query = _query("groupBy")
    ap = _produced(query, _segments("differing"))
    ref = merge.merge_partials(ap.partials, ap.dim_values)
    monkeypatch.setattr(merge, "DENSE_GROUP_LIMIT", 1)
    merged, path = _merge_path(ap.merged)
    assert path == "sorted"
    monkeypatch.undo()
    got, path = _merge_path(lambda: merge.merge_partials(merged.partials,
                                                         merged.dim_values))
    assert path == "dense"
    _same_merge(got, ref)


def test_merging_a_merged_partial_again_changes_nothing():
    query = _query("groupBy")
    once = _produced(query, _segments("differing")).merged()
    (p,) = once.partials
    twice, values = merge.merge_to_partial(once.partials, once.dim_values)
    assert values == once.dim_values[0]
    _same(twice.spec.host_unique, p.spec.host_unique, "keys")
    _same(twice.counts, p.counts, "counts")
    _same(twice.states, p.states, "states")


# ---------------------------------------------------------------------------
# several nodes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dictionaries", ("shared", "differing"))
@pytest.mark.parametrize("kind", sorted(FINISH))
def test_two_nodes_merging_their_halves_equal_one_merge_of_every_segment(
        kind, dictionaries):
    query = _query(kind, EXACT_AGGS)
    segs = _segments(dictionaries, seed=51)
    whole = _produced(query, segs)
    halves = AggregatePartials.concat([_produced(query, segs[:2]).merged(),
                                       _produced(query, segs[2:]).merged()])
    assert len(halves.partials) == 2 and len(halves.spans) == 4
    rows = FINISH[kind](query, whole)
    assert rows and _typed(FINISH[kind](query, halves)) == _typed(rows)


def test_a_broker_over_two_nodes_answers_as_one_node_does():
    segs = _segments("differing", seed=52)
    query = _query("groupBy", EXACT_AGGS)

    def cluster(groups):
        view = InventoryView()
        for i, group in enumerate(groups):
            node = DataNode(f"n{i}")
            view.register(node)
            for s in group:
                node.load_segment(s)
                view.announce(node.name, descriptor_for(s))
        return Broker(view)

    one, two = cluster([segs]), cluster([segs[:1], segs[1:]])
    try:
        rows = one.run(query)
        assert rows and _typed(two.run(query)) == _typed(rows)
    finally:
        one.stop()
        two.stop()


# ---------------------------------------------------------------------------
# the wire
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compress", (False, True),
                         ids=("plain", "compressed"))
@pytest.mark.parametrize("kind", sorted(FINISH))
def test_the_merged_partial_survives_the_wire(kind, compress):
    query = _query(kind)
    ap = _produced(query, _segments("differing", seed=53))
    merged = ap.merged()
    facts, unmerged = {}, {}
    body = wire.dumps_partials(merged, served=["s"], compress=compress,
                               facts=facts)
    wire.dumps_partials(ap, compress=compress, facts=unmerged)
    assert facts["logicalBytes"] < unmerged["logicalBytes"]
    back, served, _ = wire.loads_partials(body)
    assert served == {"s"} and len(back.partials) == 1
    assert back.spans == merged.spans
    assert list(back.intervals) == list(merged.intervals)
    (p,), (q,) = back.partials, merged.partials
    _same(p.spec.host_unique, q.spec.host_unique, "keys")
    _same(p.counts, q.counts, "counts")
    _same(p.states, q.states, "states")
    assert back.dim_values == merged.dim_values
    rows = FINISH[kind](query, ap)
    assert rows and _typed(FINISH[kind](query, back)) == _typed(rows)


# ---------------------------------------------------------------------------
# what passes through
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", (0, 1))
def test_zero_and_one_partial_pass_through_as_the_same_object(n):
    query = _query("groupBy")
    ap = _produced(query, _segments("shared")[:n])
    assert len(ap.partials) == n
    with qtrace.root_span("test", store=qtrace.TraceStore()) as root:
        assert ap.merged() is ap
        assert node_answer(ap) is ap
    assert "mergePath" not in root.attrs
    assert [s["name"] for s in root._store.spans(root.trace_id)] == ["test"]


def test_a_mesh_nodes_single_partial_passes_through(monkeypatch):
    """The sharded program merged on the device: one partial for four
    segments, four spans, and nothing left for the node's host to merge."""
    from druid_tpu.parallel import distributed, make_mesh
    node = DataNode("mesh", mesh=make_mesh(4))
    segs = _segments("shared", seed=54)
    for s in segs:
        node.load_segment(s)
    query = _query("groupBy", EXACT_AGGS)
    made = []
    real = distributed.try_sharded

    def spy(*a, **k):
        made.append(real(*a, **k))
        return made[-1]
    monkeypatch.setattr(distributed, "try_sharded", spy)
    store = qtrace.TraceStore()
    with qtrace.root_span("datanode/query", store=store) as root:
        ap, served = node.run_partials(query, [str(s.id) for s in segs])
    assert len(made) == 1 and made[0] is not None
    assert ap.partials == [made[0]] and ap.partials[0] is made[0]
    assert len(ap.spans) == len(served) == 4
    names = [s["name"] for s in store.spans(root.trace_id)]
    assert "engine/partials" in names and "datanode/merge" not in names
    rows = engines.finish_groupby(query, ap)
    assert _typed(rows) == _typed(
        engines.finish_groupby(query, _produced(query, segs)))


def test_a_realtime_node_answers_with_one_partial_for_its_hydrants():
    from druid_tpu.cluster import MetadataStore, RealtimeServer
    from druid_tpu.ingest import (SimulatedStream, StreamSupervisor,
                                  StreamSupervisorSpec, StreamTuningConfig)
    from druid_tpu.utils.intervals import Interval
    day = Interval.of("2026-03-01", "2026-03-02")
    rng = np.random.default_rng(57)
    records = [{"timestamp": int(day.start + i * 1000),
                "page": f"p{int(rng.integers(5))}",
                "value": int(rng.integers(0, 10))} for i in range(300)]
    view = InventoryView()
    rt = RealtimeServer("peon0", view)
    stream = SimulatedStream(n_partitions=1)
    sup = StreamSupervisor(StreamSupervisorSpec(
        "rt", [CountAggregator("rows"), LongSumAggregator("v", "value")],
        dimensions=["page"], task_count=1, max_rows_per_task=10**9,
        tuning=StreamTuningConfig(segment_granularity="day",
                                  max_rows_per_hydrant=100)),
        stream, MetadataStore(), realtime=rt)
    for part in (records[:120], records[120:240], records[240:]):
        stream.append(0, part)       # a poll past the bound seals a hydrant
        sup.run_once()
    query = GroupByQuery.of("rt", [day], [DefaultDimensionSpec("page")],
                            [LongSumAggregator("rows", "rows"),
                             LongSumAggregator("v", "v")])
    hydrants, served = rt._select(sorted(rt.served_segment_ids()))
    assert len(hydrants) > 1 and len(served) == 1
    ap, _ = rt.run_partials(query, sorted(served))
    assert len(ap.partials) == 1 and len(ap.spans) == len(hydrants)
    rows = engines.finish_groupby(query, ap)
    assert sum(r["event"]["rows"] for r in rows) == 300
    assert _typed(rows) == _typed(
        engines.finish_groupby(query, _produced(query, hydrants)))


def test_the_probe_runs_before_the_merge(monkeypatch):
    class Stop(Exception):
        pass

    def check():
        raise Stop()
    monkeypatch.setattr(AggregatePartials, "merged",
                        lambda self: pytest.fail("merged after a cancel"))
    ap = _produced(_query("groupBy"), _segments("shared")[:2])
    with pytest.raises(Stop):
        node_answer(ap, check)


# ---------------------------------------------------------------------------
# the node, its cache and its two serving paths
# ---------------------------------------------------------------------------

def _state_bytes(state):
    if isinstance(state, dict):
        return {k: _state_bytes(v) for k, v in state.items()}
    return np.asarray(state).tobytes()


def _entry_bytes(ap):
    (p,) = ap.partials
    return (p.counts.tobytes(), _state_bytes(p.states), p.spec.key_mode,
            list(map(list, ap.dim_values[0])), list(ap.spans))


@pytest.mark.parametrize("path", ("request-thread", "scheduler-flush"))
@pytest.mark.parametrize("kind", sorted(FINISH))
def test_the_segment_cache_keeps_unmerged_per_segment_entries(kind, path):
    query = _query(kind)
    segs = _segments("differing", seed=55)
    node = DataNode("cached", cache=LruCache())
    for s in segs:
        node.load_segment(s)
    ids = [str(s.id) for s in segs]

    def ask():
        if path == "request-thread":
            return node.run_partials(query, ids)
        (got,) = node.run_partials_group([(query, ids, None)])
        return got

    store = qtrace.TraceStore()
    with qtrace.root_span("datanode/query", store=store) as root:
        ap, served = ask()
    assert len(ap.partials) == 1 and len(ap.spans) == len(served) == 4
    spans = store.spans(root.trace_id)
    (mrg,) = [s for s in spans if s["name"] == "datanode/merge"]
    assert mrg["attrs"]["partialsIn"] == 4
    assert mrg["attrs"]["groups"] == len(ap.partials[0].counts)
    assert mrg["attrs"]["mergePath"] == "dense"
    if path == "request-thread":
        assert mrg["parentId"] == root.span_id

    qkey = query_cache_key(query)
    entries = [node.cache.get("segment", f"{s.id}|{qkey}") for s in segs]
    assert all(len(e.partials) == 1 for e in entries)
    fresh = [_produced(query, [s]) for s in segs]
    assert [_entry_bytes(e) for e in entries] \
        == [_entry_bytes(f) for f in fresh]

    hits = node.cache.stats.hits
    again, _ = ask()
    assert node.cache.stats.hits == hits + 4          # served from the cache
    assert len(again.partials) == 1
    rows = FINISH[kind](query, _produced(query, segs))
    assert rows and _typed(FINISH[kind](query, ap)) == _typed(rows)
    assert _typed(FINISH[kind](query, again)) == _typed(rows)
    # merged twice over, the entries are what they were
    assert [_entry_bytes(e) for e in entries] \
        == [_entry_bytes(f) for f in fresh]


def test_a_flush_mates_cancel_fails_that_request_alone():
    class Stop(Exception):
        pass
    calls = []

    def check():
        calls.append(1)
        if len(calls) > 1:          # lets the dispatches pass, stops the merge
            raise Stop()
    segs = _segments("shared", seed=56)
    node = DataNode("flush")
    for s in segs:
        node.load_segment(s)
    ids = [str(s.id) for s in segs]
    query = _query("groupBy", EXACT_AGGS)
    ok, stopped = node.run_partials_group(
        [(query, ids, None), (query, ids, check)])
    assert isinstance(stopped, Stop)
    ap, served = ok
    assert len(ap.partials) == 1 and len(served) == 4
