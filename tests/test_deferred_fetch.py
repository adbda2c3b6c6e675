"""A request enqueues every program it needs, then fetches once (ISSUE 31):
`grouping.run_grouped_aggregates` over `enqueue_grouped_aggregate` and
`batching._enqueue_batch`, and the ONE `engine/fetch` of a request.

Counts and bits on the CPU, never a rate: the helper's partials equal
one-at-a-time `run_grouped_aggregate` bit for bit and in order over every
route a segment can take, stacked chunks of K = 8, 4 and 2 among them, and
so do chunks whose mates come from two queries; a batched request (chunks
AND stragglers) and a per-segment one carry one `engine/fetch` whose
`programs` counts what was enqueued; a chunk's ONE stacked output (ISSUE
37) is started, waited for, fetched, counted and released once; the
un-fetched-bytes bound drains in waves and answers the same; a `check` or
an enqueue that raises surfaces its own error, fetches nothing and leaves
no megakernel carry taken and not parked.
"""
import functools
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

from druid_tpu.data import cascade
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine import (QueryExecutor, batching, engines, grouping,
                              megakernel, pallas_agg)
from druid_tpu.obs import dispatch as dispatch_mod
from druid_tpu.obs import trace as qtrace
from druid_tpu.query.model import query_from_json
from druid_tpu.server import QueryLifecycle
from druid_tpu.utils.intervals import Interval
from tests.test_batch_served import ROWS, _raw, _segment
from tests.test_batch_served import _query as _small_query
from tests.test_cascade import RUN_GROUPBY, rollup_segments

REPO = Path(__file__).resolve().parent.parent
IV = Interval.of("2026-03-01", "2026-03-03")
SCHEMA = (
    ColumnSpec("dimA", "string", cardinality=7),
    ColumnSpec("dimB", "string", cardinality=40, distribution="zipf"),
    ColumnSpec("metLong", "long", low=-50, high=900),
    ColumnSpec("metFloat", "float", distribution="normal", mean=10.0,
               std=3.0),
)
SUMS = [{"type": "count", "name": "n"},
        {"type": "longSum", "name": "ls", "fieldName": "metLong"}]
IN_FILTER = {"type": "in", "dimension": "dimB",
             "values": [f"v{i:08d}" for i in range(0, 40, 2)]}
#: tests/test_batch_served.py's first 14 segments: one shape bucket, which
#: the K ladder cuts into chunks of 8, 4 and 2
STACKED = 14


def _groupby(**over):
    q = {"queryType": "groupBy", "dataSource": "df", "intervals": [str(IV)],
         "granularity": "all", "dimensions": ["dimA", "dimB"],
         "aggregations": SUMS, "context": {"batchSegments": False}}
    q.update(over)
    return q


#: route -> (query, grouping.FORCE_STRATEGY while its segments plan, the
#: strategy its partials' specs must end in; None where no row program runs)
ROUTES = {
    "mixed": (_groupby(aggregations=SUMS + [
        {"type": "floatMax", "name": "fx", "fieldName": "metFloat"}]),
        "mixed", "mixed"),
    "blocked": (_groupby(), "blocked", "blocked"),
    "mm": (_groupby(), "mm", "mm"),
    "pallas": (_groupby(), "projection", "pallas"),
    "megakernel": (_groupby(filter=IN_FILTER), "projection", "megakernel"),
    "constFalse": (_groupby(filter={"type": "selector", "value": "x",
                                    "dimension": "noSuchColumn"}),
                   None, None),
    "runDomain": (dict(RUN_GROUPBY, context={"batchSegments": False}),
                  None, None),
    # the stacked chunks of batching._enqueue_batch, a kind each
    **{f"batched-{kind}": (_small_query(kind, f"df-k-{kind}", end=STACKED),
                           None, "blocked")
       for kind in ("topN", "timeseries", "groupBy")},
}


@functools.lru_cache(maxsize=None)
def _small_segments():
    return tuple(_segment(i, _raw(i, rows)) for i, rows in enumerate(ROWS))


def _route_segments(route, segments):
    if route == "runDomain":
        return rollup_segments(2, rows=4096)
    if route.startswith("batched-"):
        return list(_small_segments()[:STACKED])
    return segments


@pytest.fixture(scope="module")
def segments():
    return DataGenerator(SCHEMA, seed=31).segments(2, 5_000, IV,
                                                   datasource="df")


@pytest.fixture
def kernel_build(monkeypatch):
    """The projection strategy at any size, its kernel on the interpreter."""
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)


def _forced(strategy, enqueue):
    """`enqueue` under grouping.FORCE_STRATEGY = strategy: what a segment
    plans with is read when it is enqueued, so a request can mix routes."""
    def run():
        was, grouping.FORCE_STRATEGY = grouping.FORCE_STRATEGY, strategy
        try:
            return enqueue()
        finally:
            grouping.FORCE_STRATEGY = was
    return run


def _calls(route, segments, monkeypatch):
    """The arguments the served engine hands `enqueue_grouped_aggregate` for
    `route`'s query run a program a segment, a call a segment."""
    query, strategy, _ = ROUTES[route]
    query = dict(query, context=dict(query["context"], batchSegments=False))
    segs = _route_segments(route, segments)
    calls = []
    real = grouping.enqueue_grouped_aggregate

    def spy(*args, **kw):
        calls.append((args, kw))
        return real(*args, **kw)
    with monkeypatch.context() as m:
        m.setattr(engines, "enqueue_grouped_aggregate", spy)
        m.setattr(grouping, "FORCE_STRATEGY", strategy)
        assert QueryExecutor(segs).run_json(query) is not None
    assert len(calls) == len(segs)
    return calls


def _leaves(partial):
    import jax
    return [partial.counts] + [np.asarray(x)
                               for x in jax.tree.leaves(partial.states)]


def _same(a, b):
    assert a.segment is b.segment
    assert list(a.states) == list(b.states)
    for x, y in zip(_leaves(a), _leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y, equal_nan=x.dtype.kind == "f")


def _one_at_a_time(work):
    return [grouping.run_grouped_aggregates([w])[0] for w in work]


def _chunks(route, segments, monkeypatch):
    """The chunk enqueues `batching.run_with_batching` hands the helper for
    `route`'s query: (enqueue, the indices of its segments)."""
    seen = []
    real = batching.run_grouped_aggregates

    def spy(work, check=None):
        seen.extend(work)
        return real(work, check)
    with monkeypatch.context() as m:
        m.setattr(batching, "run_grouped_aggregates", spy)
        query = ROUTES[route][0]
        assert QueryExecutor(_route_segments(route, segments)).run_json(
            query) is not None
    assert all(w.func is batching._enqueue_batch for w in seen)
    return [(w, [p.index for p in w.args[0]]) for w in seen]


def _pairs(route, segments, monkeypatch):
    """`route`'s request as the helper takes it, beside the same segments
    a program each: (enqueue, the per-segment enqueues it stands for)."""
    strategy = ROUTES[route][1]
    alone = [_forced(strategy, functools.partial(
        grouping.enqueue_grouped_aggregate, *args, **kw))
        for args, kw in _calls(route, segments, monkeypatch)]
    if not route.startswith("batched-"):
        return [(w, [w]) for w in alone]
    return [(w, [alone[i] for i in index])
            for w, index in _chunks(route, segments, monkeypatch)]


def _work(route, segments, monkeypatch):
    return [w for w, _ in _pairs(route, segments, monkeypatch)]


# ---------------------------------------------------------------------------
# the helper against one program at a time
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route", sorted(ROUTES))
def test_each_route_equals_one_at_a_time(route, segments, kernel_build,
                                         monkeypatch):
    pairs = _pairs(route, segments, monkeypatch)
    work = [w for w, _ in pairs]
    hits = cascade.code_domain_stats().snapshot()["hits"]
    before = dispatch_mod.count()
    got = grouping.run_grouped_aggregates(work)
    dispatched = dispatch_mod.count() - before
    want = _one_at_a_time([a for _, alone in pairs for a in alone])
    assert len(got) == len(want) \
        == len(_route_segments(route, segments))
    for a, b in zip(got, want):
        _same(a, b)
    if route.startswith("batched-"):
        assert [len(alone) for _, alone in pairs] == [8, 4, 2]
    strategy = ROUTES[route][2]
    if strategy is not None:
        assert {p.spec.strategy for p in got} == {strategy}
        assert dispatched == len(work) \
            and sum(int(p.counts.sum()) for p in got) > 0
    elif route == "constFalse":
        assert dispatched == 0 and not any(p.counts.any() for p in got)
    else:
        assert cascade.code_domain_stats().snapshot()["hits"] - hits == 4


def test_a_request_that_mixes_every_route_keeps_bits_and_order(
        segments, kernel_build, monkeypatch):
    pairs = [pair for route in sorted(ROUTES)
             for pair in _pairs(route, segments, monkeypatch)]
    # interleave the routes, so neighbours in the queue differ: stacked
    # chunks between per-segment programs
    pairs = pairs[0::2] + pairs[1::2]
    work = [w for w, _ in pairs]
    fetches = []
    real = grouping.fetch_partials

    def spy(targets, outs, **attrs):
        fetches.append((len(targets), attrs))
        return real(targets, outs, **attrs)
    monkeypatch.setattr(grouping, "fetch_partials", spy)
    got = grouping.run_grouped_aggregates(work)
    # ONE fetch: every route but constFalse left a program's outputs
    # pending, a segment's or a chunk's of 8, 4 or 2
    assert fetches == [(12 + 3 * STACKED, {"programs": 12 + 3 * 3})]
    want = _one_at_a_time([a for _, alone in pairs for a in alone])
    assert len(got) == len(want) == 14 + 3 * STACKED
    for a, b in zip(got, want):
        _same(a, b)
    assert {p.spec.strategy for p in got} >= {
        "mixed", "blocked", "mm", "pallas", "megakernel"}


@pytest.mark.parametrize("kind", ["topN", "groupBy"])
def test_cross_query_chunk_mates_equal_one_at_a_time(kind):
    """`run_multi_with_batching` over two requests whose days overlap: the
    mates of one stacked chunk come from both, and each request's partials
    equal its own run a program a segment, bit for bit and in order."""
    segs = list(_small_segments()[:STACKED])
    asked = [_small_query(kind, f"df-x-{kind}-{n}", first=first, end=end)
             for n, (first, end) in enumerate([(0, 8), (4, STACKED)])]
    queries = [query_from_json(q) for q in asked]
    mates = []
    fused = engines.make_aggregate_partials_multi(
        [(q, segs, None) for q in queries],
        on_batch=lambda n_queries, *_: mates.append(n_queries))
    assert 2 in mates
    assert [len(got.partials) for got in fused] == [8, 10]
    for q, got in zip(asked, fused):
        alone = engines.make_aggregate_partials(query_from_json(dict(
            q, context=dict(q["context"], batchSegments=False))), segs)
        assert len(alone.partials) == len(got.partials)
        for a, b in zip(got.partials, alone.partials):
            _same(a, b)


def test_run_grouped_aggregate_is_the_helper_over_one(segments, monkeypatch):
    (args, kw), _ = _calls("blocked", segments, monkeypatch)
    single = grouping.run_grouped_aggregate(*args, **kw)
    helper, = grouping.run_grouped_aggregates(
        [functools.partial(grouping.enqueue_grouped_aggregate, *args, **kw)])
    _same(single, helper)


# ---------------------------------------------------------------------------
# the request's one engine/fetch
# ---------------------------------------------------------------------------

def _traced(executor, query, qid):
    query = dict(query, context=dict(query.get("context", {}), queryId=qid))
    rows = QueryLifecycle(executor).run_json(query)
    return rows, qtrace.trace_store().spans(qid)


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.fixture(scope="module")
def small_segments():
    """tests/test_batch_served.py's 29 segments: two shape buckets, a
    16 + 8 + 2 chunking of the first, a 2 of the second, one straggler."""
    return list(_small_segments())


@pytest.mark.parametrize("kind", ["topN", "timeseries", "groupBy"])
def test_chunks_and_stragglers_share_one_fetch(small_segments, kind):
    ex = QueryExecutor(small_segments)
    before = batching.stats().snapshot()
    rows, spans = _traced(ex, _small_query(kind, f"df-batched-{kind}"),
                          f"df-batched-{kind}")
    after = batching.stats().snapshot()
    assert after["batches"] - before["batches"] == 4
    assert after["fallbackSegments"] - before["fallbackSegments"] == 1
    dispatches = _named(spans, "engine/batch/dispatch")
    assert sorted(s["attrs"]["segments"] for s in dispatches) == [2, 2, 8, 16]
    alone, = _named(spans, "engine/segment")
    partials, = _named(spans, "engine/partials")
    fetch, = _named(spans, "engine/fetch")
    assert fetch["parentId"] == partials["spanId"]
    assert fetch["attrs"]["programs"] == len(dispatches) + 1 == 5
    assert fetch["attrs"]["bytes"] > 0
    # the fetch follows the last enqueue: the straggler's
    assert fetch["startMs"] >= alone["startMs"] + alone["durationMs"] - 0.5
    assert all(s["startMs"] <= alone["startMs"] for s in dispatches)
    # the same request a program a segment: one fetch of 29, the same rows
    query = _small_query(kind, f"df-alone-{kind}")
    query["context"]["batchSegments"] = False
    rows_alone, spans = _traced(ex, query, f"df-alone-{kind}")
    assert rows_alone == rows and rows
    fetch, = _named(spans, "engine/fetch")
    assert fetch["attrs"]["programs"] == 29 \
        == len(_named(spans, "engine/segment")) \
        == len(_named(spans, "engine/dispatch"))
    assert not _named(spans, "engine/batch/plan")


@pytest.mark.parametrize("bound,waves", [(1, [1] * 5), (2, [2, 2, 1]),
                                         (3, [3, 2]), (5, [5]), (6, [5])],
                         ids=["1", "2", "3", "5", "6"])
def test_the_byte_bound_drains_in_waves(monkeypatch, bound, waves):
    """`bound` programs' outputs reach contracts.PENDING_FETCH_BYTES: the
    helper fetches what is pending there and goes on, and answers the same."""
    segs = DataGenerator(SCHEMA, seed=5).segments(5, 1_500, IV,
                                                  datasource="df")
    ex = QueryExecutor(segs)
    want, spans = _traced(ex, _groupby(), f"df-bound-free-{bound}")
    fetch, = _named(spans, "engine/fetch")
    assert fetch["attrs"]["programs"] == 5 and fetch["attrs"]["bytes"] % 5 == 0
    monkeypatch.setattr(grouping, "PENDING_FETCH_BYTES",
                        bound * fetch["attrs"]["bytes"] // 5)
    got, spans = _traced(ex, _groupby(), f"df-bound-{bound}")
    assert got == want and want
    fetches = sorted(_named(spans, "engine/fetch"),
                     key=lambda s: s["startMs"])
    assert [s["attrs"]["programs"] for s in fetches] == waves
    assert sum(s["attrs"]["bytes"] for s in fetches) == fetch["attrs"]["bytes"]
    assert len(_named(spans, "engine/segment")) == 5


@pytest.mark.parametrize("share", [0, 0.3, 0.6, 1, 2])
def test_the_bound_counts_a_chunks_outputs_too(small_segments, monkeypatch,
                                               share):
    """A chunk's pending bytes are its ONE stacked output's, K segments'
    worth counted once: a bound of `share` of the request's bytes drains in
    the waves those bytes say, and answers the same."""
    ex = QueryExecutor(small_segments)
    qid = f"df-chunk-free-{share}"
    want, spans = _traced(ex, _small_query("groupBy", qid), qid)
    starts = sorted(_named(spans, "engine/fetch/start"),
                    key=lambda s: s["startMs"])
    sizes = [s["attrs"]["bytes"] for s in starts]
    fetch, = _named(spans, "engine/fetch")
    assert sum(sizes) == fetch["attrs"]["bytes"]
    # chunks of 16, 8 and 2 of one bucket: bytes in proportion to K
    assert sizes[0] == 2 * sizes[1] == 8 * sizes[2] > 0
    bound = max(1, int(share * sum(sizes)))
    waves, drained, n, pending = [], [], 0, 0
    for size in sizes:
        n, pending = n + 1, pending + size
        if pending >= bound:
            waves.append(n)
            drained.append(pending)
            n = pending = 0
    if n:
        waves.append(n)
        drained.append(pending)
    monkeypatch.setattr(grouping, "PENDING_FETCH_BYTES", bound)
    got, spans = _traced(ex, _small_query("groupBy", f"df-chunk-{share}"),
                         f"df-chunk-{share}")
    assert got == want and want
    fetches = sorted(_named(spans, "engine/fetch"),
                     key=lambda s: s["startMs"])
    assert [s["attrs"]["programs"] for s in fetches] == waves
    assert [s["attrs"]["bytes"] for s in fetches] == drained


def test_the_bound_is_the_one_constant():
    from druid_tpu.engine import contracts
    assert grouping.PENDING_FETCH_BYTES is contracts.PENDING_FETCH_BYTES
    assert contracts.PENDING_FETCH_BYTES == 256 * 1024 ** 2


# ---------------------------------------------------------------------------
# what raises, and what it leaves behind
# ---------------------------------------------------------------------------

class _Cancelled(Exception):
    pass


def _no_fetch(monkeypatch):
    fetched = []

    def spy(*a, **kw):
        fetched.append(a)
        raise AssertionError("a fetch after the request failed")
    monkeypatch.setattr(grouping, "fetch_partials", spy)
    return fetched


@pytest.mark.parametrize("at", [1, 2, 3])
def test_check_runs_between_enqueues_and_before_the_fetch(segments,
                                                          monkeypatch, at):
    """`check` is not asked before the first enqueue (the caller did), is
    asked before every other and once more before the fetch; where it
    raises, nothing more is enqueued and nothing is fetched."""
    work = _work("blocked", segments, monkeypatch) \
        + _work("mm", segments, monkeypatch)[:1]
    enqueued, asked = [], []

    def counted(i, enqueue):
        def run():
            enqueued.append(i)
            return enqueue()
        return run

    def check():
        asked.append(len(enqueued))
        if len(asked) == at:
            raise _Cancelled(f"at check {at}")
    fetched = _no_fetch(monkeypatch)
    with pytest.raises(_Cancelled, match=f"at check {at}"):
        grouping.run_grouped_aggregates(
            [counted(i, w) for i, w in enumerate(work)], check)
    assert asked == [1, 2, 3][:at] and enqueued == list(range(at))
    assert not fetched


def test_an_enqueue_that_raises_surfaces_its_error_and_no_other(
        segments, monkeypatch):
    work = _work("blocked", segments, monkeypatch)
    ran = []

    def broken():
        ran.append("broken")
        raise ValueError("segment 2 cannot stage")

    def never():
        ran.append("never")
        raise AssertionError("enqueued after the request failed")
    fetched = _no_fetch(monkeypatch)
    with pytest.raises(ValueError, match="segment 2 cannot stage"):
        grouping.run_grouped_aggregates(work + [broken, never])
    assert ran == ["broken"] and not fetched


def test_a_cancelled_request_leaves_no_carry_taken_and_unparked(
        kernel_build, monkeypatch):
    """The megakernel's carry is taken, handed to the program and parked
    again inside ONE enqueue: a request that stops between two enqueues
    (or fails in one) owes the pool nothing — donorguard's runtime witness
    sees every take re-parked or discarded — and the next tick answers the
    same."""
    sys.path.insert(0, str(REPO))
    from tools.druidlint.donorwitness import DonorWitness
    from tests.test_megakernel import _proj_setup
    segs, q = _proj_setup(monkeypatch)
    monkeypatch.setattr(pallas_agg, "_BROKEN", None)   # restored if latched
    query = query_from_json(dict(q, context={"batchSegments": False}))
    prev = megakernel.set_force_carry(True)
    try:
        with warnings.catch_warnings(), DonorWitness(str(REPO)) as w:
            warnings.simplefilter("ignore")
            ex = QueryExecutor(segs)
            first = ex.run_json(q)            # parks a carry a segment
            assert ex.run_json(q) == first    # takes and re-parks both

            asked = []

            def check():
                asked.append(1)
                if len(asked) == 2:           # after the first enqueue
                    raise _Cancelled("between two enqueues")
            # the second segment's BUILD fails after its take: the take is
            # discarded, the first segment's outputs are dropped
            builds = []
            real_build = grouping._build_kernel_program

            def failing_build(fn, *args):
                builds.append(1)
                if len(builds) == 2:
                    raise RuntimeError("the runtime lost the device")
                return real_build(fn, *args)
            with monkeypatch.context() as m:
                fetched = _no_fetch(m)
                with pytest.raises(_Cancelled):
                    engines.make_aggregate_partials(query, segs, check=check)
                m.setattr(grouping, "_build_kernel_program", failing_build)
                with pytest.raises(RuntimeError, match="lost the device"):
                    engines.make_aggregate_partials(query, segs)
                assert not fetched

            assert ex.run_json(q) == first
            assert w.counts.get("take", 0) > 0, w.summary()
            assert w.all_violations() == [], w.summary()
            assert pallas_agg.broken_reason() is None
        assert ex.run_json(q) == first
    finally:
        megakernel.set_force_carry(prev)


# ---------------------------------------------------------------------------
# the copies start at the enqueue
# ---------------------------------------------------------------------------

class _Output:
    """A device output as the helper sees one: bytes, a copy that can be
    started, and a host value."""

    def __init__(self, value):
        self.value = np.asarray(value)
        self.nbytes = self.value.nbytes
        self.copies = 0

    def copy_to_host_async(self):
        self.copies += 1

    def __array__(self, dtype=None, copy=None):
        return self.value if dtype is None else self.value.astype(dtype)


def test_copies_start_at_the_enqueue_and_host_outputs_pass(segments,
                                                           monkeypatch):
    """An output with `copy_to_host_async` has it called before the next
    program is enqueued; one without (numpy, as the run-domain route may
    leave) is not asked and does not raise. Both come back as they are."""
    import jax
    (args, kw), (args2, kw2) = _calls("blocked", segments, monkeypatch)
    target, (counts, states) = grouping.enqueue_grouped_aggregate(*args, **kw)
    want = grouping.run_grouped_aggregate(*args, **kw)
    wrapped = [_Output(counts)] + [_Output(x)
                                   for x in jax.tree.leaves(states)]
    states_w = jax.tree.unflatten(jax.tree.structure(states), wrapped[1:])
    seen = []

    def first():
        return target, (wrapped[0], states_w)

    def second():
        seen.append([o.copies for o in wrapped])
        return target, jax.device_get((counts, states))   # host arrays

    a, b = grouping.run_grouped_aggregates([first, second])
    assert seen == [[1] * len(wrapped)]
    _same(a, want)
    _same(b, want)


def test_a_stacked_output_is_started_waited_fetched_and_counted_once(
        segments, monkeypatch):
    """The K StackedSlots of a chunk name ONE output tree: each of its
    leaves has its copy started once at the enqueue, is handed to the wait
    and to the `device_get` once and counted in `bytes` once, and slot i
    comes back as row i."""
    import jax
    (args, kw), _ = _calls("blocked", segments, monkeypatch)
    target, out = grouping.enqueue_grouped_aggregate(*args, **kw)
    want = grouping.run_grouped_aggregate(*args, **kw)
    counts, states = jax.device_get(out)
    K = 4
    rows = [counts] + jax.tree.leaves(states)
    # slot i holds segment's own values + i: a row from another slot differs
    wrapped = [_Output(np.stack([x + i for i in range(K)])) for x in rows]
    stacked = (wrapped[0], jax.tree.unflatten(jax.tree.structure(states),
                                              wrapped[1:]))
    handed = {}
    for name in ("block_until_ready", "device_get"):
        def spy(x, real=getattr(jax, name), name=name):
            handed.setdefault(name, []).extend(
                id(leaf) for leaf in jax.tree_util.tree_leaves(x))
            return real(x)
        monkeypatch.setattr(jax, name, spy)
    seen = []

    def chunk():
        return [(target, grouping.StackedSlot(stacked, i)) for i in range(K)]

    def probe():
        seen.append([o.copies for o in wrapped])
        return want                          # a finished partial

    store = qtrace.TraceStore()
    with qtrace.root_span("stacked", store=store) as root:
        got = grouping.run_grouped_aggregates([chunk, probe])
    assert seen == [[1] * len(wrapped)]
    ids = sorted(id(o) for o in wrapped)
    assert sorted(handed["block_until_ready"]) == ids \
        == sorted(handed["device_get"])
    spans = store.spans(root.trace_id)
    nbytes = sum(o.nbytes for o in wrapped)
    start, = _named(spans, "engine/fetch/start")
    assert start["attrs"]["leaves"] == len(wrapped)
    assert start["attrs"]["bytes"] == nbytes
    fetch, = _named(spans, "engine/fetch")
    assert fetch["attrs"]["bytes"] == nbytes
    assert fetch["attrs"]["programs"] == 1
    release, = _named(spans, "engine/fetch/release")
    assert release["attrs"]["segments"] == K
    assert release["attrs"]["arrays"] == 0     # host stand-ins, not jax.Array
    assert got[K] is want and len(got) == K + 1
    for i, partial in enumerate(got[:K]):
        assert np.array_equal(partial.counts, want.counts + i)
        for x, y in zip(_leaves(partial)[1:], _leaves(want)[1:]):
            assert np.array_equal(x, y + i)
