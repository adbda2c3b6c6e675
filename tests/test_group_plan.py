"""The seam between the one planner, the one traced body and the three
builders of the aggregation program (engine/grouping.py, engine/batching.py,
parallel/distributed.py), over the shapes the builders would disagree on
most easily:

  (a) the per-segment program (`_build_device_fn` over `_assemble_aux`) and
      the stacked body at K = 1 (`traced_segment` over `stacked_origins`
      + `assemble_stacked_aux`, unrolled as the batched program does) give bit-identical counts
      and states on the same staged segment — and the counts the served
      path answers with;
  (b) `GroupPlan.columns` / `col_dtypes` are what the stage phase stages;
  (c) `stacked_origins` clips bounds at both ends of int32, splits bucket
      origins into (offset, whole periods), and is `_assemble_aux`'s head;
and, by AST, that the planner's calls and the `engine/fetch` span have one
home each.
"""
import ast
import functools

import numpy as np
import pytest

from druid_tpu.data import cascade
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine import QueryExecutor, batching, engines, grouping
from druid_tpu.engine import pallas_agg
from druid_tpu.parallel import distributed
from druid_tpu.utils.granularity import Granularity
from druid_tpu.utils.intervals import Interval

IV = Interval.of("2026-03-01", "2026-03-03")
SCHEMA = (
    ColumnSpec("dimA", "string", cardinality=7),
    ColumnSpec("dimB", "string", cardinality=40, distribution="zipf"),
    ColumnSpec("dimHi", "string", cardinality=300),
    ColumnSpec("metLong", "long", low=-50, high=900),
    ColumnSpec("metFloat", "float", distribution="normal", mean=10.0,
               std=3.0),
)
AGGS = [{"type": "count", "name": "n"},
        {"type": "longSum", "name": "ls", "fieldName": "metLong"},
        {"type": "floatMax", "name": "fx", "fieldName": "metFloat"}]


def _query(**over):
    q = {"queryType": "groupBy", "dataSource": "gp", "intervals": [str(IV)],
         "granularity": "all", "dimensions": ["dimA", "dimB"],
         "aggregations": AGGS}
    q.update(over)
    return q


def _force_projection(monkeypatch):
    monkeypatch.setattr(grouping, "FORCE_STRATEGY", "projection")
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)


#: shape -> (query, what to arm first, (key_mode, bucket_mode) the staged
#: plan must end in)
SHAPES = {
    "dense_all": (_query(), None, ("dense", "all")),
    "dense_uniform": (_query(granularity="hour"), None, ("dense", "uniform")),
    "host_keyed_projection": (_query(), _force_projection, ("host", "all")),
    "host_buckets_two_intervals": (
        _query(granularity="hour",
               intervals=["2026-03-01/2026-03-01T12", "2026-03-02/2026-03-03"]),
        None, ("dense", "host")),
    "bitmap_filter": (
        _query(filter={"type": "in", "dimension": "dimHi",
                       "values": [f"v{i:08d}" for i in range(0, 300, 3)]}),
        None, ("dense", "all")),
    "filtered_aggregator": (
        _query(aggregations=AGGS + [{
            "type": "filtered", "aggregator": {
                "type": "longSum", "name": "fs", "fieldName": "metLong"},
            "filter": {"type": "selector", "dimension": "dimA",
                       "value": "v00000001"}}]),
        None, ("dense", "all")),
    "virtual_column": (
        _query(granularity="day",
               virtualColumns=[{"type": "expression", "name": "v",
                                "expression": "metLong * 2 + 1",
                                "outputType": "LONG"}],
               aggregations=AGGS + [{"type": "longSum", "name": "vs",
                                     "fieldName": "v"}]),
        None, ("dense", "uniform")),
    "numeric_dimension": (_query(dimensions=["dimA", "metLong"]), None,
                          ("dense", "all")),
}


@pytest.fixture(scope="module")
def segment():
    return DataGenerator(SCHEMA, seed=28).segments(
        1, 6_000, IV, datasource="gp")[0]


def _staged(segment, shape, monkeypatch):
    """Run the shape's query through the served engine once, keeping what
    it handed `enqueue_grouped_aggregate` (the enqueue of
    `run_grouped_aggregates`, one call a segment); then plan and stage the
    same call again, phase by phase: (call arguments, the counts the served
    program left for the fetch, plan, (arrays, packs, cascades))."""
    query, arm, modes = SHAPES[shape]
    if arm is not None:
        arm(monkeypatch)
    # the row program is what this file compares: keep the run-domain
    # route out of the way (its own parity is tests/test_cascade.py's)
    prev = cascade.set_run_domain_enabled(False)
    calls = []
    real = grouping.enqueue_grouped_aggregate

    def spy(seg, intervals, granularity, dims, aggs, flt, **kw):
        entry = real(seg, intervals, granularity, dims, aggs, flt, **kw)
        calls.append(((seg, intervals, granularity, dims, aggs, flt,
                       kw.get("virtual_columns", ())), entry))
        return entry
    monkeypatch.setattr(engines, "enqueue_grouped_aggregate", spy)
    try:
        assert QueryExecutor([segment]).run_json(query)
        (args, (_target, (counts, _states))), = calls
        seg, intervals, granularity, dims, aggs, flt, vcs = args
        plan, route = grouping._plan_segment(
            seg, intervals, granularity, dims, aggs, flt, (), vcs, None)
        assert route is None
        staged = grouping._stage_segment(seg, plan)
    finally:
        cascade.set_run_domain_enabled(prev)
    assert (plan.spec.key_mode, plan.spec.bucket_mode) == modes
    return args, np.asarray(counts, dtype=np.int64), plan, staged


def _leaves(tree):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_per_segment_program_equals_stacked_body_at_k1(segment, shape,
                                                       monkeypatch):
    import jax
    (seg, intervals, _g, _d, _a, _f, _v), served, plan, staged = _staged(
        segment, shape, monkeypatch)
    arrays, _packs, _cascades = staged
    spec, kernels = plan.spec, plan.kernels
    if spec.strategy == "megakernel":
        pytest.skip("the carry-taking call shape is test_megakernel's")

    fn = grouping._build_device_fn(spec, len(intervals), plan.filter_node,
                                   kernels, plan.vc_plans)
    aux = grouping._assemble_aux(spec, seg, intervals, plan.filter_node,
                                 kernels, plan.vc_plans, plan.vc_luts)
    counts, states = fn(arrays, aux)

    body = functools.partial(grouping.traced_segment, spec, plan.filter_node,
                             kernels, plan.vc_plans)
    time0s, iv_rel, bucket_off = grouping.stacked_origins(
        [seg], [intervals], [spec])
    stacked_aux = grouping.assemble_stacked_aux(
        spec,
        plan.filter_node.aux_arrays() if plan.filter_node is not None else (),
        [a for k in kernels for a in k.aux_arrays()], plan.vc_luts)
    (k1_counts, k1_states), = jax.jit(
        lambda blocks, t0s, ivs, offs, a: tuple(
            body(blocks[i], t0s[i], ivs[i], offs[i], a) for i in range(1)))(
        (arrays,), time0s, iv_rel, bucket_off, stacked_aux)

    assert np.array_equal(np.asarray(counts), np.asarray(k1_counts))
    for a, b in zip(_leaves(states), _leaves(k1_states)):
        assert a.dtype == b.dtype and np.array_equal(a, b, equal_nan=True)
    # and both are the program the served path ran
    assert np.array_equal(np.asarray(counts, dtype=np.int64), served)
    assert int(served.sum()) > 0


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_plan_columns_and_dtypes_are_what_stages(segment, shape,
                                                 monkeypatch):
    (seg, *_), _served, plan, (arrays, _packs, _cascades) = _staged(
        segment, shape, monkeypatch)
    block = seg.device_block(list(plan.columns), perm=plan.perm,
                             perm_key=plan.perm_key)
    assert set(block.arrays) == {"__time_offset", "__valid", *plan.columns}
    _, dense = cascade.split_resident(arrays)
    staged = {name: np.dtype(a.dtype) for name, a in dense.items()
              if name in plan.col_dtypes}
    assert staged == plan.col_dtypes
    # whatever else stages is filter words, never a column the plan missed
    words = set(arrays) - set(plan.col_dtypes)
    assert not words & (set(seg.dims) | set(seg.metrics)), words
    if shape == "bitmap_filter":
        assert "dimHi" not in plan.columns and words


def _far_segment():
    """One hour of data whose start is far from both ends of the query."""
    gen = DataGenerator(SCHEMA[:1] + SCHEMA[3:4], seed=3)
    return gen.segments(1, 64, Interval.of("2026-06-01", "2026-06-01T01"),
                        datasource="far")[0]


@pytest.mark.parametrize("granularity", ["all", "hour"])
def test_stacked_origins_clip_at_both_ends_of_int32(granularity):
    seg = _far_segment()
    t0 = seg.interval.start
    lo, hi = -(2**31) + 1, 2**31 - 1
    # a year before to a year after: both bounds leave int32 (±24.8 days)
    wide = [Interval.of("2025-06-01", "2027-06-01")]
    # and an interval that STARTS past the segment by more than int32
    late = [Interval.of("2026-09-01", "2026-09-02")]
    g = Granularity.of(granularity)
    specs = [grouping.make_group_spec(seg, ivs, g, ()) for ivs in (wide, late)]
    time0s, iv_rel, bucket_off = grouping.stacked_origins(
        [seg, seg], [wide, late], specs, K=4)
    assert time0s.dtype == np.int64 and list(time0s) == [t0, t0, 0, 0]
    assert iv_rel.dtype == np.int32 and iv_rel.shape == (4, 1, 2)
    assert iv_rel[0].tolist() == [[lo, hi]]
    assert iv_rel[1].tolist() == [[hi, hi]]
    assert not iv_rel[2:].any()         # padding: no interval, so no row
    # a bucket origin does not clip, it splits: (offset within a period,
    # whole periods) — a year of hours before the segment, 92 days after
    assert bucket_off.dtype == np.int32 and bucket_off.shape == (4, 2)
    if granularity == "all":
        assert not bucket_off.any()
    else:
        assert bucket_off.tolist() == [[0, -365 * 24], [0, 92 * 24],
                                       [0, 0], [0, 0]]
    # an in-range origin is the number the group spec holds
    near = [Interval.of("2026-05-31", "2026-06-02")]
    spec = grouping.make_group_spec(seg, near, g, ())
    _, iv1, off1 = grouping.stacked_origins([seg], [near], [spec])
    assert iv1[0].tolist() == [[near[0].start - t0, near[0].end - t0]]
    rest, whole = off1[0].tolist()
    assert 0 <= rest < max(spec.uniform_period, 1)
    assert rest + whole * spec.uniform_period == spec.uniform_first_offset


@pytest.mark.parametrize("granularity", ["all", "hour"])
def test_assemble_aux_head_is_stacked_origins(granularity):
    seg = _far_segment()
    ivs = [Interval.of("2026-05-31", "2026-06-02")]
    spec = grouping.make_group_spec(seg, ivs, Granularity.of(granularity), ())
    time0s, iv_rel, bucket_off = grouping.stacked_origins([seg], [ivs], [spec])
    aux = grouping._assemble_aux(spec, seg, ivs, None, [])
    for got, want in zip(aux[:3], (time0s[0], iv_rel[0], bucket_off[0])):
        assert np.asarray(got).dtype == want.dtype
        assert np.array_equal(got, want)
    tail = grouping.assemble_stacked_aux(spec, (), ())
    assert len(aux) == 3 + len(tail)
    assert all(np.array_equal(a, b) for a, b in zip(aux[3:], tail))


# ---------------------------------------------------------------------------
# one home each, by AST (the style of tests/test_sharded_spans.py's
# test_fallback_reasons_are_the_closed_set_the_source_uses)
# ---------------------------------------------------------------------------

PLANNER_CALLS = {"plan_filter", "make_kernel", "plan_virtual_columns",
                 "assign_bitmap_slots"}
BUILDERS = (grouping, batching, distributed)


def _calls(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            yield node, (f.attr if isinstance(f, ast.Attribute)
                         else getattr(f, "id", None))


def _tree(module):
    with open(module.__file__) as f:
        return ast.parse(f.read())


@pytest.mark.parametrize("module", [batching, distributed],
                         ids=["batching", "distributed"])
def test_stacked_builders_plan_only_through_the_one_planner(module):
    tree = _tree(module)
    names = {name for _, name in _calls(tree)}
    assert not names & PLANNER_CALLS
    assert "needed_columns" not in names
    assert "plan_grouped_aggregate" in names
    imported = {a.name for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom) for a in n.names}
    assert not imported & (PLANNER_CALLS | {"needed_columns",
                                            "make_group_spec"})


def test_the_planner_and_the_fetch_span_have_one_home():
    """In `engine/grouping.py` each planning call sits in
    `plan_grouped_aggregate` alone; of the three builder modules only
    `fetch_partials` opens `engine/fetch`, and only the request's helper,
    the cross-query chunk and the sharded program call it."""
    fetch_sites, fetch_callers, planner_sites = [], [], []
    for module in BUILDERS:
        for fn in _tree(module).body:
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node, name in _calls(fn):
                if name in ("trace_span", "span") and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and node.args[0].value == "engine/fetch":
                    fetch_sites.append((module.__name__, fn.name))
                if name == "fetch_partials":
                    fetch_callers.append((module.__name__, fn.name))
                if module is grouping and (
                        name in PLANNER_CALLS or name in ("needed_columns",
                                                          "make_group_spec")):
                    planner_sites.append((name, fn.name))
    assert fetch_sites == [("druid_tpu.engine.grouping", "fetch_partials")]
    assert fetch_callers == [
        ("druid_tpu.engine.grouping", "run_grouped_aggregates"),
        ("druid_tpu.engine.batching", "_run_batch"),
        ("druid_tpu.parallel.distributed", "_run_sharded")]
    assert {fn for _, fn in planner_sites} == {"plan_grouped_aggregate"}
    assert {name for name, _ in planner_sites} == PLANNER_CALLS | {
        "needed_columns", "make_group_spec"}
