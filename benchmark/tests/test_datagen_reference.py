"""The generator, and the reference against the served answers (CPU, tiny)."""
import json
import os

import numpy as np
import pytest

from benchmark.datagen import basic
from benchmark.harness import traffic, verify
from benchmark.reference import engine as reference
from benchmark.tests.util import BENCH


def _config(name="basic-day5m-1chip", **over):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return dict(json.load(f), **over)


TINY = dict(segments=4, rows_per_segment=4096)


def test_generator_is_the_seeds_and_holds_the_schema():
    cfg = _config(**TINY)
    big_seed = 2 ** 31 + 12345
    a = basic.make_segment(cfg, big_seed, 2)
    b = basic.make_segment(cfg, big_seed, 2)
    c = basic.make_segment(cfg, big_seed + 1, 2)
    assert set(a) == {"__time"} | {s["name"] for s in cfg["schema"]}
    assert len(cfg["schema"]) == 8
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["dimZipf"], c["dimZipf"])
    start = basic.segment_start_ms(cfg, 2)
    assert start <= a["__time"][0] and a["__time"][-1] < start + basic.DAY_MS
    assert np.all(np.diff(a["__time"]) >= 0)
    assert np.array_equal(a["dimSequential"][:1001] ,
                          np.arange(1001) % 1000)
    assert a["dimZipf"].min() >= 1 and a["dimZipf"].max() <= 100
    assert set(np.unique(a["dimNull"])) == {basic.NULL_RAW}
    half = (a["dimSequentialHalfNull"] == basic.NULL_RAW).mean()
    assert 0.45 < half < 0.55
    assert a["metLongSequential"].max() == 4095      # 4,096 rows of 0..9,999
    assert a["metLongUniform"].min() >= 0 and a["metLongUniform"].max() <= 499
    assert a["metFloatNormal"].dtype == np.float32
    assert abs(float(a["metFloatNormal"].mean()) - 5000.0) < 0.2
    assert a["metFloatZipf"].min() >= 0 and a["metFloatZipf"].max() <= 999
    # Zipf 1.0: value 1 about twice value 2 about three times value 3 / 2 ...
    counts = np.bincount(a["dimZipf"], minlength=101)
    assert counts[1] > counts[2] > counts[4] > counts[16]


def test_the_real_configs_say_what_the_issue_says():
    one, four = _config(), _config("basic-day5m-mesh4")
    for cfg in (one, four):
        assert cfg["segments"] == 20 and cfg["rows_per_segment"] == 5_000_000
        assert sorted(cfg["reduced"]) == [
            "dimHyperUnique", "dimMultivalEnumerated", "dimMultivalEnumerated2",
            "dimMultivalSequentialWithNulls", "dimUniform"]
        assert not {s["name"] for s in cfg["schema"]} & set(cfg["reduced"])
        assert set(cfg["guarantees"]) >= {"segments", "answers",
                                          "partial_results", "caches"}
    assert one["schema"] == four["schema"]
    assert (one["chips"], one["mesh"]) == (1, None)
    assert (four["chips"], four["mesh"]) == (4, "all")
    assert one["source"] != four["source"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Tiny data on disk, one deployment, the raw columns beside it."""
    from druid_tpu.engine import pallas_agg

    from benchmark.harness import deploy
    pallas_agg.force_interpret(True)
    cfg = _config(**TINY)
    seed = 3_000_000_011
    seg_dir, raw_dir, facts = deploy.ensure_data(
        cfg, seed, str(tmp_path_factory.mktemp("data")))
    assert facts["generated"]
    d = deploy.Deployment(cfg, seg_dir)
    try:
        yield cfg, seed, d, reference.RawData(raw_dir, cfg), (seg_dir, raw_dir)
    finally:
        d.stop()
        pallas_agg.force_interpret(False)


def test_data_on_disk_is_reused_for_its_seed_only(served):
    from benchmark.harness import deploy
    cfg, seed, _d, _data, (seg_dir, _raw) = served
    directory = os.path.dirname(seg_dir)
    assert deploy.ensure_data(cfg, seed, directory)[2]["generated"] is False
    assert deploy.data_signature(cfg, seed) != deploy.data_signature(cfg, seed + 1)
    assert deploy.data_signature(cfg, seed) != \
        deploy.data_signature(dict(cfg, rows_per_segment=8192), seed)


WORKLOADS = ["analyst-groupby", "dashboard-mix"]


@pytest.mark.parametrize("name", WORKLOADS)
def test_reference_equals_served_answers_for_every_template(served, name):
    from benchmark import run
    cfg, seed, d, data, _dirs = served
    workload = run.load_workload(name)
    plan = traffic.warm_plan(BENCH, workload, cfg, seed) + \
        traffic.make_plan(BENCH, workload, cfg, seed, 8.0)[:40]
    templates = set()
    for p in plan:
        got, context = run.post_query(d.port, p["query"], timeout=120)
        assert context is None
        assert verify.check(data, p["query"], got) is None, p["query"]
        templates.add(p["template"])
    assert templates == {e["query"] for e in workload["templates"]}


def test_in_filter_and_long_min_which_no_cell_uses_yet(served):
    """The reference's vocabulary is wider than today's templates, because
    a later PR may add templates and may not edit the reference."""
    from benchmark import run
    cfg, _seed, d, data, _dirs = served
    q = {"queryType": "groupBy", "dataSource": "basic", "granularity": "all",
         "intervals": traffic._gen_slot({"gen": "all_days"}, None, 1, cfg)[0],
         "dimensions": ["dimSequentialHalfNull"],
         "aggregations": [{"type": "count", "name": "n"},
                          {"type": "longMin", "name": "lo",
                           "fieldName": "metLongUniform"}],
         "filter": {"type": "in", "dimension": "dimZipf",
                    "values": ["1", "2", "77"]}}
    got, _context = run.post_query(d.port, q, timeout=120)
    assert verify.check(data, q, got) is None
    assert got[0]["event"]["dimSequentialHalfNull"] == ""      # the null
    assert reference.columns_read(q) == ["__time", "dimSequentialHalfNull",
                                         "dimZipf", "metLongUniform"]
    assert reference.rows_scanned(data, q) == 4 * 4096


def test_reference_never_reads_the_program():
    """The yardstick's independence, as far as imports can show it."""
    import ast
    for rel in ("reference/engine.py", "datagen/basic.py", "harness/traffic.py",
                "harness/loadgen.py", "harness/layers.py", "harness/verify.py"):
        with open(os.path.join(BENCH, rel)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            for n in names:
                assert not n.startswith(("druid_tpu", "jax")), (rel, n)


def test_verify_sees_a_wrong_row_and_frees_top_n_ties(served):
    cfg, seed, d, data, _dirs = served
    from benchmark import run
    workload = run.load_workload("dashboard-mix")
    plan = traffic.warm_plan(BENCH, workload, cfg, seed)
    gb = next(p for p in plan if p["template"] == "groupby-zipf-by-seq")
    good = reference.answer(data, gb["query"])
    assert verify.check(data, gb["query"], good) is None
    bad = json.loads(json.dumps(good))
    bad[0]["event"]["rows"] += 1
    assert "row 0" in verify.check(data, gb["query"], bad)
    assert "rows" in verify.check(data, gb["query"], good[1:])
    tn = next(p for p in plan if p["template"] == "topn-seq-by-zipf")
    top = reference.answer(data, tn["query"])
    assert verify.check(data, tn["query"], top) is None
    swapped = json.loads(json.dumps(top))
    rows = swapped[0]["result"]
    rows[0], rows[1] = rows[1], rows[0]
    ordered = rows[1]["lsum"] != rows[0]["lsum"]
    assert (verify.check(data, tn["query"], swapped) is not None) == ordered


def test_plans_give_every_seed_the_same_work():
    from benchmark import run
    cfg = _config()
    workload = run.load_workload("dashboard-mix")
    a = traffic.make_plan(BENCH, workload, cfg, 5, 45.0)
    b = traffic.make_plan(BENCH, workload, cfg, 2 ** 31 + 7, 45.0)
    assert len(a) == len(b) == round(workload["loop"]["rate_qps"] * 45)

    def shape(plan):
        out = {}
        for p in plan:
            iv = p["query"].get("intervals", ["/"])[0].split("/")
            days = 0 if iv == ["", ""] else round(
                (reference.parse_instant(iv[1]) -
                 reference.parse_instant(iv[0])) / basic.DAY_MS)
            out[(p["template"], days)] = out.get((p["template"], days), 0) + 1
        return out
    assert shape(a) == shape(b)
    assert [p["query"] for p in a] != [p["query"] for p in b]
    assert all(0 <= p["due_s"] < 45.0 for p in a)
    assert a == traffic.make_plan(BENCH, workload, cfg, 5, 45.0)
    closed = run.load_workload("analyst-groupby-mesh4")
    plan = traffic.make_plan(BENCH, closed, cfg, 9, 45.0)
    pairs = [(p["query"]["filter"]["lower"], p["query"]["filter"]["upper"])
             for p in plan]
    assert len(set(pairs)) == len(pairs) == 180
    assert all(0 <= lo <= 499 and 9500 <= hi <= 9999 for lo, hi in pairs)
    assert run.load_workload("analyst-groupby")["templates"] == closed["templates"]
