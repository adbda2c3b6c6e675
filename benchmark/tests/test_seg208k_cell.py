"""`seg208k-dashboard`: the cell of the small-segment deployment
(`basic-seg208k-1chip`), rehearsed on the CPU at a size that forms the K
ladder, and its seven `batch.*` / `datanode.merge_ms` / `cache.*` metrics."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness import layers
from benchmark.tests.util import BENCH, REPO, RESULT_KEYS

CELL = "seg208k-dashboard"
CONFIG = "basic-seg208k-1chip"
NEW = ["batch.dispatches_per_query", "batch.segments_per_query",
       "batch.fallback_segments_per_query", "batch.padded_rows_per_query",
       "batch.plan_ms", "datanode.merge_ms", "cache.result_hits_per_query"]

#: `rehearse.py` with another cut: its 4 segments never form K = 16. Forty
#: segments of 20,000 rows (rung 32,768; every dimZipf value in every
#: segment, so one shape bucket): 24 -> 16 + 8; the 72- and 168-segment panels
#: and all days are cut to the data's 40 -> 32 + 8 (`traffic.py`). No
#: file is changed.
REHEARSE = """
import argparse, sys
import benchmark.run as run
import jax
load = run.load_json
def cut(kind, name):
    spec = load(kind, name)
    if kind == "configs":
        spec = dict(spec, segments=40, rows_per_segment=20000)
    if kind == "workloads":
        spec = dict(spec, loop=dict(spec["loop"], plan_qps=80))
    return spec
run.load_json = cut
d = jax.devices()[0]
sys.exit(run.run_cell(argparse.Namespace(
    workload=sys.argv[1], seed=3_000_000_019, seconds=float(sys.argv[3]),
    trace=int(sys.argv[2])),
    stub_device={"platform": d.platform, "kind": d.device_kind, "count": 1}))
"""


def _rehearse(trace: int, seconds: float):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", REHEARSE, CELL, str(trace),
                        str(seconds)], capture_output=True, text=True,
                       env=env, timeout=900, cwd=REPO)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_manifest_lists_the_configuration_the_cell_and_its_metrics(manifest):
    from benchmark import run
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, CELL, 1)
    assert entry["why"] == (
        "8 closed-loop panels on 480 x 208k-row segments: topN 1000 / day "
        "timeseries / groupBy 100 over the last 24/72/168 segments, Zipf "
        "literals: batch_agg_* at K<=64, node merge of 24-168 partials")
    config, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    day = run.load_json("configs", "basic-day5m-1chip")
    mine = run.load_json("configs", CONFIG)
    assert (mine["segments"], mine["rows_per_segment"], mine["chips"],
            mine["mesh"]) == (480, 208333, 1, None)
    # the day configuration's data source: same columns, guarantees and
    # cuts, word for word; the same 100M rows to within the division by 24
    for key in ("schema", "guarantees", "reduced", "first_day_ms",
                "datasource"):
        assert mine[key] == day[key], key
    assert config["reduced"] == sorted(day["reduced"])
    assert 0 <= day["segments"] * day["rows_per_segment"] \
        - mine["segments"] * mine["rows_per_segment"] < mine["segments"]
    assert set(day["assumed"]) | {"jmh_sizes", "scale"} == set(mine["assumed"])

    cell = run.load_workload(CELL)
    control = run.load_workload("analyst-groupby")
    assert cell["loop"] == {"kind": "closed", "clients": 8,
                            "plan_qps": cell["loop"]["plan_qps"],
                            "sure_requests": 32}
    assert cell["loop"]["plan_qps"] % 10 == 0
    assert (cell["client_timeout_s"], cell["latency_limit_ms"],
            cell["verify_sample"], cell["trace_seconds"]) == (60, None, 16, 8)
    assert [(e["query"], e["weight"]) for e in cell["templates"]] == [
        ("topn-seq-by-zipf", 40), ("timeseries-daily-seq", 30),
        ("groupby-zipf-by-seq", 30)]
    for e in cell["templates"]:
        # ISSUE 30's traffic, letter for letter: the last 1 / 3 / 7 days
        assert e["slots"]["intervals"] == {
            "gen": "recent_days", "lengths": [24, 72, 168],
            "weights": [70, 20, 10], "exponent": 1.0}
        low, high = (1, 101) if e["query"] == "topn-seq-by-zipf" else (0, 1000)
        assert e["slots"]["value"] == {"gen": "zipf_string", "low": low,
                                       "high": high, "exponent": 1.0}
        assert "distinct" not in e
        assert e["warm"][0] == {"intervals": {"gen": "all_days"}}
        assert [w["intervals"] for w in e["warm"][1:]] == [
            {"lengths": [n], "weights": [1]} for n in (24, 72, 168)]
    daily = run.load_json("queries", "timeseries-daily-seq")["query"]
    hourly = run.load_json("queries", "timeseries-hourly-seq")["query"]
    assert daily == dict(hourly, granularity="day")
    assert cell["end_to_end"] == control["end_to_end"]
    assert cell["per_layer"] == control["per_layer"] + NEW
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "latency_p50_ms"
        assert listed[name]["layer"] == (
            "partial production" if name.startswith("batch.")
            else "broker" if name.startswith("cache.") else "data node")


def test_untraced_forms_the_k_ladder():
    result, out = _rehearse(trace=0, seconds=3.0)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"latency_p50_ms", "rows_per_s",
                                      "setup_s"}
    checked = re.search(r"checked (\d+) in-window answer\(s\) against the "
                        r"reference, 0 wrong", out)
    assert checked and int(checked.group(1)) > 0, out[-3000:]
    strategies = json.loads(out.split("strategies: ", 1)[1].splitlines()[0])
    assert strategies == {"topn-seq-by-zipf": ["blocked"],
                          "timeseries-daily-seq": ["blocked"],
                          "groupby-zipf-by-seq": ["mm"]}
    # every warm-up is two stacked dispatches and equals the reference
    warm = [line for line in out.splitlines() if line.startswith("warm ")]
    assert len(warm) == 12
    assert all("2 dispatch span(s)" in w and w.endswith("equal")
               for w in warm), warm


def test_traced_reads_every_listed_metric():
    from benchmark import run
    result, out = _rehearse(trace=1, seconds=4.0)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True, out[-3000:]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # the CPU has no device plane: the two device metrics are left out
    want = set(run.load_workload(CELL)["per_layer"]) \
        - {"scan.hbm_share", "device.idle_share"}
    assert set(metrics) == want
    assert metrics["engine.compiles_in_window"] == 0
    assert metrics["batch.fallback_segments_per_query"] == 0
    assert 0 < metrics["batch.dispatches_per_query"] <= 2.0
    assert metrics["batch.segments_per_query"] >= \
        12 * metrics["batch.dispatches_per_query"]
    # every stacked slot is the 32,768-row rung
    assert metrics["batch.padded_rows_per_query"] == \
        32768 * metrics["batch.segments_per_query"]
    assert metrics["batch.plan_ms"] > 0 and metrics["datanode.merge_ms"] > 0
    # a repeated panel is the broker's result cache's: a share of requests
    assert 0 <= metrics["cache.result_hits_per_query"] < 1


@pytest.mark.parametrize("name,value", [
    ("batch.dispatches_per_query", None), ("batch.segments_per_query", None),
    ("batch.fallback_segments_per_query", None),
    ("batch.padded_rows_per_query", None), ("batch.plan_ms", None),
    ("datanode.merge_ms", None), ("cache.result_hits_per_query", 0.0)])
def test_a_program_without_the_spans_reads_nothing_and_does_not_raise(
        name, value):
    """A program with no `batching.*` counter, no `engine/batch/plan` or
    `datanode/merge` span and no `resultCacheHit` on `broker/query`."""
    spec = layers.load_layers(BENCH)[name]
    requests = [{"record": {"send_s": 0.0, "done_s": 1.0, "due_s": 0.0},
                 "spans": [{"name": "broker/query", "spanId": "a",
                            "parentId": None, "durationMs": 3.0,
                            "attrs": {}}]}]
    counters = {"dispatch.total": 4}
    assert layers.evaluate(spec, requests, counters, counters, None) == value
