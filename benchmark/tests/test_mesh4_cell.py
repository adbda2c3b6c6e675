"""`mesh4-analyst-groupby`: the cell of the four-chip mesh historical,
rehearsed on 4 virtual CPU devices, and its six `mesh.*` metrics."""
import pytest

from benchmark.harness import layers
from benchmark.tests.util import BENCH, RESULT_KEYS, rehearse

CELL = "mesh4-analyst-groupby"
MESH = ["mesh.sharded_dispatches_per_query", "mesh.fallbacks_per_query",
        "mesh.plan_ms", "mesh.stack_ms", "mesh.put_ms",
        "mesh.restack_bytes_per_query"]


def test_manifest_lists_the_cell_its_configuration_and_its_metrics(manifest):
    from benchmark import run
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("basic-day5m-mesh4", "analyst-groupby", 4)
    assert entry["config"] in {c["name"] for c in manifest["configs"]}
    cell = run.load_workload(CELL)
    control = run.load_workload("analyst-groupby")
    # the one-chip cell's traffic, letter for letter, and its metrics first
    assert all(cell[k] == control[k] for k in run.TRAFFIC_KEYS)
    assert cell["end_to_end"] == control["end_to_end"]
    assert cell["per_layer"] == control["per_layer"] + MESH
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in MESH:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "latency_p50_ms"
        assert listed[name]["layer"] == (
            "staging" if name == "mesh.restack_bytes_per_query"
            else "partial production")


def test_untraced_on_four_virtual_devices():
    result, out = rehearse(CELL, trace=0, devices=4)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"latency_p50_ms", "rows_per_s",
                                      "setup_s"}
    assert result["device"]["count"] == 4


def test_traced_reads_the_six_mesh_metrics():
    result, out = rehearse(CELL, trace=1, seconds=3.0, devices=4)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True, out[-3000:]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(MESH) <= set(metrics)
    assert metrics["mesh.sharded_dispatches_per_query"] == 1.0
    assert metrics["engine.dispatches_per_query"] == 1.0
    assert metrics["mesh.fallbacks_per_query"] == 0
    assert metrics["mesh.restack_bytes_per_query"] == 0
    assert metrics["engine.compiles_in_window"] == 0
    assert all(metrics[k] > 0 for k in
               ("mesh.plan_ms", "mesh.stack_ms", "mesh.put_ms"))


@pytest.mark.parametrize("name,value", [
    ("mesh.fallbacks_per_query", 0.0), ("mesh.restack_bytes_per_query", 0.0),
    ("mesh.plan_ms", None), ("mesh.stack_ms", None), ("mesh.put_ms", None),
    ("mesh.sharded_dispatches_per_query", None)])
def test_a_program_without_the_spans_reads_nothing_and_does_not_raise(
        name, value):
    """What the parent commit gives these readers: traced requests with no
    `engine/sharded/plan|stack|put` span, and (on a meshless run) no
    `dispatch.sharded` counter."""
    spec = layers.load_layers(BENCH)[name]
    requests = [{"record": {"send_s": 0.0, "done_s": 1.0, "due_s": 0.0},
                 "spans": [{"name": "engine/sharded/dispatch", "spanId": "a",
                            "parentId": None, "durationMs": 3.0,
                            "attrs": {"strategy": "mixed"}}]}]
    counters = {"dispatch.total": 4}
    assert layers.evaluate(spec, requests, counters, counters, None) == value
