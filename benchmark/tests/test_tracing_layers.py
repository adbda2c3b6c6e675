"""The per-layer metrics that read PR 24's spans and counters.

Their files wait under `layers/` (a `tracing` PR may add files, not edit
`workloads/analyst-groupby.json`, whose `per_layer` list is what a run
reports): here the names are appended IN A TEMPORARY COPY, the cell is
rehearsed there, and every one of them has to come out with a value; the
manifest entries a `benchmark` PR would append are held to the manifest's
rules."""
import json
import os
import shutil

import pytest

from benchmark.harness import layers
from benchmark.tests.test_manifest import CONTRACT_SOURCES, NAME, UNIT
from benchmark.tests.util import BENCH, REPO, rehearse

CELL = "analyst-groupby"
NEW = ["wire.between_ms", "wire.encode_ms", "wire.decode_ms",
       "wire.bytes_per_query", "engine.fetch_wait_ms",
       "engine.filter_words_ms", "engine.plan_ms", "engine.stage_ms",
       "engine.build_ms", "engine.segment_self_ms",
       "engine.backend_compiles_in_window", "http.respond_ms"]


def manifest_entry(spec: dict) -> dict:
    """What `BENCHMARK.json` `per_layer` would hold for a layer file."""
    return {"name": spec["name"], "unit": spec["unit"],
            "better": spec["better"], "source": spec["contract_source"],
            "layer": spec["layer"], "moves": spec["moves"],
            "workloads": [CELL]}


def test_new_layer_files_fit_the_manifest(manifest):
    specs = layers.load_layers(BENCH)
    assert set(NEW) <= set(specs)
    listed = {m["name"] for m in manifest["per_layer"]}
    assert not listed & set(NEW), "listed now: drop them from NEW's wait"
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    known_layers = {m["layer"] for m in manifest["per_layer"]} | {"wire"}
    for name in NEW:
        m = manifest_entry(specs[name])
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in CONTRACT_SOURCES
        assert m["layer"] in known_layers
        assert f"| {m['layer']} |" in perf, "PERF.md §3 lacks the layer"
        assert f"`{name}`" in perf, f"PERF.md does not name {name}"
        assert m["moves"] in cell["end_to_end"]
    grown = dict(manifest, per_layer=manifest["per_layer"]
                 + [manifest_entry(specs[n]) for n in NEW])
    assert len(json.dumps(grown)) < 64 * 1024
    assert len(grown["per_layer"]) <= 128


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """`analyst-groupby` rehearsed, traced, in a copy whose workload file
    lists the new metrics after the ones it had."""
    root = tmp_path_factory.mktemp("bench") / "benchmark"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    path = root / "workloads" / f"{CELL}.json"
    with open(path) as f:
        cell = json.load(f)
    had = list(cell["per_layer"])
    cell["per_layer"] = had + NEW
    with open(path, "w") as f:
        json.dump(cell, f, indent=1)
    result, out = rehearse(CELL, trace=1, seconds=3.0, root=str(root))
    return result, out, had


def test_rehearsal_reports_every_new_metric(traced):
    result, out, had = traced
    assert result["correct"] is True, out[-3000:]
    metrics = result["metrics"]
    for name in NEW:
        assert name in metrics, f"{name} read nothing:\n{out[-2000:]}"
        assert metrics[name]["value"] is not None
    specs = layers.load_layers(BENCH)
    assert all(metrics[n]["unit"] == specs[n]["unit"] for n in NEW)
    # what the cell reported before is still reported (the CPU has no
    # device plane: its two device metrics are left out, as before)
    assert set(had) - {"scan.hbm_share", "device.idle_share"} <= set(metrics)


def test_the_readings_hang_together(traced):
    """Counts and orderings a CPU can show (never a rate)."""
    result, _out, _had = traced
    v = {k: m["value"] for k, m in result["metrics"].items()}
    assert v["wire.bytes_per_query"] > 0
    assert v["wire.encode_ms"] > 0 and v["wire.decode_ms"] > 0
    # the hole holds at least the two ends of the wire
    assert v["wire.between_ms"] >= 0.5 * (v["wire.encode_ms"]
                                          + v["wire.decode_ms"])
    assert v["engine.fetch_wait_ms"] > 0 and v["engine.plan_ms"] > 0
    assert v["engine.segment_self_ms"] >= 0
    phases = sum(v[k] for k in ("engine.fetch_wait_ms", "engine.plan_ms",
                                "engine.filter_words_ms", "engine.stage_ms",
                                "engine.build_ms", "engine.segment_self_ms"))
    # the phases are parts of the partials span (medians do not add
    # exactly; dispatch is the one phase without a metric of its own)
    assert phases <= 1.25 * v["engine.partials_ms"]
    assert v["engine.backend_compiles_in_window"] == 0
    assert v["engine.compiles_in_window"] == 0
    assert v["http.respond_ms"] > 0


def test_the_parent_reads_nothing_and_does_not_raise():
    """Spans of a program without PR 24's changes: the new span metrics
    find nothing, the counter is unknown — None each, never an error."""
    specs = layers.load_layers(BENCH)
    spans = [{"spanId": "a", "parentId": None, "name": "query",
              "durationMs": 10.0, "attrs": {}},
             {"spanId": "b", "parentId": "a", "name": "broker/node",
              "durationMs": 8.0, "attrs": {}},
             {"spanId": "c", "parentId": "b", "name": "datanode/query",
              "durationMs": 5.0, "attrs": {}},
             {"spanId": "d", "parentId": "c", "name": "engine/partials",
              "durationMs": 4.0, "attrs": {}}]
    requests = [{"record": {"send_s": 0.0, "done_s": 0.012, "due_s": 0.0},
                 "spans": spans}]
    counters = {"dispatch.total": 3.0}
    got = {n: layers.evaluate(specs[n], requests, counters, counters, None)
           for n in NEW}
    # the hole itself can be read on the parent: 8 - 5
    assert got.pop("wire.between_ms") == 3.0
    # an attribute sum over no span is 0 by the vocabulary's own rule
    assert got.pop("wire.bytes_per_query") == 0.0
    assert all(v is None for v in got.values()), got
