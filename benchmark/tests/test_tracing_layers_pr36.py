"""The per-layer metrics that read PR 36's `cpuMs`, spans and counter.

Twelve files wait under `layers/`, as PR 24's twelve do (a `tracing` PR adds
files and edits none: a cell reports the names in its own
`workloads/<cell>.json`). Here the names are appended IN A TEMPORARY COPY of
`workloads/analyst-groupby.json` and of `workloads/seg208k-dashboard.json`
(`batch.*` there only: the day cell forms no chunk), each cell is rehearsed
there, and every name has to come out with a value; the manifest entries a
`benchmark` PR would append are held to the manifest's rules; on a PARENT's
spans (no `cpuMs`, none of the new spans, no such counter) none raises."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import layers
from benchmark.tests.test_manifest import CONTRACT_SOURCES, NAME, UNIT
from benchmark.tests.test_seg208k_cell import REHEARSE
from benchmark.tests.test_tracing_layers import manifest_entry
from benchmark.tests.util import BENCH, REPO, rehearse

CPU = ["broker.cpu_ms", "datanode.cpu_ms", "engine.partials_cpu_ms",
       "http.respond_cpu_ms"]
FETCH = ["engine.fetch_device_wait_ms", "engine.fetch_d2h_ms",
         "engine.fetch_post_ms", "engine.fetch_start_ms",
         "engine.fetch_release_ms"]
BATCH = ["batch.blocks_ms", "batch.assemble_ms"]
DROPPED = "trace.dropped_spans_in_window"
#: cell -> the names a `benchmark` PR would append to its `per_layer`
NEW = {"analyst-groupby": CPU + FETCH + [DROPPED],
       "seg208k-dashboard": CPU + FETCH + BATCH + [DROPPED]}
ALL = CPU + FETCH + BATCH + [DROPPED]


def test_new_layer_files_fit_the_manifest(manifest):
    specs = layers.load_layers(BENCH)
    assert set(ALL) <= set(specs) and len(ALL) == 12
    listed = {m["name"] for m in manifest["per_layer"]}
    assert not listed & set(ALL), "listed now: drop them from the wait"
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    known_layers = {m["layer"] for m in manifest["per_layer"]}
    entries = []
    for cell_name, names in NEW.items():
        with open(os.path.join(BENCH, "workloads", f"{cell_name}.json")) as f:
            cell = json.load(f)
        for name in names:
            m = dict(manifest_entry(specs[name]), workloads=[cell_name])
            assert set(m) - {"workloads"} == {"name", "unit", "better",
                                              "source", "layer", "moves"}
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] == "lower"
            assert m["source"] in CONTRACT_SOURCES
            assert m["layer"] in known_layers
            assert f"| {m['layer']} |" in perf, "PERF.md §3 lacks the layer"
            assert f"`{name}`" in perf, f"PERF.md does not name {name}"
            assert m["moves"] in cell["end_to_end"]
            assert specs[name]["about"] and name not in cell["per_layer"]
            entries.append(m)
    # one entry a name, its cells merged, as the manifest would hold it
    merged = {}
    for m in entries:
        merged.setdefault(m["name"], dict(m, workloads=[]))["workloads"] += \
            m["workloads"]
    assert sorted(merged) == sorted(ALL)
    assert all(merged[n]["workloads"] == ["seg208k-dashboard"] for n in BATCH)
    grown = dict(manifest,
                 per_layer=manifest["per_layer"] + list(merged.values()))
    assert len(json.dumps(grown)) < 64 * 1024
    assert len(grown["per_layer"]) <= 128
    # a span metric names the span, an attribute metric `cpuMs`, the
    # counter the key `harness/deploy.py:read_counters` gives it
    assert all(specs[n]["source"] == "span_attr_sum"
               and specs[n]["attr"] == "cpuMs" for n in CPU)
    assert all(specs[n]["source"] == "span_ms" for n in FETCH + BATCH)
    assert (specs[DROPPED]["source"], specs[DROPPED]["counter"]) == \
        ("counter_delta", "dispatch.trace_dropped_spans")


def _copy_listing(tmp_path_factory, cell_name):
    """A copy of `benchmark/` whose workload file lists the cell's new
    names after the ones it had; returns (root, the names it had)."""
    root = tmp_path_factory.mktemp(cell_name) / "benchmark"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    path = root / "workloads" / f"{cell_name}.json"
    with open(path) as f:
        cell = json.load(f)
    had = list(cell["per_layer"])
    cell["per_layer"] = had + NEW[cell_name]
    with open(path, "w") as f:
        json.dump(cell, f, indent=1)
    return root, had


@pytest.fixture(scope="module")
def analyst(tmp_path_factory):
    root, had = _copy_listing(tmp_path_factory, "analyst-groupby")
    result, out = rehearse("analyst-groupby", trace=1, seconds=3.0,
                           root=str(root))
    return result, out, had


@pytest.fixture(scope="module")
def dashboard(tmp_path_factory):
    """`test_seg208k_cell.py`'s rehearsal (40 segments of 20,000 rows: the
    K ladder forms), run from the copy: `benchmark` resolves to it,
    `druid_tpu` to the repo."""
    root, had = _copy_listing(tmp_path_factory, "seg208k-dashboard")
    top = str(root.parent)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=top + os.pathsep + REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", REHEARSE, "seg208k-dashboard",
                        "1", "6.0"], capture_output=True, text=True, env=env,
                       timeout=900, cwd=top)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout, had


@pytest.mark.parametrize("cell_name", sorted(NEW))
def test_rehearsal_reports_every_new_metric(cell_name, request):
    result, out, had = request.getfixturevalue(
        "analyst" if cell_name == "analyst-groupby" else "dashboard")
    assert result["correct"] is True, out[-3000:]
    metrics = result["metrics"]
    specs = layers.load_layers(BENCH)
    for name in NEW[cell_name]:
        assert name in metrics, f"{name} read nothing:\n{out[-2000:]}"
        assert metrics[name]["value"] is not None
        assert metrics[name]["unit"] == specs[name]["unit"]
    # what the cell reported before is still reported (the CPU has no
    # device plane: its two device metrics are left out, as before)
    assert set(had) - {"scan.hbm_share", "device.idle_share"} <= set(metrics)


@pytest.mark.parametrize("cell_name", sorted(NEW))
def test_the_readings_hang_together(cell_name, request):
    """Counts and orderings a CPU can show (never a rate)."""
    result, _out, _had = request.getfixturevalue(
        "analyst" if cell_name == "analyst-groupby" else "dashboard")
    v = {k: m["value"] for k, m in result["metrics"].items()}
    assert v[DROPPED] == 0
    # work is part of the span it was done in (medians of one population:
    # a millisecond of slack)
    assert 0 < v["engine.partials_cpu_ms"] <= v["engine.partials_ms"] + 1.0
    assert v["datanode.cpu_ms"] >= v["engine.partials_cpu_ms"] - 1.0
    assert v["broker.cpu_ms"] > 0 and v["http.respond_cpu_ms"] >= 0
    # the fetch's three parts and the copy starts are inside the partials
    parts = v["engine.fetch_device_wait_ms"] + v["engine.fetch_d2h_ms"] \
        + v["engine.fetch_post_ms"]
    assert 0 < parts and v["engine.fetch_start_ms"] > 0
    assert v["engine.fetch_release_ms"] > 0
    assert parts + v["engine.fetch_start_ms"] + v["engine.fetch_release_ms"] \
        <= v["engine.partials_ms"] + 1.0
    if cell_name == "seg208k-dashboard":
        assert v["batch.blocks_ms"] > 0 and v["batch.assemble_ms"] > 0
        assert v["batch.blocks_ms"] + v["batch.assemble_ms"] + parts \
            <= v["engine.partials_ms"] + 1.0
    assert v["engine.compiles_in_window"] == 0


def test_the_parent_reads_nothing_and_does_not_raise():
    """Spans of a program without PR 36's changes: no `cpuMs`, none of the
    new spans, no such counter."""
    specs = layers.load_layers(BENCH)
    spans = [{"spanId": "a", "parentId": None, "name": "query",
              "durationMs": 10.0, "attrs": {}},
             {"spanId": "b", "parentId": "a", "name": "broker/node",
              "durationMs": 8.0, "attrs": {}},
             {"spanId": "c", "parentId": "b", "name": "datanode/query",
              "durationMs": 5.0, "attrs": {}},
             {"spanId": "d", "parentId": "c", "name": "engine/partials",
              "durationMs": 4.0, "attrs": {}},
             {"spanId": "e", "parentId": "d", "name": "engine/fetch",
              "durationMs": 3.0, "attrs": {"bytes": 8, "programs": 1}},
             {"spanId": "f", "parentId": "a", "name": "http/respond",
              "durationMs": 1.0}]
    requests = [{"record": {"send_s": 0.0, "done_s": 0.012, "due_s": 0.0},
                 "spans": spans}]
    counters = {"dispatch.total": 3.0}
    got = {n: layers.evaluate(specs[n], requests, counters, counters, None)
           for n in ALL}
    # an attribute sum over spans that lack the attribute is 0 by the
    # vocabulary's own rule; the driver does not compare a metric new in
    # this PR against the parent
    assert [got.pop(n) for n in CPU] == [0.0] * 4
    assert all(v is None for v in got.values()), got
    # and over no request at all, nothing
    assert all(layers.evaluate(specs[n], [], {}, {}, None) is None
               for n in ALL)
