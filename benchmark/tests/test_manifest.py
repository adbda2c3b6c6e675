"""BENCHMARK.json against the files it names, and against its contract."""
import json
import os
import re

import pytest

from benchmark.harness import layers
from benchmark.tests.util import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CONTRACT_SOURCES = {"device_trace", "program_span", "program_counter",
                    "host_clock"}


def _load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def test_keys_names_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4)
        names.append(w["name"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        names.append(m["name"])
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in CONTRACT_SOURCES
        names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for text in [c[k] for c in manifest["configs"] for k in ("why", "source")] \
            + [w["why"] for w in manifest["workloads"]] \
            + [m["layer"] for m in manifest["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 2)
    assert len(json.dumps(manifest)) < 64 * 1024
    # a full check fits: 2 + 14 runs a cell at the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_cell_resolves_to_its_files(manifest):
    from benchmark import run
    configs = {c["name"]: c for c in manifest["configs"]}
    sources = [c["source"] for c in manifest["configs"]]
    assert len(set(sources)) == len(sources)
    used = set()
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for w in manifest["workloads"]:
        cell = run.load_workload(w["name"])
        assert cell["config"] == w["config"] and cell["why"] == w["why"]
        assert cell.get("traffic", w["name"]) == w["traffic"]
        cfg = _load("configs", cell["config"])
        entry = configs[cell["config"]]
        used.add(cell["config"])
        assert entry["file"] == f"benchmark/configs/{cfg['name']}.json"
        assert entry["source"] == cfg["source"]
        assert entry["reduced"] == sorted(cfg["reduced"])
        assert cfg["chips"] == w["chips"]
        for e in cell["templates"]:
            q = _load("queries", e["query"])
            from benchmark.harness import traffic
            assert set(traffic.slots_of(q["query"])) == set(e.get("slots", {}))
        # what the cell reports is what the manifest says it reports
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
        assert cell["per_layer"]
        for name, table in [(n, end_to_end) for n in cell["end_to_end"]] + \
                [(n, per_layer) for n in cell["per_layer"]]:
            assert name in table, name
            assert w["name"] in table[name].get("workloads", [w["name"]])
        for table, mine in ((end_to_end, cell["end_to_end"]),
                            (per_layer, cell["per_layer"])):
            for name, m in table.items():
                if w["name"] in m.get("workloads", [w["name"]]):
                    assert name in mine, (w["name"], name)
        for name in cell["per_layer"]:
            assert per_layer[name]["moves"] in cell["end_to_end"]
    assert used == set(configs)


def test_layer_files_match_the_manifest(manifest):
    specs = layers.load_layers(BENCH)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    # a file may wait for its cell (a metric of an unproved cell), an entry
    # may not lack its file
    assert set(listed) <= set(specs)
    by_layer = {}
    for name, m in listed.items():
        spec = specs[name]
        assert (m["unit"], m["better"], m["layer"], m["moves"], m["source"]) == \
            (spec["unit"], spec["better"], spec["layer"], spec["moves"],
             spec["contract_source"])
        by_layer.setdefault(spec["layer"], []).append(name)
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in by_layer:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_peaks_table():
    peaks = layers.load_peaks(BENCH, "TPU v5 lite")
    assert peaks == {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                     "hbm_bytes": 16e9}
    with pytest.raises(KeyError, match="TPU v9"):
        layers.load_peaks(BENCH, "TPU v9")


def test_cache_directory_is_ignored_and_holds_nothing_committed():
    import subprocess
    out = subprocess.run(["git", "check-ignore", "benchmark/.cache/x"],
                         cwd=REPO, capture_output=True, text=True)
    if out.returncode == 128:
        pytest.skip("not a git checkout")
    assert out.stdout.strip() == "benchmark/.cache/x"
