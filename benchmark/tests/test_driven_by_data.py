"""A new deployment, mix, template and layer metric are FILES: no edit to a
file that is there. (benchmark/README.md walks through the same four.)"""
import json
import os
import shutil

from benchmark.tests.util import BENCH, rehearse


def _tree_digest(root):
    import hashlib
    out = {}
    for d, _dirs, files in os.walk(root):
        if ".cache" in d or "__pycache__" in d:
            continue
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


def test_four_additions_found_by_name(tmp_path):
    root = tmp_path / "benchmark"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = _tree_digest(root)

    def add(kind, spec):
        with open(root / kind / f"{spec['name']}.json", "x") as f:
            json.dump(spec, f)

    with open(root / "configs" / "basic-day5m-1chip.json") as f:
        base = json.load(f)
    add("configs", dict(base, name="throwaway-2col", schema=[
        s for s in base["schema"]
        if s["name"] in ("dimZipf", "metLongUniform")]))
    add("queries", {"name": "throwaway-topn", "device": True, "query": {
        "queryType": "topN", "dataSource": "basic",
        "intervals": {"$slot": "when"}, "granularity": "all",
        "dimension": "dimZipf", "metric": "m", "threshold": {"$slot": "k"},
        "aggregations": [{"type": "longMin", "name": "m",
                          "fieldName": "metLongUniform"}]}})
    add("layers", {"name": "throwaway.scatter_ms", "unit": "ms",
                   "better": "lower", "layer": "broker",
                   "moves": "latency_p50_ms", "contract_source": "program_span",
                   "source": "span_ms", "span": "broker/scatter",
                   "reduce": "p95_per_request"})
    add("workloads", {
        "name": "throwaway-cell", "config": "throwaway-2col",
        "why": "a test's cell", "reduced": {},
        "loop": {"kind": "open", "rate_qps": 6, "workers": 4},
        "client_timeout_s": 30, "latency_limit_ms": 1000, "verify_sample": 3,
        "trace_seconds": 1,
        "templates": [{"query": "throwaway-topn", "weight": 1, "slots": {
            "when": {"gen": "all_days"},
            "k": {"gen": "uniform_int", "low": 3, "high": 9}}}],
        "end_to_end": ["latency_p50_ms", "setup_s"],
        "per_layer": ["throwaway.scatter_ms", "broker.merge_ms"]})

    result, out = rehearse("throwaway-cell", trace=1, seconds=2.0,
                           root=str(root))
    assert result["correct"] is True, out[-3000:]
    assert "cell throwaway-cell on throwaway-2col" in out
    assert '"throwaway-topn": [' in out                  # the strategies line
    assert set(result["metrics"]) == {"throwaway.scatter_ms", "broker.merge_ms"}
    assert result["attempted"] == 12 and result["failed"] == 0
    after = _tree_digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 4
