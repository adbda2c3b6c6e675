"""chip_smoke.py, the on-chip proof of the served path, held to its contract
on the CPU: its functions driven directly at a tiny size (servers up, four
queries twice each, exact numpy comparison, per-request strategy
assertions), and the script itself refusing to run without a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from druid_tpu.data.generator import ColumnSpec, DataGenerator  # noqa: E402
from druid_tpu.engine import grouping, pallas_agg  # noqa: E402
from druid_tpu.utils.intervals import Interval  # noqa: E402


def _segments(seed):
    """The headline's columns and value ranges at cardinalities whose
    5,000-group space still takes the sorted-projection path."""
    schema = (
        ColumnSpec("dimA", "string", cardinality=10, distribution="uniform"),
        ColumnSpec("dimB", "string", cardinality=500, distribution="zipf"),
        ColumnSpec("metLong", "long", low=0, high=10_000),
        ColumnSpec("metFloat", "float", distribution="normal", mean=100.0,
                   std=25.0),
    )
    return DataGenerator(schema, seed=seed).segments(
        chip_smoke.SEGMENTS, 4096, Interval.of("2026-01-01", "2026-01-02"),
        datasource="smoke")


def test_smoke_drives_the_served_path(monkeypatch, tmp_path):
    """Everything but the device check: persist, historical + broker up,
    HTTP queries, exact numpy parity, and the trace-backed assertions —
    the pallas and megakernel strategies through the interpreter."""
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    monkeypatch.setattr(pallas_agg, "_FORCE_INTERPRET", True)
    n = chip_smoke.SEGMENTS
    expectations = {
        "groupBy": chip_smoke.Expect("engine/dispatch", "pallas", n),
        # the CPU donates nothing: the carry handoff follows the platform
        "groupByIn": chip_smoke.Expect("engine/dispatch", "megakernel", n),
        # 4096-row segments batch into one stacked program
        "topN": chip_smoke.Expect("engine/batch/dispatch", "mm", 1),
        "timeseries": chip_smoke.Expect("engine/batch/dispatch", "blocked",
                                        1),
    }
    facts = chip_smoke.run(_segments(7), str(tmp_path), expectations,
                           run_id="t1")
    assert facts["rows"] == n * 4096
    assert [(r["query"], r["repeat"]) for r in facts["requests"]] == [
        (q, i) for q in ("groupBy", "groupByIn", "topN", "timeseries")
        for i in range(chip_smoke.REPEATS)]
    assert all(r["dispatches"] > 0 for r in facts["requests"])
    assert facts["pool_resident_bytes"] > 0


def test_smoke_fails_when_the_device_ran_something_else(monkeypatch,
                                                        tmp_path):
    """With the kernel unavailable the projection falls to an XLA strategy:
    the answers stay right, so only the trace assertion can — and must —
    fail the run, naming what ran instead."""
    monkeypatch.setattr(grouping, "PROJECTION_MIN_ROWS", 0)
    with pytest.raises(chip_smoke.SmokeFailure, match="trace says"):
        chip_smoke.run(_segments(8), str(tmp_path), chip_smoke.ONE_CHIP,
                       run_id="t2")


def test_smoke_mesh_spreads_the_stack(tmp_path):
    """--mesh: one historical over a 4-device mesh answers every query with
    ONE sharded program, exactly, and holds about a quarter of the stacked
    bytes on each device."""
    from druid_tpu.parallel import make_mesh
    facts = chip_smoke.run(_segments(9), str(tmp_path),
                           chip_smoke.MESH,
                           mesh=make_mesh(4), run_id="t3")
    shares = facts["sharded_bytes"]
    assert len(shares) == 4 and min(shares.values()) > 0


def test_script_refuses_to_run_without_a_tpu():
    """`python chip_smoke.py` on the CPU: non-zero exit before any data is
    generated, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert "generated" not in proc.stdout
    for line in proc.stdout.splitlines():
        assert "ok" not in (json.loads(line) if line.startswith("{") else {})
    assert time.monotonic() - t0 < 60
