"""`run.py` end to end: refusal without a TPU, and every cell rehearsed."""
import os
import subprocess
import sys

import pytest

from benchmark.tests.util import BENCH, REPO, RESULT_KEYS, rehearse


def _run_py(cwd, extra_env=None, args=("--workload", "analyst-groupby")):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=cwd)


def test_refuses_without_a_tpu():
    p = _run_py(REPO)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    # BENCH_RUN, which the driver sets for its own use, changes nothing
    q = _run_py(REPO, {"BENCH_RUN": "7"})
    assert (q.returncode, q.stdout) == (p.returncode, p.stdout)


def test_refuses_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: non-zero exit, no result."""
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _run_py(str(tmp_path), env)
    assert p.returncode != 0
    assert "not beside the benchmark" in p.stderr
    assert p.stdout.strip() == ""


def test_unknown_workload_is_refused():
    p = _run_py(REPO, args=("--workload", "no-such-cell"))
    assert p.returncode != 0 and "no workloads/no-such-cell.json" in p.stderr


CELLS = [("analyst-groupby", 1), ("dashboard-mix", 1),
         ("analyst-groupby-mesh4", 4)]


@pytest.mark.parametrize("name,devices", CELLS)
def test_cell_rehearsed_on_the_cpu(manifest, name, devices):
    """Deploys, runs the cell's mix for 2 s, last line = the contract's keys;
    the mesh configuration on 4 virtual CPU devices."""
    from benchmark import run
    cell = run.load_workload(name)
    result, out = rehearse(name, trace=0, devices=devices)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == set(cell["end_to_end"])
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    for k, v in result["metrics"].items():
        # a cell that waits for its proof reports metrics not yet listed
        assert v["unit"] == units.get(k, v["unit"]) and v["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == devices
    assert "strategies:" in out and "checked " in out


@pytest.mark.parametrize("name,devices", CELLS[1:])
def test_cell_traced_on_the_cpu(manifest, name, devices):
    from benchmark import run
    cell = run.load_workload(name)
    result, out = rehearse(name, trace=1, seconds=3.0, devices=devices)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True, out[-3000:]
    # the CPU has no device plane: the two device metrics are left out, the
    # rest is read
    want = set(cell["per_layer"]) - {"scan.hbm_share", "device.idle_share"}
    assert set(result["metrics"]) == want
    units = {m["name"]: m["unit"] for m in manifest["per_layer"]}
    assert all(v["unit"] == units.get(k, v["unit"])
               for k, v in result["metrics"].items())
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in result["breakdown"].values())
    if name == "analyst-groupby-mesh4":
        assert result["metrics"]["engine.dispatches_per_query"]["value"] == 1.0
