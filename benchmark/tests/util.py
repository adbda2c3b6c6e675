"""Helpers shared by the benchmark's CPU rehearsals."""
import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(workload: str, trace: int = 0, seconds: float = 2.0,
             root: str = BENCH, devices: int = 1, timeout: float = 600.0):
    """Run `rehearse.py` in a child; returns (result object, stdout)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    else:
        env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "rehearse.py"),
         "--workload", workload, "--trace", str(trace),
         "--seconds", str(seconds), "--root", root],
        capture_output=True, text=True, env=env, timeout=timeout,
        cwd=os.path.dirname(root))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout
