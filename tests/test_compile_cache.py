"""Placement of the persistent XLA compilation cache (engine/__init__.py):
the deployment places it through JAX_COMPILATION_CACHE_DIR and the code then
sets no directory; otherwise ONE fixed, git-ignored directory inside the
checkout. Checked in subprocesses — the setting is applied at import."""
import os
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

_PROBE = ("import jax, druid_tpu.engine; "
          "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir(env_value):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    proc = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_cache_dir_placed_from_outside_wins(tmp_path):
    assert _cache_dir(str(tmp_path / "cc")) == str(tmp_path / "cc")


def test_cache_dir_defaults_to_one_ignored_directory_in_the_checkout():
    assert _cache_dir(None) == str(REPO_ROOT / ".jax_cache")
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
    assert "native/*.so" in ignored
