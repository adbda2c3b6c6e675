"""Driver entry contract: the suite is structurally blind to backend-init
hangs because conftest pins platforms before jax loads. These tests run
`__graft_entry__` the way the DRIVER does — subprocess, no conftest, env
unpinned — and unit-test the backend-init watchdog that turns a blocked
backend init into a fast, actionable error instead of an rc=124 hang."""
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# watchdog unit tests (in-process, fake init)
# ---------------------------------------------------------------------------

def test_watchdog_times_out_hanging_backend_init(monkeypatch):
    """A backend init that blocks must surface as a RuntimeError within
    the deadline, not hang."""
    import jax

    import __graft_entry__ as g

    def hang(*a, **k):
        time.sleep(60)

    monkeypatch.setattr(jax, "devices", hang)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="did not complete"):
        g._init_cpu_backend(1, timeout_s=0.3)
    assert time.monotonic() - t0 < 5.0


def test_watchdog_propagates_init_errors(monkeypatch):
    import jax

    import __graft_entry__ as g

    def boom(*a, **k):
        raise ValueError("plugin exploded")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(ValueError, match="plugin exploded"):
        g._init_cpu_backend(1, timeout_s=5.0)


def test_watchdog_reports_device_shortfall(monkeypatch):
    import jax

    import __graft_entry__ as g

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [object()])
    with pytest.raises(RuntimeError, match="need 4 cpu devices, have 1"):
        g._init_cpu_backend(4, timeout_s=5.0)


# ---------------------------------------------------------------------------
# the driver contract, end to end
# ---------------------------------------------------------------------------

def test_dryrun_multichip_subprocess_like_the_driver():
    """dryrun_multichip in a fresh interpreter with NO platform pinning
    from the environment — the entry point itself must pin cpu + the
    virtual device count before backend init and complete quickly."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(2)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip(2)" in proc.stdout
    assert "sharded == host-merged" in proc.stdout
    assert elapsed < 180, f"dryrun took {elapsed:.0f}s — hang regression?"


def test_dryrun_fails_fast_when_backend_init_hangs():
    """Simulated blocked backend init: jax is pre-imported (driver-style)
    with jax.devices replaced by a blocker — the watchdog must turn this
    into a clean, fast error with an actionable message, never a hang."""
    code = (
        "import jax\n"
        "import time as _t\n"
        "jax.devices = lambda *a, **k: _t.sleep(600)\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(2)\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["DRUID_TPU_BACKEND_INIT_TIMEOUT_S"] = "2"
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    elapsed = time.monotonic() - t0
    assert proc.returncode != 0
    assert "did not complete within 2s" in proc.stderr
    assert "JAX_PLATFORMS=cpu" in proc.stderr      # actionable remedy
    assert elapsed < 60, f"failure took {elapsed:.0f}s — not fail-fast"
