"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax imports,
mirroring the reference's single-JVM simulated-cluster testing strategy
(SURVEY §4: CachingClusteredClientTest-style tests without sockets)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Opt-in whole-suite lock witness (DRUID_TPU_LOCK_WITNESS=1): must install
# BEFORE the first druid_tpu import below — module-level locks (jit caches,
# native registry) are constructed at import time and would otherwise stay
# unwrapped, blinding the sweep to the hot-path engine locks. The install
# is a process-wide singleton (lockwitness.session_witness): this file
# executes twice per session (`conftest` plugin + `from tests.conftest
# import ...`), and a second install would shadow the first witness.
# Validation and reporting happen in pytest_unconfigure.
if os.environ.get("DRUID_TPU_LOCK_WITNESS") == "1":
    import sys as _sys
    from pathlib import Path as _Path
    _sys.path.insert(0, str(_Path(__file__).resolve().parent.parent))
    from tools.druidlint.lockwitness import session_witness as _session_witness
    _session_witness(str(_Path(__file__).resolve().parent.parent))

# Opt-in whole-suite leak witness (DRUID_TPU_LEAK_WITNESS=1): installed
# BEFORE the first druid_tpu import so every project thread start is
# attributed, with the session baseline captured at the SAME point — the
# suite must return to its post-install resource state (threads, fds,
# device-pool resident bytes) by pytest_unconfigure. Same process-wide
# singleton rationale as the lock witness above.
if os.environ.get("DRUID_TPU_LEAK_WITNESS") == "1":
    import sys as _sys
    from pathlib import Path as _Path
    _root = str(_Path(__file__).resolve().parent.parent)
    if _root not in _sys.path:
        _sys.path.insert(0, _root)
    from tools.druidlint.leakwitness import session_witness as _leak_witness
    _leak_witness(_root)

# Opt-in whole-suite stall witness (DRUID_TPU_STALL_WITNESS=1): the
# dynamic side of stallguard. Installed BEFORE the first druid_tpu import
# so `from time import sleep`-style early bindings cannot escape the
# wrappers — it patches the blocking primitives themselves (Event/
# Condition.wait, Thread.join, Queue.get, Popen.wait, time.sleep) and
# times every park issued from a druid_tpu call site. An untimed park
# outside a shutdown scope fails the session in pytest_unconfigure. Same
# process-wide singleton rationale as the other witnesses.
if os.environ.get("DRUID_TPU_STALL_WITNESS") == "1":
    import sys as _sys
    from pathlib import Path as _Path
    _root = str(_Path(__file__).resolve().parent.parent)
    if _root not in _sys.path:
        _sys.path.insert(0, _root)
    from tools.druidlint.stallwitness import session_witness as _stall_witness
    _stall_witness(_root)

import numpy as np
import pytest

import druid_tpu.engine  # noqa: F401  (enables x64 before any jax use)
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.utils.intervals import Interval

# Opt-in whole-suite key witness (DRUID_TPU_KEY_WITNESS=1): the dynamic
# side of keyguard. Unlike the lock/leak witnesses above it patches
# module GLOBALS (jit caches, builders, the device pool), so it installs
# AFTER the engine import — and it records a structural fingerprint of
# every cache build next to its key, failing the session on any
# same-key/different-structure collision in pytest_unconfigure. Same
# process-wide singleton rationale as the other witnesses.
if os.environ.get("DRUID_TPU_KEY_WITNESS") == "1":
    import sys as _sys
    from pathlib import Path as _Path
    _root = str(_Path(__file__).resolve().parent.parent)
    if _root not in _sys.path:
        _sys.path.insert(0, _root)
    from tools.druidlint.keywitness import session_witness as _key_witness
    _key_witness(_root)

# Opt-in whole-suite donation/ownership witness (DRUID_TPU_DONOR_WITNESS=1):
# the dynamic side of donorguard. Like the key witness it patches module
# globals (the pool take/get_or_build methods, the donating builder, the
# discard helper), so it installs AFTER the engine import — it tracks
# array identity across the take→dispatch→re-park cycle, SIMULATES
# donation invalidation on CPU by deleting donated carry buffers after a
# successful dispatch, and fails the session on a cached-entry donation
# or an un-reparked take in pytest_unconfigure. Same process-wide
# singleton rationale as the other witnesses.
if os.environ.get("DRUID_TPU_DONOR_WITNESS") == "1":
    import sys as _sys
    from pathlib import Path as _Path
    _root = str(_Path(__file__).resolve().parent.parent)
    if _root not in _sys.path:
        _sys.path.insert(0, _root)
    from tools.druidlint.donorwitness import session_witness as _donor_witness
    _donor_witness(_root)

DAY = Interval.of("2026-01-01", "2026-01-02")
WEEK = Interval.of("2026-01-01", "2026-01-08")

TEST_SCHEMA = (
    ColumnSpec("dimA", "string", cardinality=10, distribution="uniform"),
    ColumnSpec("dimB", "string", cardinality=100, distribution="zipf"),
    ColumnSpec("dimHi", "string", cardinality=5000, distribution="uniform"),
    ColumnSpec("metLong", "long", low=0, high=100),
    ColumnSpec("metFloat", "float", distribution="normal", mean=10.0, std=3.0),
    ColumnSpec("metDouble", "double", low=0.0, high=1.0),
)


@pytest.fixture(scope="session")
def generator():
    return DataGenerator(TEST_SCHEMA, seed=42)


def persist_roundtrip(seg, directory: str):
    """Persist to the on-disk format and reload (exercises codecs, smoosh,
    lazy bitmap parts, dictionary serde on every engine test)."""
    from druid_tpu.storage.format import load_segment, persist_segment
    persist_segment(seg, directory)
    return load_segment(directory)


@pytest.fixture(scope="session")
def _base_segment():
    # a DEDICATED generator: the shared `generator` fixture's RNG is
    # stateful, and both `segment` params must see the SAME rows
    return DataGenerator(TEST_SCHEMA, seed=42).segment(
        20_000, DAY, datasource="test")


@pytest.fixture(scope="session", params=("generated", "persisted"))
def segment(request, _base_segment, tmp_path_factory):
    """Engine tests run against BOTH the in-memory and the
    persisted+reloaded form of the SAME segment (reference:
    QueryRunnerTestHelper.makeQueryRunners parameterizes every query test
    over incremental/mmapped/merged forms). The order-changing forms
    (merged-from-spills, rollup-incremental) get their own equivalence
    battery in test_representations.py."""
    if request.param == "persisted":
        return persist_roundtrip(
            _base_segment, str(tmp_path_factory.mktemp("seg") / "test"))
    return _base_segment


@pytest.fixture(scope="session")
def segments(generator):
    """4 segments over a 4-day range sharing dictionaries."""
    return generator.segments(4, 5_000, Interval.of("2026-01-01", "2026-01-05"),
                              datasource="test")


def rows_as_frame(segment):
    """Decode a segment to python-level rows for golden-result computation."""
    out = {"__time": segment.time_ms.copy()}
    for name, col in segment.dims.items():
        vals = np.asarray(col.dictionary.values, dtype=object)
        out[name] = vals[col.ids]
    for name, m in segment.metrics.items():
        out[name] = m.values.copy()
    return out


# ---------------------------------------------------------------------------
# opt-in whole-suite lock witness: installed at the TOP of this module (see
# the header block — module-level locks are constructed at import time);
# every project lock constructed during the session is wrapped, and the
# observed acquisition-order graph is checked against raceguard's static
# one at session end. The dedicated stress run in test_raceguard_witness.py
# asserts this per-test; the session-wide mode sweeps the full suite's lock
# behavior before scaling work.
# ---------------------------------------------------------------------------


def pytest_collection_finish(session):
    """Re-baseline the leak witness AFTER collection: importing the test
    modules pulls in nearly all of druid_tpu (module singletons, jax
    backend side effects), and those one-time allocations are process
    state, not suite leaks. The return-to-baseline contract starts here."""
    if os.environ.get("DRUID_TPU_LEAK_WITNESS") != "1":
        return
    from tools.druidlint.leakwitness import session_witness
    w = session_witness()
    if w is not None:
        w.baseline = w.snapshot()


def pytest_unconfigure(config):
    # a lock-witness violation must not skip the stall/key/donor/leak
    # checks (or leave hooks monkeypatched): run all five even if an
    # earlier raises
    try:
        _unconfigure_lock_witness()
    finally:
        try:
            _unconfigure_stall_witness()
        finally:
            try:
                _unconfigure_key_witness()
            finally:
                try:
                    _unconfigure_donor_witness()
                finally:
                    _unconfigure_leak_witness()


def _unconfigure_stall_witness():
    if os.environ.get("DRUID_TPU_STALL_WITNESS") != "1":
        return
    from tools.druidlint.stallwitness import end_session_witness
    w = end_session_witness()
    if w is None:
        return
    print(f"stallwitness: {w.summary()}")
    for v in w.violations:
        print(f"stallwitness: UNTIMED PARK {v}")
    if w.violations:
        raise pytest.UsageError(
            "stall witness found untimed non-shutdown parks (see lines "
            "above)")


def _unconfigure_key_witness():
    if os.environ.get("DRUID_TPU_KEY_WITNESS") != "1":
        return
    from tools.druidlint.keywitness import end_session_witness
    w = end_session_witness()
    if w is None:
        return
    print(f"keywitness: {w.summary()}")
    for c in w.collisions:
        print(f"keywitness: COLLISION {c}")
    if w.collisions:
        raise pytest.UsageError(
            "key witness found cache-key collisions (see lines above)")


def _unconfigure_donor_witness():
    if os.environ.get("DRUID_TPU_DONOR_WITNESS") != "1":
        return
    from tools.druidlint.donorwitness import end_session_witness
    w = end_session_witness()
    if w is None:
        return
    violations = w.all_violations()
    print(f"donorwitness: {w.summary()}")
    for v in violations:
        print(f"donorwitness: VIOLATION {v}")
    if violations:
        raise pytest.UsageError(
            "donor witness found buffer-ownership violations (see lines "
            "above)")


def _unconfigure_leak_witness():
    if os.environ.get("DRUID_TPU_LEAK_WITNESS") != "1":
        return
    from tools.druidlint.leakwitness import end_session_witness
    w = end_session_witness()
    if w is None or w.baseline is None:
        return
    # deliberately-pinned cache state is not a leak: drop the engine's
    # device caches (stack cache pins whole segment sets) so the pool
    # axis measures unreleased OWNERSHIP, not cache policy. The pool
    # itself is NOT cleared — entries must die with their segments.
    from druid_tpu.engine import release_device_caches
    release_device_caches()
    leaks = w.leaks(grace_s=10.0)
    print(f"leakwitness: {len(w._started)} project thread start(s) "
          f"witnessed, {len(leaks)} leak(s) vs the post-collection "
          f"baseline")
    for l in leaks:
        print(f"leakwitness: LEAK {l}")
    if leaks:
        raise pytest.UsageError(
            "leak witness found resource leaks (see lines above)")


def _unconfigure_lock_witness():
    if os.environ.get("DRUID_TPU_LOCK_WITNESS") != "1":
        return
    from tools.druidlint.lockwitness import end_session_witness
    w = end_session_witness()
    if w is None:
        return
    from pathlib import Path
    from tools.druidlint.core import load_config
    from tools.druidlint.raceguard import analyze_tree
    root = Path(__file__).resolve().parent.parent
    prog = analyze_tree(root, load_config(root))
    lines = [f"lockwitness: {len(w.constructed)} wrapped construction "
             f"site(s), {len(w.observed_edges())} observed order edge(s)"]
    violations = w.order_violations()
    unexplained = w.unexplained_edges(prog)
    for v in violations:
        lines.append(f"lockwitness: ORDER VIOLATION (both directions "
                     f"observed): {v}")
    for u in unexplained:
        lines.append(f"lockwitness: UNEXPLAINED {u}")
    for m in w.mutation_violations:
        lines.append(f"lockwitness: UNGUARDED MUTATION {m}")
    print("\n".join(lines))
    if violations or unexplained or w.mutation_violations:
        raise pytest.UsageError(
            "lock witness found inconsistencies (see lines above)")
