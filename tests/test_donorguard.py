"""donorguard unit battery: each buffer-ownership rule must fire on its
positive shape, stay quiet on the disciplined shapes, honor per-line
suppressions — and the REAL tree must fail when a verified ownership bug
is planted back in (and pass stock): an analyzer whose rules never fire
on the exact bugs it was built to catch is no gate.

Pattern mirrors tests/test_stallguard.py: check_source with a root-less
config analyzes each snippet standalone through the real rule registry,
so suppression/baseline behavior is exactly the shipped one. The
real-tree gates run donorguard's findings pass directly over
raceguard.analyze_sources of the in-memory druid_tpu tree with surgical
string mutations — each one the historical bug shape the rule exists
for (the pre-fix grouping dispatch, an inline backend check, a skipped
step-0 re-init, a cached-entry donation).

The DonorWitness tests drive the dynamic leg at two layers: the
registry protocol directly (take/park/dispatch/discard transitions,
violation shapes) and an installed witness against a fresh
DeviceSegmentPool bound as the process singleton.
"""
import gc
import sys
from pathlib import Path

import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent

sys.path.insert(0, str(REPO_ROOT))

from tools.druidlint import load_config  # noqa: E402
from tools.druidlint.core import LintConfig, check_source  # noqa: E402
from tools.druidlint.donorguard import donor_findings  # noqa: E402
from tools.druidlint.donorwitness import DonorWitness, _leaves  # noqa: E402
from tools.druidlint.raceguard import analyze_sources  # noqa: E402


def cfg(*rules) -> LintConfig:
    c = LintConfig(rules=list(rules) if rules else [])
    c.root = "/nonexistent-donorguard-root"
    return c


def findings_of(source: str, rule: str, path: str = "druid_tpu/mod.py",
                config: LintConfig = None):
    return [f for f in check_source(source, path, config or cfg(rule))
            if f.rule == rule]


#: the donating-builder shape every dispatch fixture leans on — a
#: function RETURNING a jit-with-donate, grouping._build_device_fn's form
_BUILDER = """\
import jax


def build():
    def fn(arrays, aux, carries):
        return carries
    return jax.jit(fn, donate_argnums=(2,))

"""


# ---------------------------------------------------------------------------
# read-after-donate
# ---------------------------------------------------------------------------

def test_read_after_donate_fires():
    src = _BUILDER + """
def run(pool, arrays, aux):
    fn = build()
    carried = pool.take("o", ("k",))
    out = fn(arrays, aux, carried)
    nbytes = sum(a.nbytes for a in carried)
    return out, nbytes
"""
    got = findings_of(src, "read-after-donate")
    assert len(got) == 1
    assert "no longer exists" in got[0].message


def test_read_before_dispatch_is_quiet():
    src = _BUILDER + """
def run(pool, arrays, aux):
    fn = build()
    carried = pool.take("o", ("k",))
    nbytes = sum(a.nbytes for a in carried)
    out = fn(arrays, aux, carried)
    return out, nbytes
"""
    assert findings_of(src, "read-after-donate") == []


def test_rebind_after_dispatch_is_quiet():
    # a Store kills the donated binding: later reads see the new value
    src = _BUILDER + """
def run(pool, arrays, aux, fresh):
    fn = build()
    carried = pool.take("o", ("k",))
    out = fn(arrays, aux, carried)
    carried = fresh()
    return out, carried
"""
    assert findings_of(src, "read-after-donate") == []


def test_post_dispatch_discard_is_quiet():
    # routing the reference through an explicit discard helper is the
    # blessed failure-path shape, not a read of donated content
    src = _BUILDER + """
def run(pool, arrays, aux, discard_carries):
    fn = build()
    carried = pool.take("o", ("k",))
    try:
        out = fn(arrays, aux, carried)
    except Exception:
        discard_carries(carried)
        raise
    return out
"""
    assert findings_of(src, "read-after-donate") == []


def test_read_after_donate_suppression():
    src = _BUILDER + """
def run(pool, arrays, aux):
    fn = build()
    carried = pool.take("o", ("k",))
    out = fn(arrays, aux, carried)
    nbytes = sum(a.nbytes
                 for a in carried)  # druidlint: disable=read-after-donate
    return out, nbytes
"""
    assert findings_of(src, "read-after-donate") == []


# ---------------------------------------------------------------------------
# donate-cached-entry
# ---------------------------------------------------------------------------

def test_cached_entry_into_donated_argnum_fires():
    src = _BUILDER + """
def run(pool, arrays, aux, make):
    fn = build()
    carried = pool.get_or_build("o", ("k",), make)
    return fn(arrays, aux, carried)
"""
    got = findings_of(src, "donate-cached-entry")
    assert len(got) == 1
    assert "take" in got[0].message


def test_cached_entry_derived_value_fires():
    # derivation propagates the taint: tuple(cached) is still the
    # pool-referenced buffers
    src = _BUILDER + """
def run(pool, arrays, aux, make):
    fn = build()
    cached = pool.device_cached(("k",), make)
    carried = tuple(cached)
    return fn(arrays, aux, carried)
"""
    assert len(findings_of(src, "donate-cached-entry")) == 1


def test_conditional_fallback_does_not_launder():
    # the `if carried is None` fresh-grids fallback does NOT dominate the
    # dispatch: the other branch still feeds the peeked entry in
    src = _BUILDER + """
def run(pool, arrays, aux, fresh):
    fn = build()
    carried = pool.peek("o", ("k",))
    if carried is None:
        carried = fresh()
    return fn(arrays, aux, carried)
"""
    assert len(findings_of(src, "donate-cached-entry")) == 1


def test_dominating_take_clears_taint():
    src = _BUILDER + """
def run(pool, arrays, aux):
    fn = build()
    carried = pool.peek("o", ("k",))
    carried = pool.take("o", ("k",))
    return fn(arrays, aux, carried)
"""
    assert findings_of(src, "donate-cached-entry") == []


def test_cached_entry_suppression():
    src = _BUILDER + """
def run(pool, arrays, aux, make):
    fn = build()
    carried = pool.get_or_build("o", ("k",), make)
    return fn(arrays, aux,
              carried)  # druidlint: disable=donate-cached-entry
"""
    assert findings_of(src, "donate-cached-entry") == []


# ---------------------------------------------------------------------------
# take-without-repark
# ---------------------------------------------------------------------------

def test_take_never_discharged_fires():
    # log() mentions the popped name but is no park/discard/dispatch —
    # mentioning ownership is not discharging it
    src = """\
def run(pool, log):
    carried = pool.take("o", ("k",))
    log(carried)
"""
    got = findings_of(src, "take-without-repark")
    assert len(got) == 1
    assert "no path" in got[0].message


def test_dispatch_in_try_without_handler_discharge_fires():
    src = _BUILDER + """
def run(pool, arrays, aux):
    fn = build()
    carried = pool.take("o", ("k",))
    try:
        out = fn(arrays, aux, carried)
    except Exception:
        out = None
    return out
"""
    got = findings_of(src, "take-without-repark")
    assert len(got) == 1
    assert "dispatch" in got[0].message


def test_handler_discard_covers_the_dispatch():
    src = _BUILDER + """
def run(pool, arrays, aux, discard_carries):
    fn = build()
    carried = pool.take("o", ("k",))
    try:
        out = fn(arrays, aux, carried)
    except Exception:
        discard_carries(carried)
        raise
    return out
"""
    assert findings_of(src, "take-without-repark") == []


def test_unprotected_dispatch_is_quiet():
    # no try around the dispatch: an exception unwinds out of run()
    # entirely — the caller owns the failure, not this frame
    src = _BUILDER + """
def run(pool, arrays, aux):
    fn = build()
    carried = pool.take("o", ("k",))
    out = fn(arrays, aux, carried)
    pool.put("o", ("k",), out)
"""
    assert findings_of(src, "take-without-repark") == []


def test_park_discharges_the_take():
    src = """\
def run(pool):
    carried = pool.take("o", ("k",))
    pool.put("o", ("k",), carried)
"""
    assert findings_of(src, "take-without-repark") == []


def test_take_without_repark_suppression():
    src = """\
def run(pool, log):
    c = pool.take("o", ("k",))  # druidlint: disable=take-without-repark
    log(c)
"""
    assert findings_of(src, "take-without-repark") == []


# ---------------------------------------------------------------------------
# donate-platform-gate
# ---------------------------------------------------------------------------

def test_inline_backend_check_fires():
    src = """\
import jax


def enabled():
    return jax.default_backend() in ("tpu", "gpu")
"""
    got = findings_of(src, "donate-platform-gate")
    assert len(got) == 1
    assert "donation_supported" in got[0].message


def test_platform_attribute_compare_fires():
    src = """\
def probe(dev):
    return dev.platform == "tpu"
"""
    assert len(findings_of(src, "donate-platform-gate")) == 1


def test_blessed_gate_is_quiet():
    # the shipped default pins contracts.donation_supported as THE gate
    src = """\
import jax


def donation_supported():
    return jax.default_backend() in ("tpu", "gpu")
"""
    assert findings_of(src, "donate-platform-gate",
                       path="druid_tpu/engine/contracts.py") == []


def test_sys_platform_is_not_a_backend_probe():
    src = """\
import sys


def f():
    return sys.platform == "linux"
"""
    assert findings_of(src, "donate-platform-gate") == []


def test_platform_gate_config_extension():
    c = cfg("donate-platform-gate")
    c.donorguard_platform_gate = list(c.donorguard_platform_gate) + [
        "druid_tpu/mod.py::my_gate"]
    src = """\
import jax


def my_gate():
    return jax.default_backend() == "tpu"
"""
    assert findings_of(src, "donate-platform-gate", config=c) == []


def test_platform_gate_suppression():
    src = """\
import jax


def enabled(t):
    ok = jax.default_backend() in t  # druidlint: disable=donate-platform-gate
    return ok
"""
    assert findings_of(src, "donate-platform-gate") == []


# ---------------------------------------------------------------------------
# carry-grid-init
# ---------------------------------------------------------------------------

def test_donated_pallas_without_step0_init_fires():
    src = """\
import jax
from jax.experimental import pallas as pl


def agg(arrays):
    def kernel(ref):
        ref[0] = ref[0] + 1
    return pl.pallas_call(kernel)(arrays)


def build():
    return jax.jit(agg, donate_argnums=(0,))
"""
    got = findings_of(src, "carry-grid-init")
    assert len(got) == 1
    assert "step 0" in got[0].message


def test_step0_init_reached_through_helper_fires():
    # whole-program: the pallas host sits one call edge below the
    # donated entry point and is still reached
    src = """\
import jax
from jax.experimental import pallas as pl


def leaf(arrays):
    def kernel(ref):
        ref[0] = ref[0] + 1
    return pl.pallas_call(kernel)(arrays)


def agg(arrays):
    return leaf(arrays)


def build():
    return jax.jit(agg, donate_argnums=(0,))
"""
    got = findings_of(src, "carry-grid-init")
    assert len(got) == 1
    assert "leaf" in got[0].message


def test_step0_init_present_is_quiet():
    src = """\
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def agg(arrays):
    def kernel(ref):
        i = pl.program_id(0)

        @pl.when(i == jnp.int32(0))
        def _init():
            ref[0] = 0
    return pl.pallas_call(kernel)(arrays)


def build():
    return jax.jit(agg, donate_argnums=(0,))
"""
    assert findings_of(src, "carry-grid-init") == []


def test_non_donating_jit_is_quiet():
    src = """\
import jax
from jax.experimental import pallas as pl


def agg(arrays):
    def kernel(ref):
        ref[0] = ref[0] + 1
    return pl.pallas_call(kernel)(arrays)


def build():
    return jax.jit(agg)
"""
    assert findings_of(src, "carry-grid-init") == []


def test_carry_grid_init_suppression():
    # a fresh-init-by-design kernel declares itself on the pallas_call
    src = """\
import jax
from jax.experimental import pallas as pl


def agg(arrays):
    def kernel(ref):
        ref[0] = ref[0] + 1
    return pl.pallas_call(  # druidlint: disable=carry-grid-init
        kernel)(arrays)
"""
    # the jit sits in another module shape — keep it in this one
    src += """

def build():
    return jax.jit(agg, donate_argnums=(0,))
"""
    assert findings_of(src, "carry-grid-init") == []


# ---------------------------------------------------------------------------
# real-tree mutation gates: plant each rule's historical bug shape back
# into the ACTUAL druid_tpu sources and donorguard must catch it; the
# stock tree must be clean
# ---------------------------------------------------------------------------

def _tree_sources():
    return {p.relative_to(REPO_ROOT).as_posix(): p.read_text()
            for p in sorted((REPO_ROOT / "druid_tpu").rglob("*.py"))}


def _tree_findings(sources):
    config = load_config(REPO_ROOT)
    return donor_findings(analyze_sources(sources, config), config)


def _mutate(sources, path, old, new, count=1):
    src = sources[path]
    assert src.count(old) == count, (
        f"mutation anchor drifted in {path}: {old!r} found "
        f"{src.count(old)}x, expected {count}")
    sources[path] = src.replace(old, new)
    return sources


def test_real_tree_is_donorguard_clean():
    assert _tree_findings(_tree_sources()) == {}


def test_prefix_dispatch_shape_fires_read_after_donate_and_repark():
    # the pre-PR shape: no exception-path discard, donated bytes summed
    # AFTER the dispatch — both ownership bugs donorguard was built for
    path = "druid_tpu/engine/grouping.py"
    # (1) the build under engine/build loses its exception-path discard
    sources = _mutate(
        _tree_sources(), path,
        """                            try:
                                _build_kernel_program(fn, arrays, aux,
                                                      tuple(carried))
                            except BaseException:
                                # a failed build latches the program off,
                                # so its carries are dead: discharge the
                                # ownership the take popped
                                megakernel.discard_carries(carried)
                                raise
""",
        """                            _build_kernel_program(fn, arrays, aux,
                                                  tuple(carried))
""")
    # (2) so does the dispatch, and the donated bytes are summed after it
    sources = _mutate(
        sources, path,
        """                    try:
                        counts, states, raw = fn(arrays, aux,
                                                 tuple(carried))
                    except BaseException:
                        # the take popped ownership; a failed dispatch may
                        # have already invalidated the donated buffers
                        # mid-flight, so discharge them explicitly — the
                        # pool's resident bytes stay truthful and the next
                        # tick rebuilds fresh zeros (donorguard
                        # take-without-repark)
                        megakernel.discard_carries(carried)
                        raise
""",
        """                    counts, states, raw = fn(arrays, aux,
                                             tuple(carried))
                    donated_nbytes = sum(
                        int(getattr(a, "nbytes", 0))
                        for a in carried) if donated else 0
""")
    data = _tree_findings(sources)
    assert "druid_tpu/engine/grouping.py" in data.get("read-after-donate",
                                                      {})
    # BOTH takes (the pool pop and the standing-donor pop) now leak on
    # the Mosaic-retry exception path
    repark = data.get("take-without-repark", {}).get(
        "druid_tpu/engine/grouping.py", [])
    assert len(repark) == 2


def test_cached_entry_mutation_fires():
    # take→device_cached: the dispatch would donate buffers the pool
    # still references
    sources = _tree_sources()
    _mutate(sources, "druid_tpu/engine/grouping.py",
            'carried = segment.device_take(("megacarry", sig))',
            'carried = segment.device_cached(("megacarry", sig), '
            'lambda: None)')
    _mutate(sources, "druid_tpu/engine/grouping.py",
            'carried = donor.device_take(("megacarry", sig))',
            'carried = donor.device_cached(("megacarry", sig), '
            'lambda: None)')
    data = _tree_findings(sources)
    assert "druid_tpu/engine/grouping.py" in data.get("donate-cached-entry",
                                                      {})


def test_inline_platform_gate_mutation_fires():
    # scatter the donation-enable decision back inline: the CPU-segfault
    # class donate-platform-gate centralizes away
    sources = _mutate(
        _tree_sources(), "druid_tpu/engine/megakernel.py",
        "    return donation_supported()",
        '    return jax.default_backend() in ("tpu", "gpu")')
    data = _tree_findings(sources)
    assert "druid_tpu/engine/megakernel.py" in data.get(
        "donate-platform-gate", {})


def test_missing_step0_init_mutation_fires():
    # break the PR 11 bit-identity discipline: the init block no longer
    # runs at grid step 0, so donated reuse replays stale aggregates
    # (the kernel the donating megakernel program reaches lives in
    # pallas_agg.grouped_reduce, shared with the staged strategy)
    sources = _mutate(
        _tree_sources(), "druid_tpu/engine/pallas_agg.py",
        "@pl.when(i == jnp.int32(0))",
        "@pl.when(i == jnp.int32(1))")
    data = _tree_findings(sources)
    assert "druid_tpu/engine/pallas_agg.py" in data.get("carry-grid-init",
                                                        {})


# ---------------------------------------------------------------------------
# DonorWitness: the dynamic leg
# ---------------------------------------------------------------------------

class _Leaf:
    """Weakref-able array stand-in with a device-buffer delete()."""

    def __init__(self, shape=(4,)):
        self.dtype = "int32"
        self.shape = shape
        self.deleted = False

    def delete(self):
        self.deleted = True


def test_leaves_recurses_containers():
    a, b, c = _Leaf(), _Leaf(), _Leaf()
    got = _leaves(((a, [b]), {"x": c, "y": "not-an-array"}))
    assert got == [a, b, c]


def test_witness_clean_cycle_take_dispatch_repark():
    w = DonorWitness("r")
    leaf = _Leaf()
    w._note_park((leaf,))               # built fresh, parked
    assert id(leaf) in w.resident
    w._note_take((leaf,), "k")          # popped: caller owns it
    assert id(leaf) in w.outstanding and id(leaf) not in w.resident
    w._before_dispatch((leaf,))         # not resident: no violation
    w._after_dispatch((leaf,))          # donation consumed it
    assert leaf.deleted                 # simulated invalidation
    assert w.outstanding == {}
    assert w.all_violations() == []
    assert w.counts["donated-delete"] == 1


def test_witness_cached_entry_donation_violates():
    w = DonorWitness("r")
    leaf = _Leaf()
    w._note_park((leaf,))
    w._before_dispatch((leaf,))         # donated while still pool-resident
    assert any("cached-entry donation" in v for v in w.all_violations())
    w._after_dispatch((leaf,))
    assert not leaf.deleted             # never owned: witness won't touch it


def test_witness_gc_while_outstanding_violates():
    w = DonorWitness("r")
    leaf = _Leaf()
    w._note_take((leaf,), "k")
    del leaf
    gc.collect()
    assert any("garbage-collected while outstanding" in v
               for v in w.all_violations())


def test_witness_unreparked_at_teardown():
    w = DonorWitness("r")
    leaf = _Leaf()
    w._note_take((leaf,), "('o', 'k')")
    got = w.unreparked()
    assert len(got) == 1 and "still outstanding" in got[0]
    assert "('o', 'k')" in got[0]


def test_witness_explicit_discard_discharges():
    w = DonorWitness("r")
    leaf = _Leaf()
    w._note_take((leaf,), "k")
    w._discharge((leaf,), "discard")
    assert w.all_violations() == []
    assert w.counts["discard"] == 1


def test_witness_skips_numpy_leaves():
    # host ndarrays refuse weakrefs and carry no device buffer — the
    # protocol governs device buffers only
    w = DonorWitness("r")
    w._note_take((np.zeros(4, dtype=np.int32),), "k")
    assert w.outstanding == {}
    assert w.all_violations() == []


def test_witness_install_is_reversible():
    from druid_tpu.data import devicepool
    from druid_tpu.engine import grouping, megakernel
    before = (devicepool.DeviceSegmentPool.take,
              devicepool.DeviceSegmentPool.get_or_build,
              grouping._build_device_fn, megakernel.discard_carries)
    with DonorWitness("r") as w:
        assert devicepool.DeviceSegmentPool.take is not before[0]
        assert w._installed
    after = (devicepool.DeviceSegmentPool.take,
             devicepool.DeviceSegmentPool.get_or_build,
             grouping._build_device_fn, megakernel.discard_carries)
    assert after == before


def test_witnessed_program_lowers_as_its_product_does(monkeypatch):
    # grouping._build_kernel_program lowers a pallas-class program before
    # it runs: a witnessed builder's product must still be lowerable, or
    # every witnessed build would latch pallas off
    import jax
    from druid_tpu.engine import grouping
    jitted = jax.jit(lambda arrays, aux, carries=(): (arrays + aux, carries))
    monkeypatch.setattr(grouping, "_build_device_fn", lambda: jitted)
    with DonorWitness("r"):
        fn = grouping._build_device_fn()
        assert fn is not jitted
        grouping._build_kernel_program(fn, 1.0, 2.0)
        assert fn(1.0, 2.0)[0] == 3.0


def test_witness_end_to_end_on_singleton_pool(monkeypatch):
    # a fresh pool bound as the process singleton: real take/get_or_build
    # traffic is witnessed; other pool instances stay invisible
    import jax.numpy as jnp
    from druid_tpu.data import devicepool
    pool = devicepool.DeviceSegmentPool(budget_bytes=0)
    other = devicepool.DeviceSegmentPool(budget_bytes=0)
    monkeypatch.setattr(devicepool, "_POOL", pool)

    class _Anchor:                    # bare object() refuses weakrefs
        pass

    anchor, oanchor = _Anchor(), _Anchor()
    owner = pool.register_owner(anchor)
    oowner = other.register_owner(oanchor)
    with DonorWitness("r") as w:
        entry = pool.get_or_build(owner, ("k",),
                                  lambda: (jnp.zeros(4, jnp.int32),))
        assert len(w.resident) == 1
        other.get_or_build(oowner, ("k",),
                           lambda: (jnp.ones(4, jnp.int32),))
        assert len(w.resident) == 1          # non-singleton: unrecorded
        popped = pool.take(owner, ("k",))
        assert popped is entry
        assert len(w.outstanding) == 1 and w.resident == {}
        assert w.unreparked()                # owed until re-parked...
        pool.get_or_build(owner, ("k",), lambda: popped)
        assert w.unreparked() == []          # ...and discharged by it
    assert w.all_violations() == []
    assert w.counts == {"take": 1, "repark": 2}
