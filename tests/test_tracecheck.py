"""tracecheck rule tests: positive/negative/suppression snippets per rule,
the unused-suppression audit, the --only subset flag, the scan cache, and
real-tree mutation gates (the acceptance contract: editing a BlockSpec
shape, an accumulator identity dtype, or a fold kernel's device_combine in
a fixture must fail `python -m tools.druidlint --fail-on-new`)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from tools.druidlint import check_source  # noqa: E402
from tools.druidlint.core import LintConfig  # noqa: E402
from tools.druidlint.tracecheck import Sym, SymEval, load_contracts  # noqa: E402

PALLAS = "druid_tpu/engine/pallas_agg.py"
MEGA = "druid_tpu/engine/megakernel.py"
ENGINE = "druid_tpu/engine/foo.py"
KMOD = "druid_tpu/engine/kernels.py"


def cfg(**kw):
    return LintConfig(root=str(REPO_ROOT), **kw)


def rules_hit(source, path=ENGINE, config=None):
    return {f.rule for f in check_source(textwrap.dedent(source), path,
                                         config or cfg())}


# ---- the Sym domain -------------------------------------------------------

def test_sym_interval_and_stride_arithmetic():
    contracts = load_contracts(str(REPO_ROOT))
    env = {"BLK": Sym(1024, 2048, 128), "num_total": Sym(1, 131072, 1)}
    ev = SymEval(env, contracts)
    import ast as _ast

    def e(src):
        return ev.eval(_ast.parse(src, mode="eval").body)

    r = e("BLK // 128")
    assert (r.lo, r.hi) == (8, 16)
    g2 = e("_round_up(num_total, 128) + 1024")
    assert g2.multiple_of(128) and g2.hi == 131072 + 1024
    rows = e("(_round_up(num_total, 128) + 1024) // 128")
    assert rows.hi == (131072 + 1024) // 128
    assert e("MAX_W").value == contracts["MAX_W"]   # contract constant
    assert e("unknown_name") is None
    # stride of min/max must divide EVERY argument, not the first two
    env["u"] = Sym(100, 300, 1)
    assert not e("max(BLK, BLK, u)").multiple_of(128)


def test_rank0_blockspec_does_not_crash():
    src = """
    from jax.experimental import pallas as pl
    spec = pl.BlockSpec((), lambda: ())
    """
    check_source(textwrap.dedent(src), PALLAS, cfg())   # no IndexError


# ---- pallas-tile-shape ----------------------------------------------------

def test_unaligned_last_dim_flagged():
    src = """
    from jax.experimental import pallas as pl
    grid_spec = pl.GridSpec(
        grid=(8,),
        in_specs=[pl.BlockSpec((8, 64), lambda i: (i, 0))],
    )
    """
    assert "pallas-tile-shape" in rules_hit(src, PALLAS)


def test_aligned_contract_constant_shape_ok():
    src = """
    from jax.experimental import pallas as pl
    from druid_tpu.engine.contracts import LANE
    grid_spec = pl.GridSpec(
        grid=(8,),
        in_specs=[pl.BlockSpec((8, LANE), lambda i: (i, 0))],
    )
    """
    assert "pallas-tile-shape" not in rules_hit(src, PALLAS)


def test_symbolic_shape_resolves_through_declared_bounds():
    # BLK/W/num_total come from SYMBOL_BOUNDS (plan_window is opaque);
    # the derived (R, 128) and (G2 // 128, 128) must be accepted
    src = """
    from jax.experimental import pallas as pl

    def build(span, num_total):
        BLK, W = plan_window(span)
        R = BLK // 128
        G2 = _round_up(num_total, 128) + W
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((R, 128), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((G2 // 128, 128), lambda i: (0, 0))],
        )
    """
    assert "pallas-tile-shape" not in rules_hit(src, PALLAS)


def test_unresolvable_shape_flagged():
    src = """
    from jax.experimental import pallas as pl

    def build(mystery):
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((mystery, 128), lambda i: (i, 0))],
        )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert any(f.rule == "pallas-tile-shape" and "resolvable" in f.message
               for f in hits)


def test_index_map_arity_mismatch_flagged():
    src = """
    from jax.experimental import pallas as pl
    grid_spec = pl.GridSpec(
        grid=(8, 4),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
    )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert any(f.rule == "pallas-tile-shape" and "grid" in f.message
               for f in hits)


def test_index_map_rank_mismatch_flagged():
    src = """
    from jax.experimental import pallas as pl
    grid_spec = pl.GridSpec(
        grid=(8,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i,))],
    )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert any(f.rule == "pallas-tile-shape" and "coordinate" in f.message
               for f in hits)


def test_out_spec_out_shape_drift_flagged():
    src = """
    import jax
    from jax.experimental import pallas as pl

    def build(num_total):
        G2 = _round_up(num_total, 128)
        out_shapes = [jax.ShapeDtypeStruct((G2 // 64, 128), int)]
        return pl.GridSpec(
            grid=(8,),
            out_specs=[pl.BlockSpec((G2 // 128, 128), lambda i: (0, 0))],
        ), out_shapes
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert any(f.rule == "pallas-tile-shape" and "out_shape" in f.message
               for f in hits)


def test_tile_shape_outside_pallas_modules_ignored():
    src = """
    from jax.experimental import pallas as pl
    grid_spec = pl.GridSpec(
        grid=(8,),
        in_specs=[pl.BlockSpec((8, 64), lambda i: (i, 0))],
    )
    """
    assert "pallas-tile-shape" not in rules_hit(src, ENGINE)


def test_tile_shape_suppression():
    src = """
    from jax.experimental import pallas as pl
    grid_spec = pl.GridSpec(
        grid=(8,),
        in_specs=[pl.BlockSpec((8, 64), lambda i: (i, 0))],  # druidlint: disable=pallas-tile-shape
    )
    """
    assert "pallas-tile-shape" not in rules_hit(src, PALLAS)


# ---- pallas-accum-dtype ---------------------------------------------------

def test_int_identity_with_float_ctor_flagged():
    src = """
    import jax.numpy as jnp
    ident = jnp.float32(2**31 - 1)
    """
    assert "pallas-accum-dtype" in rules_hit(src, PALLAS)


def test_identities_with_contract_dtypes_ok():
    src = """
    import jax.numpy as jnp
    a = jnp.int32(2**31 - 1)
    b = jnp.int32(-(2**31))
    c = jnp.float32(jnp.inf)
    d = jnp.float32(-jnp.inf)
    e = jnp.int32(0)
    """
    assert "pallas-accum-dtype" not in rules_hit(src, PALLAS)


def test_float_identity_with_int_ctor_flagged():
    src = """
    import jax.numpy as jnp
    ident = jnp.int32(jnp.inf)
    """
    assert "pallas-accum-dtype" in rules_hit(src, PALLAS)


def test_x64_dtype_inside_kernel_body_flagged():
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(ref, out):
        out[:, :] = ref[:, :].astype(jnp.int64)

    def run(x):
        return pl.pallas_call(kernel, out_shape=None)(x)
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert any(f.rule == "pallas-accum-dtype" and "kernel body" in f.message
               for f in hits)


def test_x64_widening_outside_kernel_ok_for_accum_rule():
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(ref, out):
        out[:, :] = ref[:, :]

    def run(x):
        outs = pl.pallas_call(kernel, out_shape=None)(x)
        return outs.astype(jnp.int64)  # druidlint: disable=x64-dtype
    """
    assert "pallas-accum-dtype" not in rules_hit(src, PALLAS)


# ---- vmem-budget ----------------------------------------------------------

def test_over_budget_tiles_flagged():
    src = """
    from jax.experimental import pallas as pl
    grid_spec = pl.GridSpec(
        grid=(8,),
        in_specs=[pl.BlockSpec((32768, 128), lambda i: (i, 0))],
    )
    """
    assert "vmem-budget" in rules_hit(src, PALLAS)


def test_within_budget_tiles_ok():
    src = """
    from jax.experimental import pallas as pl
    grid_spec = pl.GridSpec(
        grid=(8,),
        in_specs=[pl.BlockSpec((16, 128), lambda i: (i, 0))],
    )
    """
    assert "vmem-budget" not in rules_hit(src, PALLAS)


def test_vmem_cap_config_override():
    src = """
    from jax.experimental import pallas as pl
    grid_spec = pl.GridSpec(
        grid=(8,),
        in_specs=[pl.BlockSpec((16, 128), lambda i: (i, 0))],
    )
    """
    # 16*128*4 = 8192 bytes > a 4096-byte cap
    assert "vmem-budget" in rules_hit(src, PALLAS,
                                      cfg(vmem_cap_bytes=4096))


def test_unbounded_multiplicity_flagged():
    src = """
    from jax.experimental import pallas as pl

    def build(things):
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))] * len(things),
        )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert any(f.rule == "vmem-budget" and "multiplicity" in f.message
               for f in hits)


def test_filter_bitmap_word_tiles_budgeted():
    """Device filter-bitmap words (engine/filters.py): the worst-case word
    tile is (Rw32, 128) with Rw32 ≤ contracts.FILTER_WORDS_PER_BLOCK —
    SYMBOL_BOUNDS covers it, so a kernel streaming bitmap words stays
    under the vmem budget without per-site annotations."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(span, Rw32):
        BLK, W = plan_window(span)
        R = BLK // 128
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((R, 128), lambda i: (i, jnp.int32(0))),
                      pl.BlockSpec((Rw32, 128),
                                   lambda i: (i, jnp.int32(0)))],
        )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert not [f for f in hits if f.rule in ("vmem-budget",
                                              "pallas-tile-shape")], hits


def test_filter_bitmap_word_tiles_oversize_flagged():
    """...and an unboundedly-scaled word tile still blows the cap — the
    bound is a ceiling, not a waiver."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(Rw32):
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((Rw32 * 65536, 128),
                                   lambda i: (i, jnp.int32(0)))],
        )
    """
    assert "vmem-budget" in rules_hit(src, PALLAS)


# ---- x64-dtype ------------------------------------------------------------

def test_x64_in_traced_fn_flagged():
    src = """
    import jax
    import jax.numpy as jnp

    def f(x):
        return x.astype(jnp.int64)

    fn = jax.jit(f)
    """
    assert "x64-dtype" in rules_hit(src, ENGINE)


def test_x64_gated_fn_ok():
    src = """
    import jax
    import jax.numpy as jnp

    def f(x):
        dt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        return x.astype(dt)

    fn = jax.jit(f)
    """
    assert "x64-dtype" not in rules_hit(src, ENGINE)


def test_x64_in_untraced_host_fn_ok():
    src = """
    import jax.numpy as jnp

    def host_post(x):
        return x.astype(jnp.int64)
    """
    assert "x64-dtype" not in rules_hit(src, ENGINE)


def test_x64_outside_device_modules_ok():
    src = """
    import jax
    import jax.numpy as jnp

    def f(x):
        return x.astype(jnp.int64)

    fn = jax.jit(f)
    """
    assert "x64-dtype" not in rules_hit(src, "druid_tpu/cluster/foo.py")


def test_x64_suppression_with_rationale():
    src = """
    import jax
    import jax.numpy as jnp

    def f(x):
        # exactness contract, x64 globally on
        return x.astype(jnp.int64)  # druidlint: disable=x64-dtype

    fn = jax.jit(f)
    """
    assert "x64-dtype" not in rules_hit(src, ENGINE)


# ---- agg-contract ---------------------------------------------------------

AGG_BODY = """
    def signature(self):
        return "{sig}"

    def update(self, cols, mask, keys, num, aux):
        return None

    def combine(self, a, b):
        return a

    def empty_state(self, n):
        return None
"""


def _agg(name, sig, extra="", rk=None):
    rk_line = f"    reduce_kind = \"{rk}\"\n" if rk else ""
    return (f"class {name}(AggKernel):\n" + rk_line
            + AGG_BODY.format(sig=sig) + extra)


def test_fold_kernel_without_device_combine_flagged():
    src = "from druid_tpu.engine.kernels import AggKernel\n" \
        + _agg("BadKernel", "bad")
    assert "agg-contract" in rules_hit(src, KMOD)


def test_fold_kernel_with_device_combine_ok():
    src = "from druid_tpu.engine.kernels import AggKernel\n" \
        + _agg("GoodKernel", "good",
               "\n    def device_combine(self, a, b):\n        return a\n")
    assert "agg-contract" not in rules_hit(src, KMOD)


def test_sum_kernel_without_device_combine_ok():
    src = "from druid_tpu.engine.kernels import AggKernel\n" \
        + _agg("SumLike", "sumlike", rk="sum")
    assert "agg-contract" not in rules_hit(src, KMOD)


def test_dynamic_reduce_kind_skips_fold_check():
    src = ("from druid_tpu.engine.kernels import AggKernel\n"
           + _agg("DynKernel", "dyn",
                  "\n    def __init__(self, child):\n"
                  "        self.reduce_kind = child.reduce_kind\n"))
    assert "agg-contract" not in rules_hit(src, KMOD)


def test_missing_required_method_flagged():
    src = ("from druid_tpu.engine.kernels import AggKernel\n"
           "class NoUpdate(AggKernel):\n"
           "    reduce_kind = \"sum\"\n"
           "    def signature(self):\n"
           "        return \"nu\"\n"
           "    def combine(self, a, b):\n"
           "        return a\n"
           "    def empty_state(self, n):\n"
           "        return None\n")
    hits = check_source(src, KMOD, cfg())
    assert any(f.rule == "agg-contract" and "update" in f.message
               for f in hits)


def test_duplicate_signatures_flagged():
    src = ("from druid_tpu.engine.kernels import AggKernel\n"
           + _agg("KernA", "same", rk="sum")
           + _agg("KernB", "same", rk="sum"))
    hits = check_source(src, KMOD, cfg())
    assert any(f.rule == "agg-contract" and "duplicated" in f.message
               for f in hits)


def test_distinct_signatures_ok():
    src = ("from druid_tpu.engine.kernels import AggKernel\n"
           + _agg("KernA", "a", rk="sum") + _agg("KernB", "b", rk="sum"))
    assert "agg-contract" not in rules_hit(src, KMOD)


def test_agg_contract_covers_ext_modules():
    src = "from druid_tpu.engine.kernels import AggKernel\n" \
        + _agg("ExtKernel", "ext")
    assert "agg-contract" in rules_hit(src, "druid_tpu/ext/custom.py")


# ---- preferred-element-type -----------------------------------------------

def test_dot_general_without_preferred_flagged():
    src = """
    from jax import lax

    def f(a, b):
        return lax.dot_general(a, b, (((1,), (0,)), ((), ())))
    """
    assert "preferred-element-type" in rules_hit(src, ENGINE)


def test_dot_general_with_preferred_ok():
    src = """
    import jax.numpy as jnp
    from jax import lax

    def f(a, b):
        return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)
    """
    assert "preferred-element-type" not in rules_hit(src, ENGINE)


def test_host_numpy_matmul_not_flagged():
    src = """
    import numpy as np

    def f(a, b):
        return np.matmul(a, b)
    """
    assert "preferred-element-type" not in rules_hit(src, ENGINE)


# ---- unused-suppression ---------------------------------------------------

def test_dead_pragma_reported_with_audit_on():
    src = "x = 1  # druidlint: disable=swallowed-exception\n"
    hits = check_source(src, ENGINE, cfg(report_unused_suppressions=True))
    assert any(f.rule == "unused-suppression" for f in hits)


def test_dead_pragma_silent_without_audit():
    src = "x = 1  # druidlint: disable=swallowed-exception\n"
    assert "unused-suppression" not in rules_hit(src)


def test_live_pragma_not_reported():
    src = textwrap.dedent("""
    def f():
        try:
            g()
        except Exception:  # druidlint: disable=swallowed-exception
            pass
    """)
    hits = check_source(src, ENGINE, cfg(report_unused_suppressions=True))
    assert not any(f.rule == "unused-suppression" for f in hits)
    assert not any(f.rule == "swallowed-exception" for f in hits)


def test_typoed_rule_name_reported():
    src = "x = 1  # druidlint: disable=swalloed-exception\n"
    hits = check_source(src, ENGINE, cfg(report_unused_suppressions=True))
    assert any(f.rule == "unused-suppression"
               and "no registered rule" in f.message for f in hits)


def test_unused_suppression_rule_not_audited_under_only_subset():
    # with a rule subset the unheld pragmas' usage is unknowable — no noise
    src = "x = 1  # druidlint: disable=swallowed-exception\n"
    hits = check_source(src, ENGINE, cfg(
        report_unused_suppressions=True,
        rules=["jit-in-hot-path", "unused-suppression"]))
    assert not any(f.rule == "unused-suppression" for f in hits)


# ---- CLI: --only, cache, real-tree mutation gates -------------------------

def _run_cli(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tools.druidlint", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)


def test_only_flag_runs_subset(tmp_path):
    target = tmp_path / "druid_tpu" / "engine" / "bad.py"
    target.parent.mkdir(parents=True)
    target.write_text(
        "import jax\nimport jax.numpy as jnp\n"
        "def f(x):\n"
        "    try:\n"
        "        return x.astype(jnp.int64)\n"
        "    except Exception:\n"
        "        pass\n"
        "fn = jax.jit(f)\n")
    both = _run_cli("--root", str(tmp_path), "--json", "--no-cache",
                    "druid_tpu")
    rules = {f["rule"] for f in json.loads(both.stdout)["findings"]}
    assert {"x64-dtype", "swallowed-exception"} <= rules
    only = _run_cli("--root", str(tmp_path), "--json", "--no-cache",
                    "--only", "x64-dtype", "druid_tpu")
    rules = {f["rule"] for f in json.loads(only.stdout)["findings"]}
    assert rules == {"x64-dtype"}


def test_only_flag_rejects_unknown_rule(tmp_path):
    (tmp_path / "druid_tpu").mkdir()
    p = _run_cli("--root", str(tmp_path), "--only", "no-such-rule",
                 "druid_tpu")
    assert p.returncode == 2
    assert "unknown rules" in p.stderr


def test_scan_cache_hits_and_invalidates(tmp_path):
    target = tmp_path / "druid_tpu" / "mod.py"
    target.parent.mkdir(parents=True)
    target.write_text("def f():\n    try:\n        g()\n"
                      "    except Exception:\n        pass\n")
    cold = _run_cli("--root", str(tmp_path), "--json", "druid_tpu")
    cache = tmp_path / ".druidlint-cache.json"
    assert cache.exists()
    warm = _run_cli("--root", str(tmp_path), "--json", "druid_tpu")
    assert json.loads(cold.stdout)["findings"] == \
        json.loads(warm.stdout)["findings"]
    # edit the file: the cached findings must be dropped, not resurrected
    target.write_text("def f():\n    return 1\n")
    fixed = _run_cli("--root", str(tmp_path), "--json", "druid_tpu")
    assert json.loads(fixed.stdout)["findings"] == []


def test_restricted_scan_does_not_truncate_cache(tmp_path):
    bad = "def f():\n    try:\n        g()\n    except Exception:\n        pass\n"
    (tmp_path / "druid_tpu").mkdir()
    (tmp_path / "druid_tpu" / "a.py").write_text(bad)
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "b.py").write_text(bad)
    _run_cli("--root", str(tmp_path), "--json")               # full scan
    _run_cli("--root", str(tmp_path), "--json", "druid_tpu")  # restricted
    cached = json.loads((tmp_path / ".druidlint-cache.json").read_text())
    assert set(cached["files"]) == {"druid_tpu/a.py", "tools/b.py"}


def test_update_baseline_rejects_only_subset(tmp_path):
    (tmp_path / "druid_tpu").mkdir()
    p = _run_cli("--root", str(tmp_path), "--update-baseline",
                 "--only", "vmem-budget")
    assert p.returncode == 2
    assert "full scan" in p.stderr


MUTATIONS = {
    "blockspec-shape": (
        "druid_tpu/engine/pallas_agg.py", "pl.BlockSpec((R, 128)",
        "pl.BlockSpec((R, 120)", "pallas-tile-shape"),
    "accum-identity-dtype": (
        "druid_tpu/engine/pallas_agg.py", "ident = jnp.int32(2**31 - 1)",
        "ident = jnp.float32(2**31 - 1)", "pallas-accum-dtype"),
    "out-grid-rows": (
        "druid_tpu/engine/pallas_agg.py",
        "jax.ShapeDtypeStruct((G2 // 128, 128), dt)",
        "jax.ShapeDtypeStruct((G2 // 64, 128), dt)", "pallas-tile-shape"),
    "drop-device-combine": (
        # FirstLastKernel is fold-kind: renaming ITS device_combine (the
        # base-class raise-stub keeps its name) breaks the fold contract
        "druid_tpu/engine/kernels.py",
        "    def device_combine(self, a, b):\n"
        "        import jax.numpy as jnp\n"
        "        at, av, ah = a",
        "    def renamed_combine(self, a, b):\n"
        "        import jax.numpy as jnp\n"
        "        at, av, ah = a", "agg-contract"),
    "drop-preferred-element-type": (
        "druid_tpu/engine/mmagg.py",
        "preferred_element_type=jnp.int32)", "),",
        "preferred-element-type"),
    "mega-mask-tile-unaligned": (
        # the megakernel's mask word tile (the shared kernel's first word
        # tile): an unaligned last dim compiles on the interpreter but
        # fails on-chip — lint must catch
        "druid_tpu/engine/pallas_agg.py",
        "pl.BlockSpec((WORD_TILE_ROWS, 128),",
        "pl.BlockSpec((WORD_TILE_ROWS, 120),", "pallas-tile-shape"),
    "mega-key-sentinel-dtype": (
        # the in-kernel masked-key sentinel must stay the int32 identity
        "druid_tpu/engine/pallas_agg.py", "kb, jnp.int32(2**31 - 1))",
        "kb, jnp.float32(2**31 - 1))", "pallas-accum-dtype"),
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_real_tree_mutation_fails_gate(mutation, tmp_path):
    """Mutating a real engine contract in a fixture copy of the tree is
    caught by --fail-on-new (the acceptance criterion for tracecheck)."""
    rel, old, new, expect_rule = MUTATIONS[mutation]
    src = (REPO_ROOT / rel).read_text()
    assert old in src, f"mutation anchor missing from {rel}"
    target = tmp_path / rel
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(src.replace(old, new, 1))
    proc = _run_cli("--root", str(tmp_path), "--fail-on-new", "--json",
                    "--no-cache", "druid_tpu")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rules = {f["rule"] for f in json.loads(proc.stdout)["findings"]}
    assert expect_rule in rules, (mutation, rules)


def test_real_tree_scans_clean_with_tracecheck():
    """The shipped engine passes every tracecheck rule with no baseline
    entries (strict gate, no grandfathering)."""
    proc = _run_cli("--fail-on-new", "--no-cache", "--only",
                    "pallas-tile-shape,pallas-accum-dtype,vmem-budget,"
                    "x64-dtype,agg-contract,preferred-element-type")
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---- shard-spec -----------------------------------------------------------

SHARD = "druid_tpu/parallel/speclayout.py"

_SHARD_OK = """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(stacked, time0s, aux):
        counts = stacked
        merged = aux
        return counts, merged

    def run(mesh, xs, t0s, aux):
        axis = mesh.axis_names[0]
        f = shard_map(body, mesh=mesh, in_specs=(P(axis, None), P(axis), P()),
                      out_specs=(P(), P()))
        return f(xs, t0s, aux)
"""


def test_shard_spec_ok_passes():
    assert "shard-spec" not in rules_hit(_SHARD_OK, SHARD)


def test_shard_spec_in_arity_mismatch_flagged():
    src = _SHARD_OK.replace("in_specs=(P(axis, None), P(axis), P())",
                            "in_specs=(P(axis, None), P(axis))")
    assert "shard-spec" in rules_hit(src, SHARD)


def test_shard_spec_out_arity_mismatch_flagged():
    src = _SHARD_OK.replace("out_specs=(P(), P())",
                            "out_specs=(P(), P(), P())")
    assert "shard-spec" in rules_hit(src, SHARD)


def test_shard_spec_unknown_axis_flagged():
    src = _SHARD_OK.replace("in_specs=(P(axis, None), P(axis), P())",
                            "in_specs=(P('seg', None), P(axis), P())")
    assert "shard-spec" in rules_hit(src, SHARD)


def test_shard_spec_mesh_literal_axis_ok():
    src = """
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    def body(xs):
        return (xs,)

    def run(devices, xs):
        mesh = Mesh(devices, ("seg",))
        f = shard_map(body, mesh=mesh, in_specs=(P("seg"),),
                      out_specs=(P("seg"),))
        return f(xs)
    """
    assert "shard-spec" not in rules_hit(src, SHARD)


def test_shard_spec_opaque_axis_module_skips_axis_check():
    """No mesh.axis_names binding and no Mesh construction in the module:
    axis provenance cannot be judged, so only arity is checked."""
    src = """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(xs):
        return (xs,)

    def run(mesh, axis, xs):
        f = shard_map(body, mesh=mesh, in_specs=(P(axis),),
                      out_specs=(P(axis),))
        return f(xs)
    """
    assert "shard-spec" not in rules_hit(src, SHARD)


def test_shard_spec_vararg_body_skips_in_arity():
    src = """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(*xs):
        return (xs,)

    def run(mesh, xs):
        axis = mesh.axis_names[0]
        f = shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=(P(axis),))
        return f(xs, xs)
    """
    assert "shard-spec" not in rules_hit(src, SHARD)


def test_shard_spec_only_in_shard_modules():
    src = _SHARD_OK.replace("in_specs=(P(axis, None), P(axis), P())",
                            "in_specs=(P(axis),)")
    assert "shard-spec" not in rules_hit(src, ENGINE)


def test_shard_spec_suppression():
    src = _SHARD_OK.replace(
        "in_specs=(P(axis, None), P(axis), P()),",
        "in_specs=(P(axis, None), P(axis)),  # druidlint: disable=shard-spec")
    assert "shard-spec" not in rules_hit(src, SHARD)


def test_shard_spec_defaulted_params_tolerated():
    src = """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(xs, t0s, scale=2):
        return (xs,)

    def run(mesh, xs, t0s):
        axis = mesh.axis_names[0]
        f = shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=(P(axis),))
        return f(xs, t0s)
    """
    assert "shard-spec" not in rules_hit(src, SHARD)


# ---- spec-literal-outside-layout ------------------------------------------

def test_spec_literal_call_outside_layout_flagged():
    src = """
    def place(mesh, axis, arr):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(arr, NamedSharding(mesh, PartitionSpec(axis)))
    """
    hit = rules_hit(src, "druid_tpu/parallel/distributed.py")
    assert "spec-literal-outside-layout" in hit


def test_spec_literal_alias_outside_layout_flagged():
    src = """
    from jax.sharding import PartitionSpec as P

    def specs(axis):
        return (P(axis, None), P())
    """
    assert "spec-literal-outside-layout" in rules_hit(src, ENGINE)


def test_spec_literal_attribute_call_flagged():
    src = """
    import jax.sharding

    def spec(axis):
        return jax.sharding.PartitionSpec(axis)
    """
    assert "spec-literal-outside-layout" in rules_hit(src, ENGINE)


def test_spec_literal_inside_layout_module_ok():
    src = """
    from jax.sharding import NamedSharding, PartitionSpec

    def column_rows(axis):
        return PartitionSpec(axis, None)

    def sharding(mesh, spec):
        return NamedSharding(mesh, spec)
    """
    assert "spec-literal-outside-layout" not in rules_hit(src, SHARD)


def test_spec_literal_unrelated_module_clean():
    src = """
    def harmless(xs):
        return [x + 1 for x in xs]
    """
    assert "spec-literal-outside-layout" not in rules_hit(src, ENGINE)


def test_real_tree_spec_literals_only_in_layout():
    """The stock tree constructs partition specs in speclayout.py ONLY —
    the sharded rewrite left no stray literals behind."""
    proc = _run_cli("--fail-on-new", "--no-cache", "--only",
                    "spec-literal-outside-layout,shard-spec")
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---- pallas-accum-dtype: index-map i64 regression -------------

def test_untyped_index_map_constant_flagged():
    """REGRESSION for an on-TPU-only break: the offending kernel
    shape — a BlockSpec index_map returning a bare Python int — promotes
    that constant to i64 under the repo-global x64 flag, and Mosaic fails
    to legalize the lowered `func.return (i32, i64)`. The rule must flag
    exactly this shape so the break dies at lint time, not on the chip."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(num_total):
        BLK, W = plan_window(span)
        R = BLK // 128
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((R, 128), lambda i: (i, 0))],
        )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    matches = [f for f in hits if f.rule == "pallas-accum-dtype"]
    assert matches, "the untyped index-map shape must be flagged"
    assert any("i64" in f.message and "func.return" in f.message
               for f in matches)


def test_typed_index_map_constants_ok():
    """The fixed shape (constants built typed inside the lambda) passes."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(num_total):
        BLK, W = plan_window(span)
        R = BLK // 128
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((R, 128),
                                   lambda i: (i, jnp.int32(0)))],
        )
    """
    assert "pallas-accum-dtype" not in rules_hit(src, PALLAS)


def test_index_map_i64_check_only_in_pallas_modules():
    src = """
    from jax.experimental import pallas as pl
    spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
    """
    assert "pallas-accum-dtype" not in rules_hit(src, ENGINE)


# ---- vmem-budget over the packed-input spec shapes ------------------------

def test_concatenated_and_comprehension_specs_budgeted():
    """The packed-input kernel builds in_specs as `[dense] * n + [packed
    for Rw in packed_rws]` — the vmem rule must see BOTH sides: dense
    multiplicity through len(dense_fields), packed through a synthesized
    len(packed_rws), and the comprehension variable Rw through
    SYMBOL_BOUNDS. Within budget here; no multiplicity complaint."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(span, num_total, dense_fields, packed_rws):
        BLK, W = plan_window(span)
        R = BLK // 128
        return pl.GridSpec(
            grid=(8,),
            in_specs=([pl.BlockSpec((R, 128),
                                    lambda i: (i, jnp.int32(0)))]
                      * (1 + len(dense_fields))
                      + [pl.BlockSpec((Rw, 128),
                                      lambda i: (i, jnp.int32(0)))
                         for Rw in packed_rws]),
        )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert not [f for f in hits if f.rule in ("vmem-budget",
                                              "pallas-tile-shape")], hits


def test_comprehension_specs_count_toward_budget():
    """A comprehension's tiles participate in the worst-case sum: an
    oversized per-entry tile over a bounded iterable must blow the cap."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(packed_rws):
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((32768 * 64, 128),
                                   lambda i: (i, jnp.int32(0)))
                      for Rw in packed_rws],
        )
    """
    assert "vmem-budget" in rules_hit(src, PALLAS)


def test_megakernel_full_program_shape_within_budget():
    """The megakernel's whole in/out spec shape — key tile + (1, 128) mask
    word tile + dense value tiles + packed word tiles + the full accum
    grids — must fit the VMEM budget with every dim statically bounded
    (the gate that made the index-map i64 class unrepeatable covers the new
    kernel too)."""
    src = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from druid_tpu.engine.contracts import MEGA_MASK_VPW

    def build(span, num_total, dense_fields, packed_rws, out_defs):
        BLK, W = plan_window(span)
        R = BLK // 128
        BPW = MEGA_MASK_VPW // R
        G2 = _round_up(num_total, 128) + W
        out_shapes = [jax.ShapeDtypeStruct((G2 // 128, 128), int)
                      for _ in out_defs]
        return pl.GridSpec(
            grid=(8,),
            in_specs=([pl.BlockSpec((R, 128), lambda i: (i, jnp.int32(0)))]
                      + [pl.BlockSpec((1, 128),
                                      lambda i: (i // BPW, jnp.int32(0)))]
                      + [pl.BlockSpec((R, 128),
                                      lambda i: (i, jnp.int32(0)))]
                      * len(dense_fields)
                      + [pl.BlockSpec((Rw, 128),
                                      lambda i: (i, jnp.int32(0)))
                         for Rw in packed_rws]),
            out_specs=[pl.BlockSpec((G2 // 128, 128),
                                    lambda i: (jnp.int32(0), jnp.int32(0)))]
            * len(out_defs),
        ), out_shapes
    """
    hits = check_source(textwrap.dedent(src), MEGA, cfg())
    assert not [f for f in hits if f.rule in ("vmem-budget",
                                              "pallas-tile-shape",
                                              "pallas-accum-dtype")], hits


def test_megakernel_oversized_mask_tile_flagged():
    """A mask word tile scaled past the budget must still blow the cap —
    the (1, 128) tile is a measured bound, not a waiver."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(num_total):
        G2 = _round_up(num_total, 128) + 1024
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((G2 // 128 * 64, 128),
                                   lambda i: (i, jnp.int32(0)))],
        )
    """
    assert "vmem-budget" in rules_hit(src, MEGA)


def test_megakernel_accum_dtype_rules_active():
    """pallas-accum-dtype covers the megakernel module: a drifted identity
    dtype or an untyped index-map constant fails there exactly like in
    pallas_agg."""
    src = """
    import jax.numpy as jnp
    ident = jnp.float32(-(2**31))
    """
    assert "pallas-accum-dtype" in rules_hit(src, MEGA)
    src2 = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
    """
    assert "pallas-accum-dtype" in rules_hit(src2, MEGA)


def test_megakernel_x64_banned_in_kernel_body():
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(ref, out):
        out[:, :] = ref[:, :].astype(jnp.int64)

    def run(x):
        return pl.pallas_call(kernel, out_shape=None)(x)
    """
    hits = check_source(textwrap.dedent(src), MEGA, cfg())
    assert any(f.rule == "pallas-accum-dtype" and "kernel body" in f.message
               for f in hits)


def test_opaque_comprehension_multiplicity_flagged():
    """Iterating anything but a bare name cannot be bounded — the rule
    must complain rather than silently under-count."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(things):
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, jnp.int32(0)))
                      for t in things if t],
        )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert any(f.rule == "vmem-budget" and "multiplicity" in f.message
               for f in hits)


# ---- cascade run tiles (data/cascade.py run metadata) ---------------------

def test_cascade_run_tile_shapes_within_bounds():
    """Run-metadata tiles resolve through the declared run-count/run-length
    SYMBOL_BOUNDS (contracts: n_runs/Rrun ≤ CASCADE_MAX_RUNS, run_len ≤ a
    batched segment): a kernel streaming run values/ends as (Rrun, 128)
    tiles — the full CASCADE_MAX_RUNS table resident at once — passes
    pallas-tile-shape and stays inside the VMEM budget without per-site
    annotations."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(n_runs, Rrun, run_len):
        rpad = _round_up(n_runs, 128)
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((Rrun, 128),
                                   lambda i: (i, jnp.int32(0))),
                      pl.BlockSpec((rpad // 128, 128),
                                   lambda i: (jnp.int32(0), jnp.int32(0))),
                      pl.BlockSpec((max(run_len // 128, 1), 128),
                                   lambda i: (i, jnp.int32(0)))],
        )
    """
    hits = check_source(textwrap.dedent(src), PALLAS, cfg())
    assert not [f for f in hits if f.rule in ("vmem-budget",
                                              "pallas-tile-shape")], hits


def test_cascade_run_tile_oversized_flagged():
    """Scaling a run tile past the contract cap must blow the VMEM budget
    — the n_runs/Rrun bounds are measured contracts, not waivers."""
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def build(Rrun):
        return pl.GridSpec(
            grid=(8,),
            in_specs=[pl.BlockSpec((Rrun * 8192, 128),
                                   lambda i: (i, jnp.int32(0)))],
        )
    """
    assert "vmem-budget" in rules_hit(src, PALLAS)


def test_cascade_unbounded_run_symbol_still_flagged():
    """A run-shaped name OUTSIDE the declared bounds stays unresolvable —
    the bounds cover exactly the contract symbols, nothing else."""
    src = ("from jax.experimental import pallas as pl\n"
           "grid_spec = pl.GridSpec(grid=(8,), in_specs=[" +
           "pl.BlockSpec((mystery_runs, 128), lambda i: (i, 0))])\n")
    assert "pallas-tile-shape" in rules_hit(src, PALLAS)
