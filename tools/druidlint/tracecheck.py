"""tracecheck: shape/dtype/VMEM contract analysis for the engine layer.

An abstract-interpretation pass over `druid_tpu/engine/` that makes the
numeric engine's conventions — `pl.BlockSpec` tile geometry, accumulator
identity dtypes, VMEM residency, AggKernel reduce contracts — mechanically
checked, the way PR 2's druidlint did for the control plane. A kernel edit
that changes a contract now fails the tier-1 lint gate instead of the
on-chip suite.

The contracts live in ONE place: `druid_tpu/engine/contracts.py`, imported
by the engine and loaded (by file path, no package import, no jax) by this
module. Rules here never hard-code a tile constant.

Shape arithmetic like `(R, 128)` and `G2 // 128` is evaluated over an
interval + stride domain (`Sym`): every value carries optional integer
bounds and a known divisor. Module constants resolve through the scanned
module's own assignments and its `contracts` imports (cross-module);
function locals resolve through a forward pass over the function body;
anything unresolvable (results of host planning calls, parameters) falls
back to the bounds `contracts.SYMBOL_BOUNDS` declares — which the engine
enforces at runtime, so the static and dynamic contracts cannot drift.

Rules (all plug into the registry/baseline/suppression/--fail-on-new
machinery from PR 2):
  pallas-tile-shape       block shapes statically resolvable, lane-aligned,
                          index_map arity/rank consistent, out_spec shape
                          textually identical to the out_shape declaration
  pallas-accum-dtype      reduce identity literals carry their contracted
                          dtype; no 64-bit dtype inside a kernel body
  vmem-budget             worst-case sum of declared tile bytes under the
                          configured VMEM cap
  x64-dtype               jnp.int64/float64 in traced device code without
                          an x64 gate (silent truncation under default JAX)
  agg-contract            AggKernel subclasses define the required methods,
                          fold-kind kernels define device_combine,
                          signature() expressions are distinct
  preferred-element-type  device matmuls always pin their accumulator dtype
"""
from __future__ import annotations

import ast
import importlib.util
import math
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.druidlint.core import Finding, ModuleContext, rule
from tools.druidlint.rules import (_FUNC_DEFS, _collect_traced_functions,
                                   _terminal)

# ---- contracts loading ----------------------------------------------------

_CONTRACTS_REL = "druid_tpu/engine/contracts.py"
_CONTRACTS_CACHE: Dict[str, Tuple[float, Dict[str, object]]] = {}


def contracts_path(root: str = ".") -> Optional[Path]:
    """The contracts file a scan of `root` validates against: the root's
    own engine tree when present, else the contracts shipped beside this
    linter (synthetic-violation fixtures have no engine tree). The cache
    signer hashes the same file, so contract edits always invalidate."""
    path = Path(root) / _CONTRACTS_REL
    if not path.is_file():
        path = Path(__file__).resolve().parents[2] / _CONTRACTS_REL
    return path if path.is_file() else None


def load_contracts(root: str = ".") -> Dict[str, object]:
    """Load the engine contract table by file path (no package import — the
    engine package enables x64 and pulls jax on import, which the linter
    must not)."""
    path = contracts_path(root)
    if path is None:
        return {}
    key = str(path.resolve())
    mtime = path.stat().st_mtime_ns
    cached = _CONTRACTS_CACHE.get(key)
    if cached is not None and cached[0] == mtime:
        return cached[1]
    spec = importlib.util.spec_from_file_location("_druidlint_contracts", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    table = {k: v for k, v in vars(mod).items() if not k.startswith("_")}
    _CONTRACTS_CACHE[key] = (mtime, table)
    return table


def _contracts(ctx: ModuleContext) -> Dict[str, object]:
    return load_contracts(getattr(ctx.config, "root", "."))


# ---- the Sym interval + stride domain -------------------------------------

class Sym:
    """An integer abstract value: optional [lo, hi] bounds plus a known
    divisor (`value ≡ 0 (mod mult)`). Exact values have lo == hi."""

    __slots__ = ("lo", "hi", "mult")

    def __init__(self, lo: Optional[int], hi: Optional[int], mult: int = 1):
        self.lo, self.hi = lo, hi
        self.mult = max(1, mult)

    @classmethod
    def exact(cls, v: int) -> "Sym":
        return cls(v, v, abs(v) if v else 1)

    @property
    def value(self) -> Optional[int]:
        return self.lo if self.lo is not None and self.lo == self.hi else None

    def bounded(self) -> bool:
        return self.lo is not None and self.hi is not None

    def multiple_of(self, m: int) -> bool:
        if self.value is not None:
            return self.value % m == 0
        return self.mult % m == 0

    def __repr__(self):  # pragma: no cover - debug aid
        return f"Sym[{self.lo},{self.hi}]%{self.mult}"


def _gcd(a: int, b: int) -> int:
    return math.gcd(a, b)


def _sym_add(a: Sym, b: Sym) -> Sym:
    lo = None if a.lo is None or b.lo is None else a.lo + b.lo
    hi = None if a.hi is None or b.hi is None else a.hi + b.hi
    return Sym(lo, hi, _gcd(a.mult, b.mult))


def _sym_sub(a: Sym, b: Sym) -> Sym:
    lo = None if a.lo is None or b.hi is None else a.lo - b.hi
    hi = None if a.hi is None or b.lo is None else a.hi - b.lo
    return Sym(lo, hi, _gcd(a.mult, b.mult))


def _sym_mul(a: Sym, b: Sym) -> Sym:
    if a.bounded() and b.bounded():
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        return Sym(min(prods), max(prods), a.mult * b.mult)
    return Sym(None, None, a.mult * b.mult)


def _sym_floordiv(a: Sym, b: Sym) -> Optional[Sym]:
    d = b.value
    if d is None or d <= 0:
        return None
    lo = None if a.lo is None else a.lo // d
    hi = None if a.hi is None else a.hi // d
    mult = a.mult // d if a.mult % d == 0 else 1
    return Sym(lo, hi, mult)


def _sym_mod(a: Sym, b: Sym) -> Optional[Sym]:
    d = b.value
    if d is None or d <= 0:
        return None
    if a.value is not None:
        return Sym.exact(a.value % d)
    return Sym(0, d - 1, 1)


def _sym_pow(a: Sym, b: Sym) -> Optional[Sym]:
    if a.value is not None and b.value is not None and b.value >= 0:
        return Sym.exact(a.value ** b.value)
    return None


def _sym_minmax(args: List[Sym], is_max: bool) -> Sym:
    pick = max if is_max else min
    los = [a.lo for a in args]
    his = [a.hi for a in args]
    if is_max:
        # lo of max: the largest known lo; hi of max: needs every hi
        lo = pick([l for l in los if l is not None], default=None)
        hi = None if any(h is None for h in his) else pick(his)
    else:
        lo = None if any(l is None for l in los) else pick(los)
        hi = pick([h for h in his if h is not None], default=None)
    # the result can be ANY argument, so the stride must divide all of them
    mult = args[0].mult
    for a in args[1:]:
        mult = _gcd(mult, a.mult)
    return Sym(lo, hi, mult)


def _round_up_int(x: int, m: int) -> int:
    return -(-x // m) * m


class SymEval:
    """Evaluate an AST expression to a Sym (or a tuple of results for
    ast.Tuple), given an environment of named Syms and the contract table."""

    def __init__(self, env: Dict[str, Sym], contracts: Dict[str, object]):
        self.env = env
        self.contracts = contracts
        self.bounds = contracts.get("SYMBOL_BOUNDS", {}) or {}

    def eval(self, node: ast.AST):
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool):
            return Sym.exact(node.value)
        if isinstance(node, ast.Name):
            s = self.env.get(node.id)
            if s is not None:
                return s
            v = self.contracts.get(node.id)   # bare contract-constant name
            if isinstance(v, int) and not isinstance(v, bool):
                return Sym.exact(v)
            # declared symbol bound: names the function env cannot see
            # (comprehension targets, opaque planning results) resolve
            # through SYMBOL_BOUNDS exactly like parameters do
            b = self.bounds.get(node.id)
            if b:
                return Sym(b[0], b[1], b[2])
            return None
        if isinstance(node, ast.Attribute):
            # contracts.X / any <alias>.X whose terminal names a contract int
            v = self.contracts.get(node.attr)
            if isinstance(v, int) and not isinstance(v, bool):
                return Sym.exact(v)
            return None
        if isinstance(node, ast.Tuple):
            return tuple(self.eval(e) for e in node.elts)
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            s = self.eval(node.operand)
            if isinstance(s, Sym):
                return _sym_sub(Sym.exact(0), s)
            return None
        if isinstance(node, ast.BinOp):
            a, b = self.eval(node.left), self.eval(node.right)
            if not (isinstance(a, Sym) and isinstance(b, Sym)):
                return None
            if isinstance(node.op, ast.Add):
                return _sym_add(a, b)
            if isinstance(node.op, ast.Sub):
                return _sym_sub(a, b)
            if isinstance(node.op, ast.Mult):
                return _sym_mul(a, b)
            if isinstance(node.op, ast.FloorDiv):
                return _sym_floordiv(a, b)
            if isinstance(node.op, ast.Mod):
                return _sym_mod(a, b)
            if isinstance(node.op, ast.Pow):
                return _sym_pow(a, b)
            return None
        if isinstance(node, ast.Call):
            name = _terminal(node.func)
            if name == "len" and len(node.args) == 1 \
                    and isinstance(node.args[0], ast.Name):
                b = self.bounds.get(f"len({node.args[0].id})")
                if b:
                    return Sym(b[0], b[1], b[2])
                return None
            if name in ("max", "min"):
                args = [self.eval(a) for a in node.args]
                if args and all(isinstance(a, Sym) for a in args):
                    return _sym_minmax(args, name == "max")
                return None
            if name in ("_round_up", "round_up") and len(node.args) == 2:
                x, m = self.eval(node.args[0]), self.eval(node.args[1])
                if isinstance(x, Sym) and isinstance(m, Sym) \
                        and m.value and m.value > 0:
                    lo = None if x.lo is None \
                        else _round_up_int(max(x.lo, 0), m.value)
                    hi = None if x.hi is None \
                        else _round_up_int(x.hi, m.value)
                    return Sym(lo, hi, m.value)
                return None
            return None
        return None


def _module_env(ctx: ModuleContext,
                contracts: Dict[str, object]) -> Dict[str, Sym]:
    """Top-level constants: `from ...contracts import X` names resolve
    cross-module against the loaded contract table; plain `NAME = <expr>`
    assignments evaluate in source order."""
    env: Dict[str, Sym] = {}
    ev = SymEval(env, contracts)
    for node in ctx.tree.body:
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.rsplit(".", 1)[-1] == "contracts":
            for alias in node.names:
                v = contracts.get(alias.name)
                if isinstance(v, int) and not isinstance(v, bool):
                    env[alias.asname or alias.name] = Sym.exact(v)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            s = ev.eval(node.value)
            if isinstance(s, Sym):
                env[node.targets[0].id] = s
    return env


def _function_env(ctx: ModuleContext, fn: Optional[ast.AST],
                  contracts: Dict[str, object],
                  module_env: Dict[str, Sym]) -> Dict[str, Sym]:
    """Forward pass over a function body: parameters and unresolvable
    assignments (host planning calls, array attributes) fall back to the
    declared SYMBOL_BOUNDS; everything else evaluates symbolically."""
    env = dict(module_env)
    bounds = contracts.get("SYMBOL_BOUNDS", {}) or {}

    def bound_sym(name: str) -> Optional[Sym]:
        b = bounds.get(name)
        return Sym(b[0], b[1], b[2]) if b else None

    if fn is None:
        return env
    for a in list(getattr(fn.args, "args", [])) + \
            list(getattr(fn.args, "kwonlyargs", [])):
        s = bound_sym(a.arg)
        if s is not None:
            env[a.arg] = s
    ev = SymEval(env, contracts)
    assigns = [n for n in ast.walk(fn) if isinstance(n, ast.Assign)]
    for node in sorted(assigns, key=lambda n: (n.lineno, n.col_offset)):
        if len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if isinstance(tgt, ast.Name):
            s = ev.eval(node.value)
            if not isinstance(s, Sym):
                s = bound_sym(tgt.id)
            if isinstance(s, Sym):
                env[tgt.id] = s
        elif isinstance(tgt, ast.Tuple) \
                and all(isinstance(e, ast.Name) for e in tgt.elts):
            val = ev.eval(node.value)
            if isinstance(val, tuple) and len(val) == len(tgt.elts) \
                    and all(isinstance(v, Sym) for v in val):
                for e, v in zip(tgt.elts, val):
                    env[e.id] = v
            else:
                for e in tgt.elts:
                    s = bound_sym(e.id)
                    if s is not None:
                        env[e.id] = s
    return env


# ---- shared AST helpers ---------------------------------------------------

def _dump(node: ast.AST) -> str:
    return ast.dump(node, annotate_fields=False)


def _call_kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _block_shape(call: ast.Call) -> Optional[ast.AST]:
    if call.args:
        return call.args[0]
    return _call_kw(call, "block_shape")


def _index_map(call: ast.Call) -> Optional[ast.AST]:
    if len(call.args) > 1:
        return call.args[1]
    return _call_kw(call, "index_map")


def _spec_entries(node: ast.AST) -> List[Tuple[ast.Call, Optional[ast.AST]]]:
    """Flatten an in_specs/out_specs expression to (BlockSpec call,
    multiplicity expr or None) pairs. Handles `[spec, ...]`,
    `[spec] * expr`, list concatenation (`A + B`), a comprehension over a
    named iterable (multiplicity = a synthesized `len(<name>)`, resolved
    via SYMBOL_BOUNDS), and a bare spec."""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        return _spec_entries(node.left) + _spec_entries(node.right)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
        lst, mult = node.left, node.right
        if not isinstance(lst, (ast.List, ast.Tuple)):
            lst, mult = node.right, node.left
        if isinstance(lst, (ast.List, ast.Tuple)):
            return [(c, mult) for c, _ in _spec_entries(lst)]
        return []
    if isinstance(node, (ast.ListComp, ast.GeneratorExp)):
        entries = _spec_entries(node.elt)
        if not entries:
            return []
        mult: ast.AST
        if len(node.generators) == 1 \
                and isinstance(node.generators[0].iter, ast.Name) \
                and not node.generators[0].ifs:
            # multiplicity = len(<iterable>) — SymEval resolves it through
            # the declared SYMBOL_BOUNDS ("len(packed_rws)" style keys)
            mult = ast.copy_location(
                ast.Call(func=ast.Name(id="len", ctx=ast.Load()),
                         args=[node.generators[0].iter], keywords=[]),
                node)
        else:
            # filtered / nested / opaque iteration: force the vmem rule's
            # "multiplicity not statically bounded" finding rather than
            # silently under-counting
            mult = ast.copy_location(
                ast.Name(id="__unbounded_spec_multiplicity__",
                         ctx=ast.Load()), node)
        return [(c, mult) for c, _ in entries]
    if isinstance(node, (ast.List, ast.Tuple)):
        out = []
        for el in node.elts:
            out.extend(_spec_entries(el))
        return out
    if isinstance(node, ast.Call) and _terminal(node.func) == "BlockSpec":
        return [(node, None)]
    return []


def _enclosing_grid(ctx: ModuleContext, node: ast.AST) -> Optional[ast.AST]:
    """The `grid=` tuple of the GridSpec/pallas_call the node sits inside."""
    cur = ctx.parent(node)
    while cur is not None:
        if isinstance(cur, ast.Call) \
                and _terminal(cur.func) in ("GridSpec", "pallas_call"):
            g = _call_kw(cur, "grid")
            if g is not None:
                return g
        cur = ctx.parent(cur)
    return None


def _kernel_functions(ctx: ModuleContext) -> List[ast.AST]:
    """Function defs passed by name as the first argument to pallas_call —
    their bodies run on-chip under Mosaic's lowering rules."""
    defs_by_name: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, _FUNC_DEFS):
            defs_by_name.setdefault(node.name, []).append(node)
    out: List[ast.AST] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) \
                and _terminal(node.func) == "pallas_call" and node.args \
                and isinstance(node.args[0], ast.Name):
            out.extend(defs_by_name.get(node.args[0].id, []))
    return out


# ---- pallas-tile-shape ----------------------------------------------------

@rule("pallas-tile-shape", "error",
      "pl.BlockSpec tile geometry violates the engine contract")
def check_pallas_tile_shape(ctx: ModuleContext) -> Iterable[Finding]:
    """Every `pl.BlockSpec` in the pallas modules (config `pallas-modules`)
    must declare a block shape the abstract interpreter can bound, with a
    last dim that is a multiple of contracts.LANE (Mosaic tiles are
    (sublane, 128); an unaligned last dim fails on-chip, not at trace
    time). The index_map lambda's arity must match the grid rank, its
    returned tuple the block rank, and out_specs' shapes must stay
    textually identical to the out_shape ShapeDtypeStruct declaration."""
    if not ctx.path_matches(ctx.config.pallas_modules):
        return
    contracts = _contracts(ctx)
    lane = contracts.get("LANE", 128)
    module_env = _module_env(ctx, contracts)
    fn_envs: Dict[Optional[ast.AST], Dict[str, Sym]] = {}

    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _terminal(node.func) == "BlockSpec"):
            continue
        shape = _block_shape(node)
        if shape is None:
            continue                      # memory_space-only spec: whole ref
        if not isinstance(shape, ast.Tuple):
            yield ctx.finding(node, "BlockSpec block shape is not a static "
                                    "tuple — the tile geometry must be "
                                    "resolvable without running the engine")
            continue
        fn = ctx.enclosing_function(node)
        if fn not in fn_envs:
            fn_envs[fn] = _function_env(ctx, fn, contracts, module_env)
        ev = SymEval(fn_envs[fn], contracts)
        dims = [ev.eval(e) for e in shape.elts]
        bad = [i for i, s in enumerate(dims)
               if not (isinstance(s, Sym) and s.bounded())]
        if bad:
            yield ctx.finding(
                shape, f"block shape dim(s) {bad} not statically resolvable "
                       f"— declare the bound in contracts.SYMBOL_BOUNDS or "
                       f"use contract constants")
        elif dims and not dims[-1].multiple_of(lane):
            yield ctx.finding(
                shape, f"block shape last dim is not a multiple of the "
                       f"{lane}-lane tile width (Mosaic lowers (sublane, "
                       f"{lane}) tiles; this fails on-chip only)")
        imap = _index_map(node)
        if isinstance(imap, ast.Lambda):
            grid = _enclosing_grid(ctx, node)
            if isinstance(grid, ast.Tuple):
                nargs = len(imap.args.args)
                if nargs != len(grid.elts):
                    yield ctx.finding(
                        imap, f"index_map takes {nargs} arg(s) but the grid "
                              f"has rank {len(grid.elts)}")
            if isinstance(imap.body, ast.Tuple) \
                    and len(imap.body.elts) != len(shape.elts):
                yield ctx.finding(
                    imap, f"index_map returns {len(imap.body.elts)} "
                          f"coordinate(s) for a rank-{len(shape.elts)} "
                          f"block shape")

    # out_specs shape ↔ out_shape ShapeDtypeStruct shape: the kernel writes
    # orefs[j][:, :] assuming they agree; a drift reshapes the accumulator
    # grid silently. The contract is textual identity of the shape exprs.
    for fn in [n for n in ast.walk(ctx.tree) if isinstance(n, _FUNC_DEFS)]:
        out_spec_shapes: Set[str] = set()
        out_shape_shapes: Set[str] = set()
        anchor = None
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if _terminal(node.func) in ("GridSpec", "pallas_call"):
                specs = _call_kw(node, "out_specs")
                if specs is not None:
                    for call, _ in _spec_entries(specs):
                        sh = _block_shape(call)
                        if sh is not None:
                            out_spec_shapes.add(_dump(sh))
                            anchor = anchor or call
            elif _terminal(node.func) == "ShapeDtypeStruct" and node.args:
                out_shape_shapes.add(_dump(node.args[0]))
        if len(out_spec_shapes) == 1 and len(out_shape_shapes) == 1 \
                and out_spec_shapes != out_shape_shapes:
            yield ctx.finding(
                anchor, "out_specs block shape differs from the out_shape "
                        "ShapeDtypeStruct shape — the full-grid accumulator "
                        "contract requires them textually identical")


# ---- pallas-accum-dtype ---------------------------------------------------

_INF_NAMES = {"inf", "infty", "Inf", "Infinity"}


def _literal_value(node: ast.AST):
    """Evaluate a pure-literal arithmetic expression (ints, floats, ±inf
    spelled jnp.inf / np.inf / math.inf / float('inf'))."""
    if isinstance(node, ast.Constant) \
            and isinstance(node.value, (int, float)) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.Attribute) and node.attr in _INF_NAMES:
        return float("inf")
    if isinstance(node, ast.Name) and node.id in _INF_NAMES:
        return float("inf")
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _literal_value(node.operand)
        return None if v is None else -v
    if isinstance(node, ast.BinOp):
        a, b = _literal_value(node.left), _literal_value(node.right)
        if a is None or b is None:
            return None
        try:
            if isinstance(node.op, ast.Add):
                return a + b
            if isinstance(node.op, ast.Sub):
                return a - b
            if isinstance(node.op, ast.Mult):
                return a * b
            if isinstance(node.op, ast.Pow):
                return a ** b
            if isinstance(node.op, ast.FloorDiv):
                return a // b
        except (ZeroDivisionError, OverflowError, ValueError):
            return None
        return None
    if isinstance(node, ast.Call) and _terminal(node.func) == "float" \
            and len(node.args) == 1 \
            and isinstance(node.args[0], ast.Constant) \
            and isinstance(node.args[0].value, str):
        try:
            return float(node.args[0].value)
        except ValueError:
            return None
    return None


@rule("pallas-accum-dtype", "error",
      "accumulator identity literal carries the wrong dtype, or a 64-bit "
      "dtype appears inside a kernel body")
def check_pallas_accum_dtype(ctx: ModuleContext) -> Iterable[Finding]:
    """In pallas modules, every dtype constructor applied to a reduce
    identity literal must use the dtype contracts.REDUCE_IDENTITIES maps it
    to — `jnp.int32(2**31 - 1)` for the int-min identity, `jnp.float32(inf)`
    for the float-min identity, and so on; a drifted identity dtype poisons
    the whole accumulator grid. 64-bit dtypes are banned inside kernel
    bodies outright (Mosaic cannot lower them on these chips): the
    `astype(jnp.int64)` widenings belong outside the kernel."""
    if not ctx.path_matches(ctx.config.pallas_modules):
        return
    contracts = _contracts(ctx)
    identities = contracts.get("REDUCE_IDENTITIES", {}) or {}
    dtype_names = set(contracts.get("DTYPE_BYTES", {}) or ())
    x64 = set(contracts.get("X64_DTYPES", ()) or ())

    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in dtype_names
                and len(node.args) == 1 and not node.keywords):
            continue
        v = _literal_value(node.args[0])
        if v is None or v not in identities:
            continue
        want = identities[v]
        if node.func.attr != want:
            yield ctx.finding(
                node, f"reduce identity {ast.unparse(node.args[0])} must be "
                      f"constructed as {want} (got {node.func.attr}) — a "
                      f"mismatched identity dtype corrupts every group's "
                      f"accumulator")

    seen: Set[Tuple[int, int]] = set()
    for fn in _kernel_functions(ctx):
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and node.attr in x64:
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                yield ctx.finding(
                    node, f"{node.attr} inside the kernel body of "
                          f"{getattr(fn, 'name', '<kernel>')}() — Mosaic "
                          f"cannot lower 64-bit element types; widen "
                          f"outside the kernel (lo/hi limbs inside)")

    # index-map i64 regression class: a BlockSpec index_map returning a BARE
    # Python int promotes to i64 under the repo-global x64 flag, and Mosaic
    # fails to legalize the lowered index map's mixed `func.return
    # (i32, i64)` — an on-TPU-only compile failure the CPU interpreter
    # never sees. Constants in index maps must be built typed inside the
    # lambda (jnp.int32(0)).
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _terminal(node.func) == "BlockSpec"):
            continue
        imap = _index_map(node)
        if not isinstance(imap, ast.Lambda):
            continue
        rets = imap.body.elts if isinstance(imap.body, ast.Tuple) \
            else [imap.body]
        for r in rets:
            if isinstance(r, ast.Constant) and isinstance(r.value, int) \
                    and not isinstance(r.value, bool):
                yield ctx.finding(
                    r, f"untyped int constant {r.value} in a BlockSpec "
                       f"index_map — promotes to i64 under x64 and Mosaic "
                       f"fails to legalize the (i32, i64) func.return "
                       f"(an on-TPU-only break); build it typed "
                       f"inside the lambda: jnp.int32({r.value})")


# ---- vmem-budget ----------------------------------------------------------

@rule("vmem-budget", "error",
      "declared pallas tiles exceed the VMEM budget")
def check_vmem_budget(ctx: ModuleContext) -> Iterable[Finding]:
    """The worst-case sum of BlockSpec tile bytes (upper bounds of the
    symbolic shapes × spec multiplicity × the widest kernel element type)
    must stay under the configured cap (`[tool.druidlint] vmem-cap-bytes`,
    default contracts.VMEM_BUDGET_BYTES): the kernel keeps every declared
    tile resident, so a shape/cap drift that compiles fine on the
    interpreter OOMs VMEM on-chip."""
    if not ctx.path_matches(ctx.config.pallas_modules):
        return
    contracts = _contracts(ctx)
    cap = int(getattr(ctx.config, "vmem_cap_bytes", 0) or 0) \
        or contracts.get("VMEM_BUDGET_BYTES", 12 * 1024 * 1024)
    elem_bytes = contracts.get("PALLAS_MAX_TILE_DTYPE_BYTES", 4)
    module_env = _module_env(ctx, contracts)

    for call in ast.walk(ctx.tree):
        if not (isinstance(call, ast.Call)
                and _terminal(call.func) in ("GridSpec", "pallas_call")):
            continue
        entries: List[Tuple[ast.Call, Optional[ast.AST]]] = []
        for kw_name in ("in_specs", "out_specs"):
            specs = _call_kw(call, kw_name)
            if specs is not None:
                entries.extend(_spec_entries(specs))
        if not entries:
            continue
        fn = ctx.enclosing_function(call)
        ev = SymEval(_function_env(ctx, fn, contracts, module_env),
                     contracts)
        total = 0
        for spec_call, mult_expr in entries:
            shape = _block_shape(spec_call)
            if not isinstance(shape, ast.Tuple):
                continue
            dims = [ev.eval(e) for e in shape.elts]
            if not all(isinstance(s, Sym) and s.bounded() for s in dims):
                continue                # pallas-tile-shape reports these
            cells = 1
            for s in dims:
                cells *= max(s.hi, 0)
            mult = 1
            if mult_expr is not None:
                m = ev.eval(mult_expr)
                if not (isinstance(m, Sym) and m.hi is not None):
                    yield ctx.finding(
                        mult_expr, "spec-list multiplicity not statically "
                                   "bounded — the VMEM budget cannot be "
                                   "checked; bound it via "
                                   "contracts.SYMBOL_BOUNDS")
                    mult = 0
                else:
                    mult = max(m.hi, 0)
            total += cells * mult * elem_bytes
        if total > cap:
            yield ctx.finding(
                call, f"declared tiles need up to {total} bytes of VMEM, "
                      f"over the {cap}-byte budget — shrink the window/"
                      f"group caps in contracts.py or raise vmem-cap-bytes "
                      f"deliberately")


# ---- x64-dtype ------------------------------------------------------------

_X64_GATES = {"x64_enabled", "jax_enable_x64"}
_X64_MODULES = {"jnp", "jax", "np", "numpy", "onp"}


@rule("x64-dtype", "error",
      "64-bit dtype in traced device code without an x64 gate")
def check_x64_dtype(ctx: ModuleContext) -> Iterable[Finding]:
    """Inside traced device code (config `device-modules`; kernel bodies
    passed to pallas_call count), `jnp.int64` / `jnp.float64` silently
    produce 32-bit arrays when JAX's x64 flag is off — a truncation that
    corrupts long sums near 2**31 without any error. Either gate the dtype
    choice on `jax.config.jax_enable_x64` (reading the flag anywhere in the
    function counts as the gate) or suppress with a rationale where the
    engine's global x64 enablement makes the wide dtype load-bearing."""
    if not ctx.path_matches(ctx.config.device_modules):
        return
    contracts = _contracts(ctx)
    x64 = set(contracts.get("X64_DTYPES", ("int64", "uint64", "float64")))
    traced = _collect_traced_functions(ctx, frozenset({"pallas_call"}))
    seen: Set[Tuple[int, int]] = set()
    for fn in traced:
        gated = any(
            (isinstance(n, ast.Attribute) and n.attr in _X64_GATES)
            or (isinstance(n, ast.Name) and n.id in _X64_GATES)
            for n in ast.walk(fn))
        if gated:
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and node.attr in x64 \
                    and _terminal(node.value) in _X64_MODULES:
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                yield ctx.finding(
                    node, f"{_terminal(node.value)}.{node.attr} in traced "
                          f"function {getattr(fn, 'name', '<fn>')}() — "
                          f"silently 32-bit when x64 is off; gate on "
                          f"jax.config.jax_enable_x64 or widen on host")


# ---- agg-contract ---------------------------------------------------------

@rule("agg-contract", "error",
      "AggKernel subclass violates the reduce contract")
def check_agg_contract(ctx: ModuleContext) -> Iterable[Finding]:
    """In kernel modules (config `kernel-modules`), every AggKernel
    subclass must define the contracts.AGG_REQUIRED_METHODS
    (signature/update/combine/empty_state); classes whose effective
    reduce_kind is "fold" (the base default — unless the class or an
    in-module ancestor overrides it, or __init__ assigns it dynamically)
    must define device_combine, because the sharded merge folds states
    pairwise on device. signature() return expressions must be distinct
    across kernels in a module: the jit caches key on them, and two kernels
    sharing a signature silently share compiled programs."""
    if not ctx.path_matches(ctx.config.kernel_modules):
        return
    contracts = _contracts(ctx)
    required = contracts.get(
        "AGG_REQUIRED_METHODS",
        ("signature", "update", "combine", "empty_state"))
    fold_required = contracts.get("AGG_FOLD_REQUIRED", ("device_combine",))

    classes: Dict[str, ast.ClassDef] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ClassDef):
            classes[node.name] = node

    def chain(cls: ast.ClassDef) -> List[ast.ClassDef]:
        """cls plus in-module ancestors, base-class AggKernel excluded."""
        out, todo, seen = [], [cls.name], set()
        while todo:
            name = todo.pop()
            if name in seen or name == "AggKernel":
                continue
            seen.add(name)
            c = classes.get(name)
            if c is None:
                continue
            out.append(c)
            todo.extend(_terminal(b) for b in c.bases)
        return out

    def derives_agg(cls: ast.ClassDef) -> bool:
        todo = [_terminal(b) for b in cls.bases]
        seen = set()
        while todo:
            name = todo.pop()
            if name == "AggKernel":
                return True
            if name in seen:
                continue
            seen.add(name)
            c = classes.get(name)
            if c is not None:
                todo.extend(_terminal(b) for b in c.bases)
        return False

    sig_exprs: Dict[str, List[Tuple[str, ast.AST]]] = {}
    for cls in classes.values():
        if cls.name == "AggKernel" or not derives_agg(cls):
            continue
        ch = chain(cls)
        methods: Dict[str, ast.AST] = {}
        class_rk: Optional[str] = None
        init_assigns_rk = False
        for c in ch:                     # cls first: nearest wins
            for item in c.body:
                if isinstance(item, _FUNC_DEFS):
                    methods.setdefault(item.name, item)
                elif isinstance(item, ast.Assign):
                    for t in item.targets:
                        if isinstance(t, ast.Name) \
                                and t.id == "reduce_kind" \
                                and class_rk is None \
                                and isinstance(item.value, ast.Constant):
                            class_rk = item.value.value
        init = methods.get("__init__")
        if init is not None:
            for node in ast.walk(init):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Attribute) \
                                and t.attr == "reduce_kind":
                            init_assigns_rk = True
        missing = [m for m in required if m not in methods]
        if missing:
            yield ctx.finding(
                cls, f"AggKernel subclass {cls.name} missing required "
                     f"method(s): {', '.join(missing)}")
        if not init_assigns_rk and (class_rk or "fold") == "fold":
            fold_missing = [m for m in fold_required if m not in methods]
            if fold_missing:
                yield ctx.finding(
                    cls, f"{cls.name} has reduce_kind \"fold\" (the base "
                         f"default) but defines no "
                         f"{', '.join(fold_missing)} — the sharded merge "
                         f"all_gathers and folds states pairwise on device")
        sig = methods.get("signature")
        if sig is not None and sig in cls.body:   # defined here, not inherited
            rets = [n.value for n in ast.walk(sig)
                    if isinstance(n, ast.Return) and n.value is not None]
            if rets:
                key = "|".join(_dump(r) for r in rets)
                sig_exprs.setdefault(key, []).append((cls.name, sig))
    for key, owners in sig_exprs.items():
        if len(owners) > 1:
            names = ", ".join(n for n, _ in owners)
            for _, sig in owners[1:]:
                yield ctx.finding(
                    sig, f"signature() return expression duplicated across "
                         f"kernels ({names}) — the jit caches key on it, "
                         f"so these kernels would share compiled programs")


# ---- preferred-element-type -----------------------------------------------

_MATMUL_CALLS = {"dot_general", "dot", "matmul", "einsum", "tensordot"}
_DEVICE_NS = {"lax", "jnp"}


@rule("preferred-element-type", "error",
      "device matmul without preferred_element_type")
def check_preferred_element_type(ctx: ModuleContext) -> Iterable[Finding]:
    """`lax.dot_general` / `jnp.matmul`-family calls in device modules must
    pass `preferred_element_type`: without it the MXU accumulates int8
    products in int8 (wrapping) and bf16 products in bf16 (losing the exact
    f32 accumulation the mm path's error analysis assumes)."""
    if not ctx.path_matches(ctx.config.device_modules):
        return
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _MATMUL_CALLS
                and _terminal(node.func.value) in _DEVICE_NS):
            continue
        if not any(kw.arg == "preferred_element_type"
                   for kw in node.keywords):
            yield ctx.finding(
                node, f"{_terminal(node.func.value)}.{node.func.attr}() "
                      f"without preferred_element_type — the MXU "
                      f"accumulator dtype must be pinned (int32 for int8 "
                      f"rows, float32 for bf16 rows)")


# ---- shard-spec -----------------------------------------------------------

def _partition_spec_names(ctx: ModuleContext) -> Set[str]:
    """Local names PartitionSpec is importable under (incl. aliases)."""
    names = {"PartitionSpec"}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name == "PartitionSpec":
                    names.add(alias.asname or alias.name)
    return names


def _mesh_axis_sources(ctx: ModuleContext) -> Tuple[Set[str], Set[str]]:
    """(variable names bound from <mesh>.axis_names[...], literal axis
    strings declared by Mesh(...) constructions) — the two ways a module
    can legitimately name a mesh axis."""
    axis_vars: Set[str] = set()
    axis_literals: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Subscript) \
                and isinstance(node.value.value, ast.Attribute) \
                and node.value.value.attr == "axis_names":
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    axis_vars.add(tgt.id)
        if isinstance(node, ast.Call) and _terminal(node.func) == "Mesh":
            cands = list(node.args[1:2]) + [
                kw.value for kw in node.keywords if kw.arg == "axis_names"]
            for cand in cands:
                if isinstance(cand, (ast.Tuple, ast.List)):
                    for e in cand.elts:
                        if isinstance(e, ast.Constant) and isinstance(e.value, str):
                            axis_literals.add(e.value)
    return axis_vars, axis_literals


def _own_returns(fn: ast.AST) -> List[ast.Return]:
    """Return statements belonging to `fn` itself (nested defs/lambdas have
    their own returns and must not count)."""
    out: List[ast.Return] = []

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FUNC_DEFS + (ast.Lambda,)):
                continue
            if isinstance(child, ast.Return):
                out.append(child)
            walk(child)

    walk(fn)
    return out


@rule("shard-spec", "error",
      "shard_map partition specs disagree with the mesh or body")
def check_shard_spec(ctx: ModuleContext) -> Iterable[Finding]:
    """In sharding modules (config `shard-modules`), every `shard_map`
    call's partition specs must agree with its body and its mesh:
    `in_specs` tuples need one entry per body positional parameter,
    `out_specs` tuples one entry per element of the body's returned tuple,
    and every PartitionSpec axis argument must be derived from the mesh —
    a name bound from mesh.axis_names[...] or a literal axis a Mesh(...)
    construction in the module declares. A resharding edit that breaks any
    of these otherwise surfaces in the multichip suite (or as a silent
    replication of what should be sharded), not at lint time."""
    if not ctx.path_matches(ctx.config.shard_modules):
        return
    p_names = _partition_spec_names(ctx)
    axis_vars, axis_literals = _mesh_axis_sources(ctx)

    defs_by_name: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, _FUNC_DEFS):
            defs_by_name.setdefault(node.name, []).append(node)

    def spec_entries(node) -> Optional[List[ast.AST]]:
        return list(node.elts) if isinstance(node, (ast.Tuple, ast.List)) \
            else None

    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _terminal(node.func) == "shard_map"):
            continue
        body = None
        if node.args and isinstance(node.args[0], ast.Name):
            cands = defs_by_name.get(node.args[0].id, [])
            body = cands[0] if len(cands) == 1 else None
        in_specs = _call_kw(node, "in_specs")
        out_specs = _call_kw(node, "out_specs")

        # arity: in_specs entries ↔ body positional parameters (defaulted
        # params are optional, so any count in [required, total] is valid)
        if body is not None and in_specs is not None:
            entries = spec_entries(in_specs)
            n_pos = len(getattr(body.args, "posonlyargs", [])) \
                + len(body.args.args)
            n_required = n_pos - len(body.args.defaults)
            if entries is not None and body.args.vararg is None \
                    and not (n_required <= len(entries) <= n_pos):
                yield ctx.finding(
                    in_specs, f"in_specs has {len(entries)} spec(s) but "
                              f"body {body.name}() takes {n_required}"
                              f"{f'-{n_pos}' if n_pos != n_required else ''} "
                              f"positional parameter(s)")

        # arity: out_specs entries ↔ body return tuple
        if body is not None and out_specs is not None:
            entries = spec_entries(out_specs)
            if entries is not None:
                ret_lens = set()
                resolvable = True
                for ret in _own_returns(body):
                    if isinstance(ret.value, ast.Tuple):
                        ret_lens.add(len(ret.value.elts))
                    else:
                        resolvable = False
                if resolvable and len(ret_lens) == 1 \
                        and ret_lens != {len(entries)}:
                    yield ctx.finding(
                        out_specs, f"out_specs has {len(entries)} spec(s) "
                                   f"but body {body.name}() returns a "
                                   f"{ret_lens.pop()}-tuple")

        # axis provenance: every PartitionSpec argument must trace to the
        # mesh. Skip when the module declares no axis source at all (a
        # fixture or a mesh passed opaquely) — no false positives.
        if not axis_vars and not axis_literals:
            continue
        for spec_src in (in_specs, out_specs):
            if spec_src is None:
                continue
            for sub in ast.walk(spec_src):
                if not (isinstance(sub, ast.Call)
                        and _terminal(sub.func) in p_names):
                    continue
                for arg in sub.args:
                    if isinstance(arg, ast.Constant) and arg.value is None:
                        continue
                    if isinstance(arg, ast.Name) and arg.id in axis_vars:
                        continue
                    if isinstance(arg, ast.Constant) \
                            and isinstance(arg.value, str) \
                            and arg.value in axis_literals:
                        continue
                    yield ctx.finding(
                        arg, f"PartitionSpec axis {ast.dump(arg) if not isinstance(arg, ast.Constant) else arg.value!r} "
                             f"is not derived from the mesh (bind it from "
                             f"mesh.axis_names[...] or declare it in the "
                             f"Mesh construction)")


_SPEC_CTORS = ("PartitionSpec", "NamedSharding")


def _sharding_ctor_names(ctx: ModuleContext) -> Set[str]:
    """Local names PartitionSpec/NamedSharding are importable under
    (aliases included) — the constructors the layout module monopolizes."""
    names = set(_SPEC_CTORS)
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name in _SPEC_CTORS:
                    names.add(alias.asname or alias.name)
    return names


@rule("spec-literal-outside-layout", "error",
      "PartitionSpec/NamedSharding constructed outside the layout module")
def check_spec_literal_outside_layout(ctx: ModuleContext) -> Iterable[Finding]:
    """Everywhere except the canonical layout module (config
    `shard-modules` — parallel/speclayout.py), constructing a
    PartitionSpec or NamedSharding (or importing one, which is how the
    literal would sneak in) is a finding. The SpecLayout is the ONE source
    of partition specs: a hand-rolled spec at a call site is exactly the
    per-site drift the layout module exists to make impossible — it would
    compile, shard wrong (or silently replicate), and only surface in the
    multichip suite."""
    if ctx.path_matches(ctx.config.shard_modules):
        return
    names = _sharding_ctor_names(ctx)
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name in _SPEC_CTORS:
                    yield ctx.finding(
                        node, f"import of {alias.name} outside the layout "
                              f"module — ask the canonical SpecLayout "
                              f"(parallel/speclayout.py) for specs/"
                              f"shardings instead")
        elif isinstance(node, ast.Call) and _terminal(node.func) in names:
            yield ctx.finding(
                node, f"{_terminal(node.func)}(...) constructed outside "
                      f"the layout module — every partition spec must come "
                      f"from the canonical SpecLayout "
                      f"(parallel/speclayout.py)")
