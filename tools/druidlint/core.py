"""druidlint core: rule registry, config, suppressions, baseline, runner.

Design notes:
  * Findings key on (rule, path, line) — the same identity scheme the
    baseline file uses, so `--fail-on-new` is a set difference.
  * Suppression is per physical line: a `# druidlint: disable=<rule>[,..]`
    comment on the line a finding anchors to silences it (`disable=all`
    silences every rule on that line). Suppressions are for invariant-
    preserving exceptions the rule cannot see (e.g. an availability probe
    that must never raise); anything else belongs in the baseline or gets
    fixed.
  * Config comes from pyproject.toml [tool.druidlint]; the container's
    Python (3.10) predates tomllib, so a minimal single-table parser
    handles the subset this project writes (strings, string arrays, ints,
    bools). Unknown keys are rejected loudly — a typoed option silently
    disabling a rule would defeat the gate.
"""
from __future__ import annotations

import ast
import fnmatch
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Set

SEVERITIES = ("error", "warning")

_SUPPRESS_RE = re.compile(r"#\s*druidlint:\s*disable=([A-Za-z0-9_,\- ]+)")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative, posix separators
    line: int
    col: int
    message: str
    severity: str

    @property
    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.line}"

    def format(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"[{self.severity}] {self.rule}: {self.message}")

    def to_json(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message}


@dataclass
class Rule:
    name: str
    severity: str
    description: str
    check: Callable[["ModuleContext"], Iterable[Finding]]


_RULES: Dict[str, Rule] = {}


def rule(name: str, severity: str, description: str):
    """Register a rule. The decorated function receives a ModuleContext and
    yields Findings (built via ctx.finding)."""
    if severity not in SEVERITIES:
        raise ValueError(f"unknown severity {severity!r} for rule {name!r}")

    def deco(fn):
        _RULES[name] = Rule(name, severity, fn.__doc__ or description, fn)
        return fn
    return deco


def registered_rules() -> Dict[str, Rule]:
    from tools.druidlint import rules as _rules  # noqa: F401 (registration)
    from tools.druidlint import tracecheck as _tracecheck  # noqa: F401
    from tools.druidlint import raceguard as _raceguard  # noqa: F401
    from tools.druidlint import leakguard as _leakguard  # noqa: F401
    from tools.druidlint import keyguard as _keyguard  # noqa: F401
    from tools.druidlint import stallguard as _stallguard  # noqa: F401
    from tools.druidlint import donorguard as _donorguard  # noqa: F401
    return dict(_RULES)


#: analyzer family of a rule, derived from the registering module — the
#: unified `--all` runner groups findings and timings by this
_FAMILIES = {"rules": "druidlint", "tracecheck": "tracecheck",
             "raceguard": "raceguard", "leakguard": "leakguard",
             "keyguard": "keyguard", "stallguard": "stallguard",
             "donorguard": "donorguard"}


def family_of(r: Rule) -> str:
    mod = getattr(r.check, "__module__", "") or ""
    return _FAMILIES.get(mod.rsplit(".", 1)[-1], "druidlint")


# ---- configuration -------------------------------------------------------

_DEFAULT_CONFIG = {
    "include": ["druid_tpu", "tools", "__graft_entry__.py"],
    "exclude": ["**/__pycache__/**", "*.pyc"],
    "rules": [],                        # empty = all registered rules
    "baseline": "tools/druidlint/baseline.json",
    # unfenced-metadata-write: leader-duty modules whose MetadataStore
    # mutations must thread a fencing term
    "duty-modules": ["druid_tpu/cluster/coordinator.py",
                     "druid_tpu/indexing/overlord.py"],
    # no-executable-deserialization + wire-decoded-rows: modules that face
    # the wire / carry the compressed data path end to end
    "wire-modules": ["druid_tpu/cluster/wire.py",
                     "druid_tpu/cluster/cache.py",
                     "druid_tpu/server/*",
                     "druid_tpu/storage/format_v2.py"],
    # host-device-sync: modules whose traced functions are device code
    "device-modules": ["druid_tpu/engine/*", "druid_tpu/parallel/*"],
    # lock-scope: modules exempted because the lock EXISTS to serialize the
    # blocking resource (metadata.py's lock guards its one sqlite conn)
    "lock-scope-exclude": ["druid_tpu/cluster/metadata.py"],
    # tracecheck: modules holding pallas kernels (tile/accum/vmem rules)
    "pallas-modules": ["druid_tpu/engine/pallas_agg.py",
                       "druid_tpu/engine/megakernel.py"],
    # tracecheck: modules defining AggKernel subclasses (agg-contract)
    "kernel-modules": ["druid_tpu/engine/kernels.py", "druid_tpu/ext/*"],
    # tracecheck: the canonical sharding-layout module(s) — shard_map
    # partition specs are checked against mesh construction + body arity
    # (shard-spec) there, and PartitionSpec/NamedSharding literals
    # anywhere ELSE are findings (spec-literal-outside-layout)
    "shard-modules": ["druid_tpu/parallel/speclayout.py"],
    # tracecheck: VMEM tile budget in bytes; 0 = contracts.VMEM_BUDGET_BYTES
    "vmem-cap-bytes": 0,
    # unbounded-retry: data-plane modules whose catch-and-retry loops
    # must consult a Deadline or attempt bound
    "retry-modules": ["druid_tpu/cluster/*", "druid_tpu/server/*"],
    # raceguard: the whole-program concurrency-analysis member set — every
    # module whose locks/threads/shared state enter the shared index
    "raceguard-modules": ["druid_tpu/*"],
    # raceguard: thread roots the AST cannot see, as "path-glob::qual-glob"
    # (e.g. "druid_tpu/*::*.do_monitor" — monitor ticks run on the
    # MonitorScheduler thread but are dispatched through a list the binder
    # cannot type)
    "extra-thread-roots": [],
    # raceguard: declared order edges ("lockid -> lockid") for acquisition
    # paths through OPAQUE callbacks the binder cannot enumerate (a
    # handoff lambda announcing to the view under the driver lock); they
    # join the static order graph, so they participate in cycle detection
    # and explain dynamic-witness observations
    "raceguard-assume-edges": [],
    # metric-name: modules whose emitter.metric("...") literals must be
    # declared in the metrics catalog
    "metric-modules": ["druid_tpu/*"],
    # metric-name: the single-source metrics catalog (METRICS dict literal)
    "metrics-catalog": "druid_tpu/obs/catalog.py",
    # flag-name: modules whose literal DRUID_TPU_* env reads must name a
    # flag declared in the flags catalog
    "flag-modules": ["druid_tpu/*"],
    # flag-name + keyguard env-flag-latch: the single-source flags
    # catalog (FLAGS dict literal of Flag(...) declarations)
    "flags-catalog": "druid_tpu/config/flags.py",
    # keyguard env-flag-latch: plan/build modules where a DRUID_TPU_*
    # read must match its declared latch/live semantics
    "keyguard-plan-modules": ["druid_tpu/engine/*", "druid_tpu/data/*",
                              "druid_tpu/parallel/*"],
    # keyguard unkeyed-trace-input: canonical key-derivation functions
    # ("path::qual"); every parameter must flow into the returned key
    "keyguard-key-fns": ["druid_tpu/engine/grouping.py::_structure_sig",
                         "druid_tpu/parallel/distributed.py::_sharded_sig",
                         "druid_tpu/parallel/speclayout.py::layout_sig",
                         "druid_tpu/engine/filters.py::bitmap_pool_key",
                         "druid_tpu/cluster/cache.py::query_cache_key",
                         "druid_tpu/cluster/cache.py::result_level_key",
                         "druid_tpu/data/cascade.py::plan_pair"],
    # keyguard impure-eligibility: eligibility/planning predicates
    # ("path::qual") that must stay pure functions of descriptors
    "keyguard-eligibility": ["druid_tpu/engine/standing.py::check_eligible",
                             "druid_tpu/data/cascade.py::plan_columns",
                             "druid_tpu/data/cascade.py::plan_pair",
                             "druid_tpu/data/cascade.py::run_domain_probe",
                             "druid_tpu/data/packed.py::plan_columns",
                             "druid_tpu/cluster/view.py::*.fusable"],
    # stallguard: request-path entry points the handler heuristic cannot
    # see, as "path-glob::qual-glob" — functions that run ON a request
    # thread (the long-poll hub entry, the scheduler admission gate);
    # everything they reach through the call graph inherits the
    # request-path park rules
    "stallguard-request-roots": [],
    # donorguard donate-platform-gate: the blessed platform predicates
    # ("path-glob::qual-glob") — the ONE donation gate plus the pallas
    # availability probe; a backend/platform comparison anywhere else is
    # a scattered donation-enable decision (the CPU-segfault class)
    "donorguard-platform-gate": [
        "druid_tpu/engine/contracts.py::donation_supported",
        "druid_tpu/engine/pallas_agg.py::backend_ok"],
    # unused-suppression audit (CLI --report-unused-suppressions)
    "report-unused-suppressions": False,
}


@dataclass
class LintConfig:
    include: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["include"]))
    exclude: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["exclude"]))
    rules: List[str] = field(default_factory=list)
    baseline: str = _DEFAULT_CONFIG["baseline"]
    duty_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["duty-modules"]))
    wire_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["wire-modules"]))
    device_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["device-modules"]))
    lock_scope_exclude: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["lock-scope-exclude"]))
    pallas_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["pallas-modules"]))
    kernel_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["kernel-modules"]))
    shard_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["shard-modules"]))
    vmem_cap_bytes: int = 0
    retry_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["retry-modules"]))
    raceguard_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["raceguard-modules"]))
    extra_thread_roots: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["extra-thread-roots"]))
    raceguard_assume_edges: List[str] = field(
        default_factory=lambda: list(
            _DEFAULT_CONFIG["raceguard-assume-edges"]))
    metric_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["metric-modules"]))
    metrics_catalog: str = _DEFAULT_CONFIG["metrics-catalog"]
    flag_modules: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["flag-modules"]))
    flags_catalog: str = _DEFAULT_CONFIG["flags-catalog"]
    keyguard_plan_modules: List[str] = field(
        default_factory=lambda: list(
            _DEFAULT_CONFIG["keyguard-plan-modules"]))
    keyguard_key_fns: List[str] = field(
        default_factory=lambda: list(_DEFAULT_CONFIG["keyguard-key-fns"]))
    keyguard_eligibility: List[str] = field(
        default_factory=lambda: list(
            _DEFAULT_CONFIG["keyguard-eligibility"]))
    stallguard_request_roots: List[str] = field(
        default_factory=lambda: list(
            _DEFAULT_CONFIG["stallguard-request-roots"]))
    donorguard_platform_gate: List[str] = field(
        default_factory=lambda: list(
            _DEFAULT_CONFIG["donorguard-platform-gate"]))
    report_unused_suppressions: bool = False
    #: scan root; tracecheck resolves druid_tpu/engine/contracts.py here
    #: (set by load_config/lint_paths, not a pyproject key)
    root: str = "."

    def enabled_rules(self) -> Dict[str, Rule]:
        all_rules = registered_rules()
        if not self.rules:
            return all_rules
        unknown = set(self.rules) - set(all_rules)
        if unknown:
            raise ValueError(f"unknown rules in config: {sorted(unknown)}")
        return {n: r for n, r in all_rules.items() if n in self.rules}


def _parse_toml_value(raw: str):
    raw = raw.strip()
    if raw in ("true", "false"):
        return raw == "true"
    try:
        return ast.literal_eval(raw)
    except (ValueError, SyntaxError):
        raise ValueError(f"unsupported TOML value for druidlint: {raw!r}")


def _read_druidlint_table(pyproject: Path) -> Dict[str, object]:
    """Minimal parser for the [tool.druidlint] table (no tomllib on 3.10):
    key = <string | int | bool | [string, ...]>, arrays may span lines."""
    out: Dict[str, object] = {}
    if not pyproject.exists():
        return out
    in_table = False
    pending_key, pending_val = None, ""
    header = re.compile(r"^\[([^\]]+)\]\s*(#.*)?$")
    for line in pyproject.read_text().splitlines():
        stripped = line.strip()
        m = header.match(stripped)
        if m:
            in_table = m.group(1).strip() == "tool.druidlint"
            continue
        if not in_table or not stripped or stripped.startswith("#"):
            continue
        if pending_key is not None:
            pending_val += " " + stripped
            if stripped.endswith("]"):
                out[pending_key] = _parse_toml_value(pending_val)
                pending_key, pending_val = None, ""
            continue
        key, _, val = line.partition("=")
        key, val = key.strip(), val.strip()
        if val.startswith("[") and not val.endswith("]"):
            pending_key, pending_val = key, val
            continue
        out[key] = _parse_toml_value(val)
    if pending_key is not None:
        raise ValueError(f"unterminated array for [tool.druidlint] "
                         f"key {pending_key!r}")
    return out


def load_config(root: Path) -> LintConfig:
    table = _read_druidlint_table(root / "pyproject.toml")
    cfg = LintConfig()
    known = {k.replace("_", "-") for k in vars(cfg)} - {"root"}
    unknown = set(table) - known
    if unknown:
        raise ValueError(f"unknown [tool.druidlint] keys: {sorted(unknown)}")
    for key, val in table.items():
        setattr(cfg, key.replace("-", "_"), val)
    cfg.root = str(root)
    return cfg


# ---- per-module context ---------------------------------------------------

class ModuleContext:
    """Everything a rule needs about one module: path, AST (with parent
    links), source lines, config."""

    def __init__(self, path: str, source: str, config: LintConfig):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.config = config
        self.tree = ast.parse(source, filename=path)
        self._parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent
        self._rule: Optional[Rule] = None

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = self.parent(cur)
        return None

    def path_matches(self, patterns: List[str]) -> bool:
        return any(fnmatch.fnmatch(self.path, pat) or self.path == pat
                   for pat in patterns)

    def finding(self, node: ast.AST, message: str) -> Finding:
        assert self._rule is not None
        return Finding(self._rule.name, self.path,
                       getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0) + 1,
                       message, self._rule.severity)


def _suppressions(lines: List[str]) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(line)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def check_source(source: str, path: str,
                 config: Optional[LintConfig] = None) -> List[Finding]:
    """Lint one module given as a string — the unit-test entry point."""
    config = config or LintConfig()
    ctx = ModuleContext(path, source, config)
    suppressed = _suppressions(ctx.lines)
    used: Set[tuple] = set()            # (line, rule-or-"all") that matched
    findings: List[Finding] = []
    enabled = config.enabled_rules()
    for r in enabled.values():
        ctx._rule = r
        for f in r.check(ctx):
            lines_rules = suppressed.get(f.line, ())
            if "all" in lines_rules:
                used.add((f.line, "all"))
                continue
            if f.rule in lines_rules:
                used.add((f.line, f.rule))
                continue
            findings.append(f)
    if config.report_unused_suppressions and "unused-suppression" in enabled:
        sev = enabled["unused-suppression"].severity
        all_rules = set(registered_rules())
        for line, names in sorted(suppressed.items()):
            if "unused-suppression" in names:
                continue            # the audit's own pragma silences it
            for name in sorted(names):
                if (line, name) in used:
                    continue
                if name == "all":
                    # only auditable when every rule ran this pass
                    if config.rules:
                        continue
                    msg = ("disable=all suppresses no finding on this "
                           "line — remove the dead pragma")
                elif name not in all_rules:
                    msg = (f"disable={name} names no registered rule — "
                           f"a typoed pragma suppresses nothing")
                elif name not in enabled:
                    continue        # rule not run: usage unknowable
                else:
                    msg = (f"disable={name} suppresses no finding on "
                           f"this line — remove the dead pragma")
                findings.append(Finding("unused-suppression", path, line,
                                        1, msg, sev))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


# ---- file collection + runner --------------------------------------------

def _excluded(rel: str, config: LintConfig) -> bool:
    return any(fnmatch.fnmatch(rel, pat) for pat in config.exclude)


def collect_files(root: Path, config: LintConfig,
                  paths: Optional[List[str]] = None) -> List[Path]:
    roots = paths if paths else config.include
    out: List[Path] = []
    seen: Set[Path] = set()
    for entry in roots:
        p = (root / entry) if not Path(entry).is_absolute() else Path(entry)
        if p.is_dir():
            candidates: Iterator[Path] = sorted(p.rglob("*.py"))
        elif p.suffix == ".py":
            candidates = iter([p])
        else:
            continue
        for c in candidates:
            try:
                rel = c.relative_to(root).as_posix()
            except ValueError:
                # outside the root (scratch file): rules keyed on repo
                # paths simply won't match it
                rel = c.as_posix()
            if c in seen or _excluded(rel, config):
                continue
            seen.add(c)
            out.append(c)
    return out


def _cache_meta_sig(root: Path, config: LintConfig) -> str:
    """Identity of everything findings depend on besides the scanned file:
    the analyzer sources (rules + core + tracecheck), the engine contracts
    module, the effective config — and the raceguard PROGRAM signature
    (every member module's mtime/size): raceguard findings in module B can
    change when module A changes, so any edit inside the program set must
    drop every per-file cache entry, not just the edited file's."""
    from tools.druidlint.tracecheck import contracts_path  # lazy: no cycle
    from tools.druidlint.raceguard import program_sig  # lazy: no cycle
    # private attrs are per-run caches (raceguard memoizes its program on
    # the config), not finding-relevant identity
    parts = [repr(sorted((k, v) for k, v in vars(config).items()
                         if not k.startswith("_"))),
             program_sig(root, config)]
    tool_files = sorted(Path(__file__).parent.glob("*.py"))
    contracts = contracts_path(str(root))
    if contracts is not None:
        tool_files.append(contracts)
    for p in tool_files:
        try:
            st = p.stat()
            parts.append(f"{p.name}:{st.st_mtime_ns}:{st.st_size}")
        except OSError:
            parts.append(f"{p.name}:gone")
    return "|".join(parts)


def _finding_from_cache(entry: dict) -> Finding:
    return Finding(entry["rule"], entry["path"], entry["line"],
                   entry["col"], entry["message"], entry["severity"])


def _finding_to_cache(f: Finding) -> dict:
    return {"rule": f.rule, "path": f.path, "line": f.line, "col": f.col,
            "message": f.message, "severity": f.severity}


def lint_paths(root: Path, config: Optional[LintConfig] = None,
               paths: Optional[List[str]] = None,
               cache_path: Optional[Path] = None) -> List[Finding]:
    """Lint the tree. With `cache_path`, per-file findings are reused when
    the file's (mtime, size) and the analyzer/config identity are unchanged
    — the full-tree scan stays inside the tier-1 time budget even with the
    symbolic-shape rules enabled. Rules are strictly per-module, so file
    identity is a sound cache key."""
    config = config or load_config(root)
    config.root = str(root)
    cache: Dict[str, dict] = {}
    meta_sig = None
    if cache_path is not None:
        meta_sig = _cache_meta_sig(root, config)
        try:
            data = json.loads(cache_path.read_text())
            if data.get("version") == 1 and data.get("meta") == meta_sig:
                cache = data.get("files", {})
        except (OSError, ValueError):
            cache = {}
    out_files: Dict[str, dict] = {}
    findings: List[Finding] = []
    for f in collect_files(root, config, paths):
        try:
            rel = f.relative_to(root).as_posix()
        except ValueError:
            rel = f.as_posix()
        try:
            st = f.stat()
            key = f"{st.st_mtime_ns}:{st.st_size}"
        except OSError:
            key = "gone"
        hit = cache.get(rel)
        if hit is not None and hit.get("key") == key:
            file_findings = [_finding_from_cache(e)
                             for e in hit["findings"]]
            findings.extend(file_findings)
            out_files[rel] = hit
            continue
        try:
            source = f.read_text()
        except (OSError, UnicodeDecodeError):
            continue
        try:
            file_findings = check_source(source, rel, config)
        except SyntaxError as e:
            file_findings = [Finding("syntax-error", rel, e.lineno or 1,
                                     (e.offset or 0) + 1, str(e.msg),
                                     "error")]
        findings.extend(file_findings)
        out_files[rel] = {"key": key,
                          "findings": [_finding_to_cache(x)
                                       for x in file_findings]}
    if cache_path is not None:
        # merge over the loaded cache: a restricted-path scan must not
        # truncate the full tree's entries (stale files re-key on read;
        # deleted files linger harmlessly until the next meta change)
        cache.update(out_files)
        try:
            cache_path.write_text(json.dumps(
                {"version": 1, "meta": meta_sig, "files": cache}))
        except OSError:
            pass                      # cache is best-effort, never fatal
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


# ---- baseline -------------------------------------------------------------

def load_baseline(path: Path) -> Dict[str, dict]:
    if not path.exists():
        return {}
    data = json.loads(path.read_text())
    out = {}
    for entry in data.get("findings", []):
        key = f"{entry['rule']}:{entry['path']}:{entry['line']}"
        out[key] = entry
    return out


def save_baseline(path: Path, findings: List[Finding]) -> None:
    data = {"version": 1,
            "findings": [f.to_json() for f in findings]}
    path.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")


def split_by_baseline(findings: List[Finding], baseline: Dict[str, dict]):
    """Returns (new, grandfathered, stale-baseline-keys)."""
    new = [f for f in findings if f.key not in baseline]
    old = [f for f in findings if f.key in baseline]
    stale = sorted(set(baseline) - {f.key for f in findings})
    return new, old, stale
