"""stallguard: whole-program deadline-propagation analysis — no
request-path thread may park forever.

The sixth analyzer family, riding raceguard's shared program index (same
module set, binder, call graph, thread roots, cache signature). Where
raceguard proves lock discipline and leakguard proves resource lifecycle,
stallguard proves DEADLINE discipline: every blocking primitive
(`Condition.wait`, `Event.wait`, `Lock.acquire`, `Queue.get`,
`future.result`, `thread.join`, `proc.wait`, `urlopen`/socket connect,
`time.sleep`) is discovered and classified by the thread class that
reaches it — request path (HTTP handler / configured request roots such
as the broker scatter and the long-poll hub), thread-root loop, or
shutdown path — and five rules enforce that a budget admitted at the
HTTP edge actually bounds every park under it:

  unbounded-blocking-call   request-path park with no timeout argument
                            and no enclosing bounded-retry loop
  deadline-not-propagated   a function holding a deadline/timeout/budget
                            parameter parks without threading the
                            remaining budget into the park
  unclamped-external-timeout a wire/context/user-supplied timeout reaches
                            a park (or bounds a park loop) without a
                            clamp (min / MAX_* / Deadline.clamp) — the
                            PR 14 `timeoutMs=inf` long-poll bug,
                            generalized
  sleep-on-request-path     fixed time.sleep serving a request must be
                            deadline-guarded and jittered
                            (decorrelated_jitter)
  stop-signal-coverage      every `while True` in a thread root must
                            consult its stop event/flag each iteration —
                            the graceful-shutdown dual of leakguard's
                            unjoined-thread

The dynamic peer is tools/druidlint/stallwitness.py: it times real parks
at druid_tpu call sites suite-wide (DRUID_TPU_STALL_WITNESS=1) and fails
the session on any untimed park outside a shutdown scope — observed
parks must be a subset of the statically-predicted bounded sites.

Like keyguard, findings are memoized on the Program PER config key:
the request-root list is config, not program state.
"""
from __future__ import annotations

import ast
import fnmatch
import re
from types import SimpleNamespace
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.druidlint.core import Finding, ModuleContext, rule
from tools.druidlint.leakguard import ENTRY_METHODS  # noqa: F401 (witness)
from tools.druidlint.raceguard import FuncInfo, Program, Site, _own
from tools.druidlint.rules import (_DEADLINE_CONSULTS, _FUNC_DEFS,
                                   _deadline_names, _loop_bounded,
                                   _terminal)

# ---------------------------------------------------------------------------
# blocking-primitive discovery
# ---------------------------------------------------------------------------

#: keyword names a park accepts its bound under
_TIMEOUT_KWS = ("timeout", "timeout_s", "timeout_ms", "timeout_sec")

#: parameter names that carry a remaining budget into a function
_BUDGET_PARAM = re.compile(r"deadline|timeout|budget")

#: substrings marking a name as a stop signal (self._stopping,
#: self._shutdown, stop_event, closed, cancelled, ...)
_STOPISH = ("stop", "shutdown", "shutting", "halt", "exit", "quit",
            "teardown", "closed", "closing", "cancel", "abort")


def _is_none(e: Optional[ast.AST]) -> bool:
    return isinstance(e, ast.Constant) and e.value is None


def _all_args(fn: ast.AST) -> List[ast.arg]:
    a = fn.args
    return list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)


def _classify_park(call: ast.Call) -> Optional[Tuple[str,
                                                     Optional[ast.AST],
                                                     bool]]:
    """(kind, timeout_expr, bounded) for a blocking-primitive call, else
    None. Purely syntactic (terminal attribute + argument shape): inside
    the druid_tpu program set these terminals overwhelmingly ARE the
    threading/queue/subprocess/socket primitives, and the rules that
    consume this are path-classified, so a stray same-named method on a
    non-primitive costs one suppression, not soundness."""
    f = call.func
    t = _terminal(f)
    kws = {k.arg: k.value for k in call.keywords if k.arg}
    tkw = next((kws[k] for k in _TIMEOUT_KWS if k in kws), None)
    if t in ("wait", "wait_futures"):
        if isinstance(f, ast.Attribute):
            # Condition/Event/Popen .wait([timeout])
            expr = call.args[0] if call.args else tkw
            return ("wait", expr, expr is not None and not _is_none(expr))
        if isinstance(f, ast.Name) and (call.args or tkw is not None):
            # concurrent.futures.wait(fs, timeout=...) or an alias of it
            expr = call.args[1] if len(call.args) > 1 else tkw
            return ("wait", expr, expr is not None and not _is_none(expr))
        return None
    if t == "acquire" and isinstance(f, ast.Attribute):
        blocking = kws.get("blocking",
                           call.args[0] if call.args else None)
        expr = call.args[1] if len(call.args) > 1 else tkw
        bounded = (expr is not None and not _is_none(expr)) or \
            (isinstance(blocking, ast.Constant) and blocking.value is False)
        return ("acquire", expr, bounded)
    if t == "get" and isinstance(f, ast.Attribute):
        recv = _terminal(f.value).lower()
        if not (recv in ("q", "inbox") or recv.endswith("_q")
                or "queue" in recv):
            return None                   # dict.get, not Queue.get
        block = kws.get("block", call.args[0] if call.args else None)
        expr = call.args[1] if len(call.args) > 1 else tkw
        bounded = (expr is not None and not _is_none(expr)) or \
            (isinstance(block, ast.Constant) and block.value is False)
        return ("queue-get", expr, bounded)
    if t == "result" and isinstance(f, ast.Attribute):
        expr = call.args[0] if call.args else tkw
        return ("future-result", expr,
                expr is not None and not _is_none(expr))
    if t == "join" and isinstance(f, ast.Attribute):
        if isinstance(f.value, ast.Constant):
            return None                   # ", ".join(parts)
        expr = call.args[0] if call.args else tkw
        if expr is None and (call.args or call.keywords):
            return None                   # non-thread join shape
        return ("join", expr, expr is not None and not _is_none(expr))
    if t == "urlopen":
        return ("urlopen", tkw, tkw is not None and not _is_none(tkw))
    if t == "create_connection":
        expr = call.args[1] if len(call.args) > 1 else tkw
        return ("connect", expr, expr is not None and not _is_none(expr))
    if t == "sleep":
        expr = call.args[0] if call.args else tkw
        return ("sleep", expr, True)      # bounded by its own argument
    return None


def _own_sorted(fi: FuncInfo) -> List[ast.AST]:
    return sorted((n for n in _own(fi) if hasattr(n, "lineno")),
                  key=lambda n: (n.lineno, n.col_offset))


def _parents_of(fi: FuncInfo) -> Dict[ast.AST, ast.AST]:
    """Child → parent over fi's own scope (nested def/class bodies are
    separate FuncInfos and excluded, mirroring _own)."""
    out: Dict[ast.AST, ast.AST] = {}
    stack = [fi.node]
    while stack:
        node = stack.pop()
        if node is not fi.node and isinstance(
                node, _FUNC_DEFS + (ast.ClassDef,)):
            continue
        for child in ast.iter_child_nodes(node):
            out[child] = node
            stack.append(child)
    return out


def _enclosing_loops(parents: Dict[ast.AST, ast.AST],
                     node: ast.AST) -> Iterable[ast.AST]:
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.For, ast.While)):
            yield cur
        cur = parents.get(cur)


def _mentions(node: ast.AST, names: Set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in names
               for n in ast.walk(node))


def _call_args_mention(call: ast.Call, names: Set[str]) -> bool:
    return any(_mentions(a, names) for a in call.args) or \
        any(_mentions(k.value, names) for k in call.keywords)


def _consults_names(loop: ast.AST, names: Set[str]) -> bool:
    """The loop re-checks one of `names` as a budget: a Deadline-style
    consult call on it, or a comparison involving it."""
    for n in ast.walk(loop):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr in _DEADLINE_CONSULTS \
                and _terminal(n.func.value) in names:
            return True
        if isinstance(n, ast.Compare) and _mentions(n, names):
            return True
    return False


# ---------------------------------------------------------------------------
# thread-class classification over the shared program index
# ---------------------------------------------------------------------------

def _match_fid(fid: str, entries: List[str]) -> bool:
    path, _, qual = fid.partition("::")
    for e in entries:
        ep, _, eq = e.partition("::")
        if fnmatch.fnmatch(path, ep) and fnmatch.fnmatch(qual, eq):
            return True
    return False


def _request_fids(prog: Program, config) -> Dict[str, str]:
    """func_id → human-readable origin, for every function reachable from
    an HTTP handler root or a configured request root
    (`stallguard-request-roots`), following the binder's call edges."""
    seeds: Dict[str, str] = {}
    for fid, kind in prog.roots.items():
        if kind == "handler":
            seeds[fid] = f"HTTP handler {fid.partition('::')[2]}"
    roots_cfg = list(getattr(config, "stallguard_request_roots", []) or [])
    for fid in prog.funcs:
        if _match_fid(fid, roots_cfg):
            seeds.setdefault(
                fid, f"request root {fid.partition('::')[2]}")
    out = dict(seeds)
    work = list(seeds)
    while work:
        fid = work.pop()
        fi = prog.funcs.get(fid)
        if fi is None:
            continue
        for callee, _held, _site, _recv in fi.calls:
            if callee not in out and callee in prog.funcs:
                out[callee] = out[fid]
                work.append(callee)
    return out


def _thread_root_fids(prog: Program, config) -> List[str]:
    """Thread-root entry functions whose duty loops must stay
    stop-responsive: Thread targets plus configured extra roots, minus
    anything declared a REQUEST root (a long-poll entry point runs on a
    handler thread; its loop is bounded by the poll deadline, not a stop
    flag)."""
    roots_cfg = list(getattr(config, "stallguard_request_roots", []) or [])
    return [fid for fid, kind in prog.roots.items()
            if kind in ("thread", "extra")
            and not _match_fid(fid, roots_cfg)]


# ---------------------------------------------------------------------------
# the five checks
# ---------------------------------------------------------------------------

def _check_unbounded(prog: Program, config, add,
                     request: Dict[str, str]) -> None:
    for fid, origin in request.items():
        fi = prog.funcs.get(fid)
        if fi is None or not isinstance(fi.node, _FUNC_DEFS):
            continue
        dl_names = _deadline_names(fi.node)
        parents = _parents_of(fi)
        for node in _own(fi):
            if not isinstance(node, ast.Call):
                continue
            park = _classify_park(node)
            if park is None:
                continue
            kind, _expr, bounded = park
            if bounded or kind == "sleep":
                continue
            if any(_loop_bounded(lp, dl_names)
                   for lp in _enclosing_loops(parents, node)):
                continue                  # bounded-retry / deadline loop
            add("unbounded-blocking-call",
                Site(fi.path, node.lineno, node.col_offset),
                f"{kind} parks with no timeout on the request path "
                f"(reachable from {origin}) — pass a bound "
                f"(deadline.clamp(...)) or move the park off the "
                f"request path")


def _check_propagation(prog: Program, config, add) -> None:
    for fid, fi in prog.funcs.items():
        fn = fi.node
        if not isinstance(fn, _FUNC_DEFS):
            continue
        dl_names = _deadline_names(fn)
        params = {a.arg for a in _all_args(fn)
                  if a.arg not in ("self", "cls")
                  and not a.arg.startswith("_")
                  and (_BUDGET_PARAM.search(a.arg.lower())
                       or a.arg in dl_names)}
        if not params:
            continue
        derived = set(params)
        own = _own(fi)
        changed = True
        while changed:                    # forward dataflow to a fixpoint
            changed = False
            for node in own:
                if isinstance(node, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign)):
                    value = node.value
                    if value is None or not _mentions(value, derived):
                        continue
                    targets = node.targets \
                        if isinstance(node, ast.Assign) else [node.target]
                    for t in targets:
                        if isinstance(t, ast.Name) and t.id not in derived:
                            derived.add(t.id)
                            changed = True
        parents = _parents_of(fi)
        for node in own:
            if not isinstance(node, ast.Call):
                continue
            park = _classify_park(node)
            if park is None or park[0] == "sleep":
                continue
            if _call_args_mention(node, derived):
                continue                  # budget threaded into the park
            if any(_consults_names(lp, derived)
                   for lp in _enclosing_loops(parents, node)):
                continue                  # poll quantum + budget re-check
            add("deadline-not-propagated",
                Site(fi.path, node.lineno, node.col_offset),
                f"{fi.qual} receives a budget ({', '.join(sorted(params))})"
                f" but this {park[0]} ignores it — bound the park with the"
                f" remaining budget (deadline.clamp(...)) or re-check the"
                f" deadline in the enclosing loop")


def _expr_clamped(e: ast.AST, raw: Set[str]) -> bool:
    """The expression's value is bounded independently of any raw
    external timeout: a constant, a clamped local, min()/Deadline.clamp()
    with at least one bounded argument, or a MAX_*-style ceiling."""
    if isinstance(e, ast.Constant):
        return isinstance(e.value, (int, float))
    if isinstance(e, ast.Name):
        return e.id not in raw
    if isinstance(e, ast.Attribute):
        return True                       # self.MAX_..., module constant
    if isinstance(e, ast.Call):
        t = _terminal(e.func)
        if t == "min" or (isinstance(e.func, ast.Attribute)
                          and e.func.attr == "clamp"):
            return any(_expr_clamped(a, raw) for a in e.args)
        return False
    if isinstance(e, ast.BinOp):
        return _expr_clamped(e.left, raw) and _expr_clamped(e.right, raw)
    return False


def _check_unclamped(prog: Program, config, add,
                     request: Dict[str, str]) -> None:
    for fid, origin in request.items():
        fi = prog.funcs.get(fid)
        if fi is None or not isinstance(fi.node, _FUNC_DEFS):
            continue
        fn = fi.node
        params = {a.arg for a in _all_args(fn)
                  if a.arg not in ("self", "cls")
                  and "timeout" in a.arg.lower()}
        if not params:
            continue
        raw = set(params)
        for node in _own_sorted(fi):
            if isinstance(node, ast.Assign):
                names = {t.id for t in node.targets
                         if isinstance(t, ast.Name)}
                if not names:
                    continue
                if not _mentions(node.value, raw):
                    raw -= names          # rebound from something else
                elif _expr_clamped(node.value, raw):
                    raw -= names          # timeout_s = min(timeout_s, MAX)
                else:
                    raw |= names          # deadline = Deadline(timeout_ms)
            elif isinstance(node, (ast.While, ast.For)):
                # a park loop whose bound is the raw external value parks
                # (in quanta or in one go) for as long as the wire asked
                has_park = any(isinstance(n, ast.Call)
                               and _classify_park(n) is not None
                               for n in ast.walk(node))
                if has_park and _consults_names(node, raw):
                    add("unclamped-external-timeout",
                        Site(fi.path, node.lineno, node.col_offset),
                        f"loop in {fi.qual} parks under an unclamped "
                        f"external timeout ({', '.join(sorted(params))}) "
                        f"— clamp it (min(..., MAX_*) / Deadline.clamp) "
                        f"before it bounds a request-path park")
            elif isinstance(node, ast.Call):
                park = _classify_park(node)
                if park is None:
                    continue
                _kind, expr, _b = park
                if expr is not None and _mentions(expr, raw) \
                        and not _expr_clamped(expr, raw):
                    add("unclamped-external-timeout",
                        Site(fi.path, node.lineno, node.col_offset),
                        f"external timeout ({', '.join(sorted(params))}) "
                        f"reaches this {_kind} unclamped — a wire value "
                        f"of inf parks the handler thread forever; clamp "
                        f"with min(..., MAX_*) or Deadline.clamp")


def _check_sleep(prog: Program, config, add,
                 request: Dict[str, str]) -> None:
    for fid, origin in request.items():
        fi = prog.funcs.get(fid)
        if fi is None or not isinstance(fi.node, _FUNC_DEFS):
            continue
        own = _own(fi)
        jittered: Set[str] = set()
        for node in own:
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and "jitter" in _terminal(node.value.func).lower():
                jittered |= {t.id for t in node.targets
                             if isinstance(t, ast.Name)}
        dl_names = _deadline_names(fi.node) | \
            {n.id for n in ast.walk(fi.node)
             if isinstance(n, ast.Name) and "deadline" in n.id.lower()}
        guarded = any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr in _DEADLINE_CONSULTS
            and (_terminal(n.func.value) in dl_names
                 or "deadline" in _terminal(n.func.value).lower())
            for fnode in own for n in ast.walk(fnode))
        for node in own:
            if not isinstance(node, ast.Call):
                continue
            park = _classify_park(node)
            if park is None or park[0] != "sleep":
                continue
            expr = park[1]
            jitter_ok = expr is not None and (
                _mentions(expr, jittered)
                or (isinstance(expr, ast.Call)
                    and "jitter" in _terminal(expr.func).lower()))
            if jitter_ok and guarded:
                continue
            add("sleep-on-request-path",
                Site(fi.path, node.lineno, node.col_offset),
                f"fixed sleep on the request path (reachable from "
                f"{origin}) — derive the pause from decorrelated_jitter "
                f"and guard it with the remaining deadline, or use a "
                f"stop-responsive wait")


def _consults_stop(loop: ast.AST) -> bool:
    for n in ast.walk(loop):
        name = n.attr if isinstance(n, ast.Attribute) \
            else n.id if isinstance(n, ast.Name) else None
        if name and any(k in name.lstrip("_").lower() for k in _STOPISH):
            return True
    return False


def _check_stop_coverage(prog: Program, config, add,
                         thread_roots: List[str]) -> None:
    for fid in thread_roots:
        fi = prog.funcs.get(fid)
        if fi is None or not isinstance(fi.node, _FUNC_DEFS):
            continue
        for node in _own(fi):
            if not isinstance(node, ast.While):
                continue
            test = node.test
            infinite = isinstance(test, ast.Constant) and bool(test.value)
            if not infinite or _loop_bounded(node):
                continue
            if _consults_stop(node):
                continue
            add("stop-signal-coverage",
                Site(fi.path, node.lineno, node.col_offset),
                f"infinite loop in thread root {fi.qual} never consults "
                f"a stop signal — check a stop event/flag each iteration "
                f"so shutdown can end the thread")


# ---------------------------------------------------------------------------
# findings assembly + rule shims (leakguard's structure, keyguard's
# config-keyed memo: the request-root list is config, not program state)
# ---------------------------------------------------------------------------

def _config_key(config) -> tuple:
    return (tuple(getattr(config, "stallguard_request_roots", []) or []),
            tuple(config.raceguard_modules))


def stall_findings(prog: Program, config) \
        -> Dict[str, Dict[str, List[Tuple]]]:
    key = _config_key(config)
    got = getattr(prog, "_stall_findings", None)
    if got is not None and got[0] == key:
        return got[1]
    findings: Dict[str, Dict[str, List[Tuple]]] = {}

    def add(rule_name: str, site: Site, message: str) -> None:
        findings.setdefault(rule_name, {}).setdefault(
            site.path, []).append((site.line, site.col, message))

    request = _request_fids(prog, config)
    _check_unbounded(prog, config, add, request)
    _check_propagation(prog, config, add)
    _check_unclamped(prog, config, add, request)
    _check_sleep(prog, config, add, request)
    _check_stop_coverage(prog, config, add,
                         _thread_root_fids(prog, config))
    prog._stall_findings = (key, findings)
    return findings


def _program_for(ctx: ModuleContext) -> Program:
    from tools.druidlint.raceguard import _program_for as rg_program
    return rg_program(ctx)


def _emit(ctx: ModuleContext, rule_name: str) -> Iterable[Finding]:
    if not ctx.path_matches(ctx.config.raceguard_modules):
        return
    prog = _program_for(ctx)
    data = stall_findings(prog, ctx.config)
    for line, col, message in sorted(
            data.get(rule_name, {}).get(ctx.path, ())):
        yield ctx.finding(SimpleNamespace(lineno=line, col_offset=col),
                          message)


@rule("unbounded-blocking-call", "error",
      "request-path blocking call with no timeout and no bounded loop")
def check_unbounded_blocking_call(ctx: ModuleContext) -> Iterable[Finding]:
    """A blocking primitive (wait/acquire/Queue.get/result/join/urlopen/
    connect) reachable from an HTTP handler or a configured request root
    (`stallguard-request-roots`) parks with no timeout argument and no
    enclosing bounded-retry loop. One such park is one handler thread
    gone for as long as the peer cares to stall. Bound the park with the query's
    remaining budget (`deadline.clamp(...)`) or take a rationale
    suppression for parks that provably complete (e.g. `.result()` on an
    already-done future)."""
    yield from _emit(ctx, "unbounded-blocking-call")


@rule("deadline-not-propagated", "error",
      "function receives a budget but parks without threading it in")
def check_deadline_not_propagated(ctx: ModuleContext) -> Iterable[Finding]:
    """A function that RECEIVES a deadline/timeout/budget value (by
    parameter name, or a parameter of the shared Deadline type) calls a
    blocking primitive without the budget — or anything derived from it —
    in the call's arguments, and without a budget re-check in the
    enclosing loop. The budget dies at this frame: callers time out while
    the callee parks on its own clock. Thread the remaining budget into
    the park (`deadline.clamp(quantum)`) or consult the deadline each
    loop iteration (the scheduler's `_await` poll idiom)."""
    yield from _emit(ctx, "deadline-not-propagated")


@rule("unclamped-external-timeout", "error",
      "wire/context timeout reaches a park without a clamp")
def check_unclamped_external_timeout(ctx: ModuleContext) \
        -> Iterable[Finding]:
    """A timeout parameter entering a request-path function flows into a
    park's bound — directly or as the bound of a park loop — without
    passing a clamp (`min(..., MAX_*)`, `Deadline.clamp`). External
    values are adversarial: `timeoutMs=inf` on the PR 14 long-poll parked
    a handler thread forever and defeated the idle sweep that would have
    reclaimed it. Clamp at the edge, like SubscriptionHub's
    MAX_POLL_TIMEOUT_S."""
    yield from _emit(ctx, "unclamped-external-timeout")


@rule("sleep-on-request-path", "error",
      "fixed time.sleep on a request-serving path")
def check_sleep_on_request_path(ctx: ModuleContext) -> Iterable[Finding]:
    """A fixed `time.sleep` on a request-serving path burns the caller's
    budget invisibly and, under a retry storm, re-synchronizes every
    client onto one instant (the next shed wave). A request-path pause
    must be derived from `decorrelated_jitter` AND guarded by the
    remaining deadline — the remote client's 429 back-off is the
    canonical shape."""
    yield from _emit(ctx, "sleep-on-request-path")


@rule("stop-signal-coverage", "error",
      "thread-root infinite loop never consults a stop signal")
def check_stop_signal_coverage(ctx: ModuleContext) -> Iterable[Finding]:
    """Every `while True` in a thread-root function must consult its stop
    event/flag each iteration (`self._stopping`, a stop Event wait, a
    shutdown re-check) — otherwise stop() can only abandon the thread,
    and leakguard's join discipline turns into a 5-second hang per
    orphan at every teardown. The graceful-shutdown dual of
    unjoined-thread."""
    yield from _emit(ctx, "stop-signal-coverage")
