"""leakguard: whole-program resource-lifecycle analysis.

The north star is a service absorbing heavy traffic for months: every
thread, timer, executor, socket, file handle, HTTP server, temp dir and
device-pool entry acquired per start()/query/stop() cycle must be provably
released, or the process bleeds until a run killed at its time limit or
an OOM. Every recent PR found this bug
class BY HAND — the FileEmitter handle leak, the devicepool finalizer
self-deadlock, the emitter-vs-shutdown race, the stop() un-chaining bugs
in both server types. leakguard closes the static-analysis triad's missing
leg next to druidlint/tracecheck/raceguard by making the discipline
mechanical.

It rides raceguard's whole-program index (module set = config
`raceguard-modules`): the binder types attribute owners, the per-function
event walk already records calls/acquisitions, and the same
program-signature cache keying keeps cross-module findings sound. On top
of that index leakguard discovers ACQUISITION SITES — constructor calls
whose result pins an OS or device resource — binds each to an OWNER (the
class whose attribute, or the module global, holds it), and checks
release reachability from the owner's shutdown surface.

Five rules ride the shared registry/baseline/suppression/cache machinery
(suppress with `# druidlint: disable=<rule>  # <rationale>`):

  unreleased-resource   an owned acquisition (executor, HTTP server, file,
                        socket, TemporaryDirectory, mmap, or a service
                        whose constructor starts a thread) with no release
                        call reachable from the owner's stop()/close()/
                        shutdown()/__exit__;
  unjoined-thread       an owned STARTED Thread/Timer that is never
                        joined, not joined on any shutdown path, or only
                        joined without a timeout on shutdown paths (a hung
                        worker then hangs every stop() above it);
  stop-start-pairing    a class with start() whose __init__/start wires
                        itself into FOREIGN state (chaining another
                        object's attribute) without stop() undoing that
                        wiring — the identity-guarded un-chain idiom PRs 6
                        and 7 had to hand-enforce;
  leak-on-error-path    a local acquisition followed by a raise-capable
                        statement before ownership transfer, outside any
                        try — the constructor raises and the handle leaks;
  finalizer-unsafe      a weakref.finalize callback or __del__ whose call
                        closure acquires a lock — GC runs finalizers at
                        arbitrary allocation points, including while the
                        very lock is held (the PR 5 devicepool witness
                        bug, now caught statically).

Dynamic complement: tools/druidlint/leakwitness.py snapshots live threads,
open fds and devicepool resident bytes around the test suite
(DRUID_TPU_LEAK_WITNESS=1) and asserts return-to-baseline — the witness
catches what the model cannot see, exactly like lockwitness does for the
lock-order graph.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.druidlint.core import Finding, ModuleContext, rule
from tools.druidlint.rules import _FUNC_DEFS, _terminal
from tools.druidlint.raceguard import (INIT_METHODS, Program, Site, _Scope,
                                       _class_with, _closure_frames,
                                       _frame_of, _own, _resolve_value,
                                       _self_param, analyze_sources)

# ---------------------------------------------------------------------------
# Resource model
# ---------------------------------------------------------------------------

#: constructor terminal name → resource kind (direct stdlib acquisitions)
ACQ_CTORS = {
    "Thread": "thread", "Timer": "thread",
    "ThreadPoolExecutor": "executor", "ProcessPoolExecutor": "executor",
    "ThreadingHTTPServer": "server", "HTTPServer": "server",
    "ThreadingTCPServer": "server", "TCPServer": "server",
    "UDPServer": "server", "ThreadingUDPServer": "server",
    "open": "file",
    "socket": "socket", "create_connection": "socket",
    "TemporaryDirectory": "tempdir",
    "mmap": "mmap", "memmap": "mmap",
}

#: stdlib server base-class names: a program class deriving one of these
#: is itself a server acquisition when constructed
SERVER_BASES = {"ThreadingHTTPServer", "HTTPServer", "ThreadingTCPServer",
                "TCPServer", "UDPServer", "ThreadingUDPServer",
                "BaseServer", "socketserver"}

#: kind → method names any one of which releases the resource
RELEASES = {
    "thread": {"join"},
    "executor": {"shutdown"},
    "server": {"server_close", "close"},
    "file": {"close"},
    "socket": {"close", "detach"},
    "tempdir": {"cleanup"},
    "mmap": {"close"},
    "service": {"stop", "close", "shutdown"},
}

#: what a human should call, for messages
RELEASE_HINT = {
    "thread": ".join(timeout=...)", "executor": ".shutdown()",
    "server": ".server_close()", "file": ".close()", "socket": ".close()",
    "tempdir": ".cleanup()", "mmap": ".close()",
    "service": ".stop()/.close()",
}

#: the owner's shutdown surface: release must be reachable from one of
#: these (when the owner defines any of them)
ENTRY_METHODS = {"stop", "close", "shutdown", "__exit__", "cleanup",
                 "uninstall", "terminate"}

#: kinds leak-on-error-path tracks for LOCAL variables (an unstarted
#: Thread object holds no OS resource yet)
LOCAL_LEAK_KINDS = {"file", "socket", "tempdir", "mmap", "executor",
                    "server"}

#: container-read methods whose result is an element of the attr
_DERIVE_GETTERS = {"get", "pop", "popleft", "popitem", "setdefault"}

@dataclass
class Acq:
    kind: str
    owner: Optional[str]              # class_key, or None for module global
    attr: str                         # attribute name / global name
    site: Site
    path: str


@dataclass
class Release:
    attr: str
    method: str                       # join/close/shutdown/…
    fid: str                          # function it occurs in
    has_timeout: bool
    site: Site


@dataclass
class _ClassLeaks:
    acqs: List[Acq] = field(default_factory=list)
    releases: List[Release] = field(default_factory=list)
    #: attrs whose value was handed to a Lifecycle-style registrar or
    #: returned/escaped — ownership transferred, owner no longer on the
    #: hook for the release
    escaped_attrs: Set[str] = field(default_factory=set)
    started_attrs: Set[str] = field(default_factory=set)


# ---------------------------------------------------------------------------
# Discovery
# ---------------------------------------------------------------------------

def _threaded_ctor_classes(prog: Program) -> Set[str]:
    """Program classes whose __init__ both CONSTRUCTS and STARTS a thread:
    holding such an instance is holding a running thread, so the holder
    must stop it (the EventReceiver/TaskActionServer/LoadQueuePeon/
    BatchingEmitter shape)."""
    out: Set[str] = set()
    for ck, ci in prog.classes.items():
        init = ci.methods.get("__init__")
        if init is None:
            continue
        fi = prog.funcs[init]
        ctor = started = False
        for node in _own(fi):
            if isinstance(node, ast.Call):
                name = _terminal(node.func)
                if name in ("Thread", "Timer"):
                    ctor = True
                elif name == "start":
                    started = True
        if ctor and started:
            out.add(ck)
    return out


def _acq_kind(prog: Program, mod, scope: _Scope, call: ast.Call,
              services: Set[str]) -> Optional[str]:
    """Resource kind of a constructor call. "service" = a program class
    whose ctor starts a thread; "service?" = a program class with a
    start()+stop() surface — it only becomes an acquisition if the owner
    actually start()s the attribute (resolved by the caller)."""
    name = _terminal(call.func)
    kind = ACQ_CTORS.get(name)
    if kind is not None:
        # bare `open` only as a Name or os./io. prefix; `self.open(...)`
        # is a method call, not the builtin
        if kind == "file" and isinstance(call.func, ast.Attribute) \
                and _terminal(call.func.value) not in ("os", "io",
                                                       "gzip", "bz2",
                                                       "lzma"):
            return None
        return kind
    got = _resolve_value(prog, mod, scope, call.func)
    if got is not None and got[0] == "class":
        ci = prog.classes.get(got[1])
        if ci is not None:
            if any(_terminal(b) in SERVER_BASES for b in ci.bases):
                return "server"
            has_release = bool(set(ci.methods) & RELEASES["service"])
            if got[1] in services and has_release:
                return "service"
            if "start" in ci.methods and has_release:
                return "service?"
    return None


def _src_order(fi) -> List[ast.AST]:
    """fi's own nodes in source order (the _own DFS stack order is not)."""
    return sorted((n for n in _own(fi) if hasattr(n, "lineno")),
                  key=lambda n: (n.lineno, n.col_offset))


def _self_attr(expr: ast.AST, self_name: Optional[str]) -> Optional[str]:
    """`self.X` → "X" (None otherwise)."""
    if self_name is not None and isinstance(expr, ast.Attribute) \
            and isinstance(expr.value, ast.Name) \
            and expr.value.id == self_name:
        return expr.attr
    return None


_SNAPSHOT_FNS = {"list", "sorted", "tuple", "set", "reversed", "iter"}


def _derived_locals(fi, self_name: Optional[str]) -> Dict[str, str]:
    """Local name → attr it derives from: `t = self._thread`,
    `t = self._threads[k]`, `t = self._threads.pop(k)`, loop targets over
    `self._threads` / `.values()` / `.items()`, snapshot wrappers
    (`ts = list(self._threads.values())` — the take-under-the-lock idiom
    the lock-scope rule forces), and transitively through locals."""
    out: Dict[str, str] = {}

    def origin(expr) -> Optional[str]:
        attr = _self_attr(expr, self_name)
        if attr is not None:
            return attr
        if isinstance(expr, ast.Name):
            return out.get(expr.id)
        if isinstance(expr, ast.Subscript):
            return origin(expr.value)
        if isinstance(expr, ast.Call):
            if isinstance(expr.func, ast.Attribute) \
                    and expr.func.attr in (_DERIVE_GETTERS
                                           | {"values", "items"}):
                return origin(expr.func.value)
            if isinstance(expr.func, ast.Name) \
                    and expr.func.id in _SNAPSHOT_FNS and expr.args:
                return origin(expr.args[0])
        return None

    for node in _src_order(fi):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            o = origin(node.value)
            if o is not None:
                out[node.targets[0].id] = o
        elif isinstance(node, (ast.For, ast.comprehension)):
            it = node.iter
            o = origin(it)
            if o is None:
                continue
            tgt = node.target
            if isinstance(tgt, ast.Name):
                out[tgt.id] = o
            elif isinstance(tgt, ast.Tuple) and len(tgt.elts) == 2 \
                    and isinstance(tgt.elts[1], ast.Name) \
                    and isinstance(it, ast.Call) \
                    and _terminal(it.func) == "items":
                out[tgt.elts[1].id] = o     # for k, v in self.X.items()
    return out


def _collect_class(prog: Program, ck: str,
                   services: Set[str]) -> _ClassLeaks:
    ci = prog.classes[ck]
    mod = prog.modules[ci.path]
    out = _ClassLeaks()
    all_release_names = set().union(*RELEASES.values())
    for mname, fid in ci.methods.items():
        fi = prog.funcs[fid]
        self_name = _self_param(fi.node)
        if self_name is None:
            continue
        scope = _Scope(mod, _closure_frames(prog, mod, fi)
                       + [_frame_of(prog, mod, fi)])
        derived = _derived_locals(fi, self_name)
        #: locals holding a fresh acquisition in this function
        local_acq: Dict[str, str] = {}
        #: local name → attr it was stored into (`self.X[k] = t`)
        local_home: Dict[str, str] = {}
        for node in _src_order(fi):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(node.value, ast.Call):
                    kind = _acq_kind(prog, mod, scope, node.value, services)
                    attr = _self_attr(t, self_name)
                    if kind is not None and attr is not None:
                        out.acqs.append(Acq(kind, ck, attr,
                                            Site(ci.path,
                                                 node.value.lineno,
                                                 node.value.col_offset),
                                            ci.path))
                    elif kind is not None and isinstance(t, ast.Name):
                        local_acq[t.id] = kind
                    elif kind is not None and isinstance(t, ast.Subscript):
                        cattr = _self_attr(t.value, self_name)
                        if cattr is not None:
                            out.acqs.append(Acq(kind, ck, cattr,
                                                Site(ci.path,
                                                     node.value.lineno,
                                                     node.value.col_offset),
                                                ci.path))
                elif isinstance(node.value, ast.Name) \
                        and node.value.id in local_acq:
                    # two-step ownership: `t = Thread(...); self.X = t`
                    # (or container store `self.X[k] = t`)
                    kind = local_acq[node.value.id]
                    attr = _self_attr(t, self_name)
                    if attr is None and isinstance(t, ast.Subscript):
                        attr = _self_attr(t.value, self_name)
                    if attr is not None:
                        out.acqs.append(Acq(kind, ck, attr,
                                            Site(ci.path, node.lineno,
                                                 node.col_offset),
                                            ci.path))
                        local_home[node.value.id] = attr
            elif isinstance(node, ast.Call):
                func = node.func
                if isinstance(func, ast.Attribute):
                    recv = func.value
                    attr = _self_attr(recv, self_name)
                    if attr is None and isinstance(recv, ast.Subscript):
                        attr = _self_attr(recv.value, self_name)
                    if attr is None and isinstance(recv, ast.Name):
                        attr = derived.get(recv.id)
                    if attr is not None:
                        if func.attr == "start":
                            out.started_attrs.add(attr)
                        elif func.attr in all_release_names:
                            has_to = bool(node.args) or any(
                                kw.arg == "timeout"
                                for kw in node.keywords)
                            out.releases.append(Release(
                                attr, func.attr, fid, has_to,
                                Site(ci.path, node.lineno,
                                     node.col_offset)))
                # `t.start()` on a local that was stored into (or read
                # out of) an attr container marks that attr started (the
                # ForkingTaskRunner start-outside-the-lock shape)
                if isinstance(func, ast.Attribute) \
                        and func.attr == "start" \
                        and isinstance(func.value, ast.Name):
                    home = local_home.get(func.value.id) \
                        or derived.get(func.value.id)
                    if home is not None:
                        out.started_attrs.add(home)
                # bare `self.X` as an argument = ownership escapes (a
                # Lifecycle.add(self._monitors) registrar now owns the
                # stop; a callback receiver may close it) — but ONLY when
                # the callee can actually close it: a points-to pass over
                # resolvable program callees keeps the obligation here
                # when the receiving parameter is provably never
                # released, stored, returned, or re-escaped (the PR 14
                # rider; unresolvable callees stay conservative)
                for pos, arg in enumerate(node.args):
                    attr = _self_attr(arg, self_name)
                    if attr is not None and _callee_can_close(
                            prog, mod, scope, node, pos, None):
                        out.escaped_attrs.add(attr)
                for kw in node.keywords:
                    attr = _self_attr(kw.value, self_name)
                    if attr is not None and _callee_can_close(
                            prog, mod, scope, node, None, kw.arg):
                        out.escaped_attrs.add(attr)
            elif isinstance(node, ast.Return) and node.value is not None:
                attr = _self_attr(node.value, self_name)
                if attr is not None:
                    out.escaped_attrs.add(attr)
    return out


# ---------------------------------------------------------------------------
# Points-to: can a callee close the attribute handed to it?
# ---------------------------------------------------------------------------

#: transitive-escape recursion bound: past this depth the pass answers
#: "yes, it can close it" (the pre-pass conservative default)
_POINTS_TO_DEPTH = 3


def _callee_can_close(prog: Program, mod, scope: _Scope, call: ast.Call,
                      pos: Optional[int], kw_name: Optional[str],
                      depth: int = 0) -> bool:
    """True when passing an owned attribute as this call argument may
    transfer the release obligation. Conservative by default (unknown or
    external callees, constructors, varargs, re-escapes all answer True);
    False ONLY when the callee resolves to a program function whose
    receiving parameter is provably inert — never the receiver of a
    release-family method, never stored into an attribute/subscript,
    never returned/yielded, never a context manager, and never passed on
    to anything that could itself close it (followed transitively to
    _POINTS_TO_DEPTH)."""
    if depth >= _POINTS_TO_DEPTH:
        return True
    got = _resolve_value(prog, mod, scope, call.func)
    if got is None or got[0] == "class":
        return True                       # unknown / constructor stores it
    if got[0] != "func":
        return True
    fi = prog.funcs.get(got[1])
    if fi is None or isinstance(fi.node, ast.Lambda):
        return True
    args = fi.node.args
    if args.vararg is not None or args.kwarg is not None:
        return True
    params = [a.arg for a in getattr(args, "posonlyargs", [])] \
        + [a.arg for a in args.args]
    if fi.class_key is not None and isinstance(call.func, ast.Attribute) \
            and params:
        params = params[1:]               # bound call: drop self
    if kw_name is not None:
        pname = kw_name if kw_name in params \
            or kw_name in {a.arg for a in args.kwonlyargs} else None
    else:
        pname = params[pos] if pos is not None and pos < len(params) \
            else None
    if pname is None:
        return True
    return _param_can_be_closed(prog, fi, pname, depth)


def _param_can_be_closed(prog: Program, fi, pname: str,
                         depth: int) -> bool:
    """Whether `pname` inside `fi` can end up closed/owned elsewhere.
    Tracks direct uses plus simple local aliases (`x = pname`)."""
    all_release_names = set().union(*RELEASES.values())
    names = {pname}
    #: names the function declares global/nonlocal: a store to one is an
    #: ownership transfer, not a local alias
    outer_names: Set[str] = set()
    for node in _own(fi):
        if isinstance(node, (ast.Global, ast.Nonlocal)):
            outer_names.update(node.names)
    for node in _src_order(fi):           # aliases first, source order
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id not in outer_names \
                and isinstance(node.value, ast.Name) \
                and node.value.id in names:
            names.add(node.targets[0].id)

    def is_tracked(expr) -> bool:
        return isinstance(expr, ast.Name) and expr.id in names

    def holds_tracked(expr) -> bool:
        """The VALUE being stored/returned holds the resource itself: the
        bare name, or the name inside (nested) tuple/list/set/dict
        containers. Derived expressions (an f-string reading an
        attribute, arithmetic) yield new objects, not the handle."""
        if is_tracked(expr):
            return True
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            return any(holds_tracked(e) for e in expr.elts)
        if isinstance(expr, ast.Dict):
            return any(v is not None and holds_tracked(v)
                       for v in expr.values)
        if isinstance(expr, ast.Starred):
            return holds_tracked(expr.value)
        return False

    mod = prog.modules[fi.path]
    scope = _Scope(mod, _closure_frames(prog, mod, fi)
                   + [_frame_of(prog, mod, fi)])
    # a closure (nested def/lambda) capturing the parameter can release
    # it later from anywhere — conservative escape
    for node in ast.walk(fi.node):
        if isinstance(node, _FUNC_DEFS + (ast.Lambda,)) \
                and node is not fi.node:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id in names:
                    return True
    for node in _own(fi):
        if isinstance(node, ast.Attribute) and is_tracked(node.value) \
                and node.attr in all_release_names:
            # any REFERENCE to a release-family attribute of the param —
            # `param.close()` but also a bound-method value like
            # `sinks.append(param.close)` — can release it
            return True
        if isinstance(node, ast.Call):
            # param passed onward: recurse (bounded); unresolvable → True
            for i, arg in enumerate(node.args):
                if is_tracked(arg) and _callee_can_close(
                        prog, mod, scope, node, i, None, depth + 1):
                    return True
            for kw in node.keywords:
                if is_tracked(kw.value) and _callee_can_close(
                        prog, mod, scope, node, None, kw.arg, depth + 1):
                    return True
        elif isinstance(node, ast.Assign):
            # stored into an attribute/subscript or a global/nonlocal
            # name: ownership taken (any tracked name anywhere in the
            # stored value counts — tuples, method references, wrappers)
            for t in node.targets:
                outer = isinstance(t, ast.Name) and t.id in outer_names
                if (isinstance(t, (ast.Attribute, ast.Subscript))
                        or outer) and holds_tracked(node.value):
                    return True
        elif isinstance(node, (ast.Return, ast.Yield)) \
                and getattr(node, "value", None) is not None:
            if holds_tracked(node.value):
                return True
        elif isinstance(node, ast.With):
            for item in node.items:
                if is_tracked(item.context_expr):
                    return True           # __exit__ closes it
    return False


# ---------------------------------------------------------------------------
# Reachability
# ---------------------------------------------------------------------------

def _self_closure(prog: Program, ck: str, entry_names: Set[str],
                  include_free: bool = False) -> Set[str]:
    """func_ids reachable from the named methods of `ck` following
    self-receiver call edges (and, optionally, calls into free module
    functions — the compose_sink-style helper shape)."""
    ci = prog.classes.get(ck)
    if ci is None:
        return set()
    seen: Set[str] = set()
    stack = [fid for name, fid in ci.methods.items()
             if name in entry_names]
    while stack:
        fid = stack.pop()
        if fid in seen:
            continue
        seen.add(fid)
        fi = prog.funcs.get(fid)
        if fi is None:
            continue
        for callee, _h, _s, recv_self in fi.calls:
            tfi = prog.funcs.get(callee)
            if tfi is None:
                continue
            same_class = tfi.class_key == ck
            free = tfi.class_key is None
            if recv_self or same_class or (include_free and free):
                stack.append(callee)
    return seen


def _entry_methods_of(prog: Program, ck: str) -> Set[str]:
    ci = prog.classes[ck]
    return {m for m in ci.methods if m in ENTRY_METHODS}


# ---------------------------------------------------------------------------
# Rules: unreleased-resource + unjoined-thread
# ---------------------------------------------------------------------------

def _check_ownership(prog: Program, add) -> None:
    services = _threaded_ctor_classes(prog)
    for ck in sorted(prog.classes):
        cl = _collect_class(prog, ck, services)
        if not cl.acqs:
            continue
        entries = _entry_methods_of(prog, ck)
        entry_closure = _self_closure(prog, ck, entries) if entries \
            else set()
        rel_by_attr: Dict[str, List[Release]] = {}
        for r in cl.releases:
            rel_by_attr.setdefault(r.attr, []).append(r)
        seen_attr_kinds: Set[Tuple[str, str]] = set()
        for acq in cl.acqs:
            if acq.kind == "service?":
                # a held start/stop service only becomes our resource if
                # WE start it (tests constructing-but-never-starting one
                # owe nothing)
                if acq.attr not in cl.started_attrs:
                    continue
                acq.kind = "service"
            key = (acq.attr, acq.kind)
            if key in seen_attr_kinds:
                continue              # one finding per (attr, kind)
            seen_attr_kinds.add(key)
            if acq.attr in cl.escaped_attrs:
                continue              # ownership handed off — not ours
            rels = [r for r in rel_by_attr.get(acq.attr, ())
                    if r.method in RELEASES[acq.kind]]
            if acq.kind == "thread":
                if acq.attr not in cl.started_attrs:
                    continue          # never started: no OS thread to join
                if not rels:
                    add("unjoined-thread", acq.site,
                        f"{_short(ck)}.{acq.attr} thread is start()ed but "
                        f"never joined — stop() returns while the worker "
                        f"still runs, and a million start/stop cycles "
                        f"strand a million threads; join it (with a "
                        f"timeout) on the shutdown path")
                    continue
                if entries:
                    on_path = [r for r in rels if r.fid in entry_closure]
                    if not on_path:
                        add("unjoined-thread", acq.site,
                            f"{_short(ck)}.{acq.attr} thread is joined, "
                            f"but not on any shutdown path "
                            f"({'/'.join(sorted(entries))}) — stop() can "
                            f"return with the worker still running")
                    elif all(not r.has_timeout for r in on_path):
                        add("unjoined-thread", on_path[0].site,
                            f"{_short(ck)}.{acq.attr}.join() without a "
                            f"timeout on a shutdown path — a wedged "
                            f"worker then hangs every stop() above it; "
                            f"pass a bounded timeout")
                continue
            # non-thread kinds → unreleased-resource
            if not rels:
                add("unreleased-resource", acq.site,
                    f"{_short(ck)}.{acq.attr} ({acq.kind}) is acquired "
                    f"but no release ({RELEASE_HINT[acq.kind]}) exists "
                    f"anywhere in {_short(ck)} — every owner lifecycle "
                    f"leaks one; release it from "
                    f"stop()/close()/shutdown()")
            elif entries and not any(r.fid in entry_closure for r in rels):
                rel = min(rels, key=lambda r: (r.site.path, r.site.line))
                add("unreleased-resource", acq.site,
                    f"{_short(ck)}.{acq.attr} ({acq.kind}) is released "
                    f"only outside the shutdown surface (release at "
                    f"{rel.site.path}:{rel.site.line} is not reachable "
                    f"from {'/'.join(sorted(entries))}) — a plain stop() "
                    f"leaks it")


# ---------------------------------------------------------------------------
# Rule: leak-on-error-path
# ---------------------------------------------------------------------------

def _check_error_paths(prog: Program, add) -> None:
    services: Set[str] = set()        # service kind not tracked for locals
    for fid in sorted(prog.funcs):
        fi = prog.funcs[fid]
        mod = prog.modules[fi.path]
        scope = _Scope(mod, _closure_frames(prog, mod, fi)
                       + [_frame_of(prog, mod, fi)])
        def walk_block(body, in_try: bool):
            #: name → (site, kind) acquired and not yet transferred
            pending: Dict[str, Tuple[Site, str]] = {}
            for node in body:
                if isinstance(node, _FUNC_DEFS + (ast.ClassDef,)):
                    continue
                if isinstance(node, ast.Try):
                    # anything pending is now covered by a handler/finally
                    pending.clear()
                    for sub in ([node.body, node.orelse, node.finalbody]
                                + [h.body for h in node.handlers]):
                        walk_block(sub, True)
                    continue
                if isinstance(node, ast.With):
                    # `with open(...) as f`: the manager releases
                    for item in node.items:
                        _transfer_names(item.context_expr, pending)
                    walk_block(node.body, in_try)
                    continue
                # 1) transfers in this statement clear pending
                acquired_here: Set[str] = set()
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1:
                    t = node.targets[0]
                    if isinstance(node.value, ast.Call) \
                            and isinstance(t, ast.Name) and not in_try:
                        kind = _acq_kind(prog, mod, scope, node.value,
                                         services)
                        if kind in LOCAL_LEAK_KINDS:
                            pending[t.id] = (Site(fi.path,
                                                  node.value.lineno,
                                                  node.value.col_offset),
                                             kind)
                            acquired_here.add(t.id)
                    if isinstance(node.value, ast.Name):
                        pending.pop(node.value.id, None)  # stored → owned
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call):
                        for arg in (list(sub.args)
                                    + [kw.value for kw in sub.keywords]):
                            _transfer_names(arg, pending)
                    elif isinstance(sub, (ast.Return, ast.Yield)) \
                            and getattr(sub, "value", None) is not None:
                        _transfer_names(sub.value, pending)
                # 2) a raise-capable statement with acquisitions pending
                #    (not acquired by this very statement) leaks on raise
                at_risk = {n: ps for n, ps in pending.items()
                           if n not in acquired_here}
                if at_risk and _raise_capable(node, set(at_risk)):
                    for name, (site, kind) in sorted(at_risk.items()):
                        add("leak-on-error-path", site,
                            f"local {kind} `{name}` is acquired here, and "
                            f"a later call can raise before ownership "
                            f"transfers — the handle leaks on that path; "
                            f"use a context manager or try/finally")
                        pending.pop(name, None)
                # nested control flow inherits pending? conservative: a
                # branch may transfer — drop pending entering branches
                if any(getattr(node, b, None)
                       for b in ("body", "orelse", "finalbody")):
                    for sub in (getattr(node, "body", None),
                                getattr(node, "orelse", None),
                                getattr(node, "finalbody", None)):
                        if sub:
                            walk_block(sub, in_try)
                    pending.clear()

        walk_block(fi.node.body if not isinstance(fi.node, ast.Lambda)
                   else [], False)


def _transfer_names(expr: ast.AST, pending: Dict[str, Tuple]) -> None:
    if isinstance(expr, ast.Name):
        pending.pop(expr.id, None)
    elif isinstance(expr, (ast.Tuple, ast.List)):
        for e in expr.elts:
            _transfer_names(e, pending)


def _raise_capable(node: ast.AST, pending_names: Set[str]) -> bool:
    """A statement that can raise mid-flight: any call NOT on a pending
    resource itself (fh.write() raising still leaks fh, but the common
    `fh = open(); self._fh = fh` shape must stay quiet), or an explicit
    raise/assert."""
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Raise, ast.Assert)):
            return True
        if isinstance(sub, ast.Call):
            f = sub.func
            if isinstance(f, ast.Attribute) \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id in pending_names:
                continue              # method on the resource itself
            return True
    return False


# ---------------------------------------------------------------------------
# Rule: finalizer-unsafe
# ---------------------------------------------------------------------------

def _call_closure(prog: Program, fid: str) -> Set[str]:
    seen: Set[str] = set()
    stack = [fid]
    while stack:
        cur = stack.pop()
        if cur in seen:
            continue
        seen.add(cur)
        fi = prog.funcs.get(cur)
        if fi is None:
            continue
        for callee, _h, _s, _r in fi.calls:
            stack.append(callee)
    return seen


def _check_finalizers(prog: Program, add) -> None:
    #: (registration site, callback fid, label)
    finalizers: List[Tuple[Site, str, str]] = []
    for fid in sorted(prog.funcs):
        fi = prog.funcs[fid]
        mod = prog.modules[fi.path]
        scope = _Scope(mod, _closure_frames(prog, mod, fi)
                       + [_frame_of(prog, mod, fi)])
        for node in _own(fi):
            if isinstance(node, ast.Call) \
                    and _terminal(node.func) == "finalize" \
                    and len(node.args) >= 2:
                got = _resolve_value(prog, mod, scope, node.args[1])
                if got is not None and got[0] == "func":
                    finalizers.append(
                        (Site(fi.path, node.lineno, node.col_offset),
                         got[1], "weakref.finalize callback"))
    for ck, ci in prog.classes.items():
        if "__del__" in ci.methods:
            fid = ci.methods["__del__"]
            fi = prog.funcs[fid]
            finalizers.append(
                (Site(ci.path, fi.node.lineno, fi.node.col_offset),
                 fid, f"{_short(ck)}.__del__"))
    for site, fid, label in finalizers:
        for member in sorted(_call_closure(prog, fid)):
            mfi = prog.funcs.get(member)
            if mfi is None or not mfi.acquires:
                continue
            lock, _h, lsite, _w = mfi.acquires[0]
            add("finalizer-unsafe", site,
                f"{label} reaches a lock acquisition "
                f"({mfi.qual}() at {lsite.path}:{lsite.line}) — GC runs "
                f"finalizers at arbitrary allocation points, including "
                f"while that very lock is held: self-deadlock. Enqueue "
                f"into a lock-free structure drained under the lock "
                f"instead (the devicepool._dead_owners idiom)")
            break


# ---------------------------------------------------------------------------
# Rule: stop-start-pairing
# ---------------------------------------------------------------------------

def _check_pairing(prog: Program, add) -> None:
    # index: state → [(fid, site)] of every attribute write in the program
    writes_by_state: Dict[Tuple, List[Tuple[str, Site]]] = {}
    for fid, fi in prog.funcs.items():
        for st, _held, site in fi.writes:
            if st[0] != "attr":
                continue
            writes_by_state.setdefault(st, []).append((fid, site))
    for ck in sorted(prog.classes):
        ci = prog.classes[ck]
        if "start" not in ci.methods:
            continue
        wiring_closure = _self_closure(prog, ck, {"__init__", "start"},
                                       include_free=True)
        stop_closure = _self_closure(
            prog, ck, _entry_methods_of(prog, ck), include_free=True)
        #: classes this class constructs itself (their attrs die with us)
        constructed: Set[str] = set()
        init = ci.methods.get("__init__")
        if init is not None:
            fi = prog.funcs[init]
            mod = prog.modules[ci.path]
            scope = _Scope(mod, [_frame_of(prog, mod, fi)])
            for node in _own(fi):
                if isinstance(node, ast.Call):
                    got = _resolve_value(prog, mod, scope, node.func)
                    if got is not None and got[0] == "class":
                        constructed.add(got[1])
        for fid in sorted(wiring_closure):
            fi = prog.funcs[fid]
            for st, _held, site in fi.writes:
                if st[0] != "attr" or st[1] == ck:
                    continue          # own state is not wiring
                if st[1] in constructed:
                    continue          # we own that object's lifetime
                if fi.class_key is not None and fi.class_key != ck:
                    continue          # another class's method: its problem
                # undo present? (a) same state written in stop closure
                undone = any(w_fid in stop_closure and w_site != site
                             for w_fid, w_site
                             in writes_by_state.get(st, ()))
                # (b) the wiring function (or a nested local fn of it)
                #     also writes the state — the compose_sink restore
                #     closure idiom
                if not undone:
                    prefix = fi.qual + ".<locals>."
                    for w_fid, w_site in writes_by_state.get(st, ()):
                        wfi = prog.funcs.get(w_fid)
                        if wfi is None:
                            continue
                        if w_site != site and wfi.path == fi.path and (
                                w_fid == fid
                                or wfi.qual.startswith(prefix)):
                            undone = True
                            break
                if not undone:
                    add("stop-start-pairing", site,
                        f"{_short(ck)} wires foreign state "
                        f"{_short(st[1])}.{st[2]} here (during "
                        f"__init__/start) but no stop()/close() path "
                        f"writes it back — a reconstructed "
                        f"{_short(ck)} over the same object double-"
                        f"chains; restore the previous value "
                        f"(identity-guarded) on stop")


def _short(class_key: str) -> str:
    return class_key.split("::", 1)[-1]


# ---------------------------------------------------------------------------
# Orchestration + rule shims
# ---------------------------------------------------------------------------

def leak_findings(prog: Program) -> Dict[str, Dict[str, List[Tuple]]]:
    """rule → path → [(line, col, message)], memoized on the Program."""
    got = getattr(prog, "_leak_findings", None)
    if got is not None:
        return got
    findings: Dict[str, Dict[str, List[Tuple]]] = {}

    def add(rule_name: str, site: Site, message: str) -> None:
        findings.setdefault(rule_name, {}).setdefault(
            site.path, []).append((site.line, site.col, message))

    _check_ownership(prog, add)
    _check_error_paths(prog, add)
    _check_finalizers(prog, add)
    _check_pairing(prog, add)
    prog._leak_findings = findings
    return findings


def _program_for(ctx: ModuleContext) -> Program:
    from tools.druidlint.raceguard import _program_for as rg_program
    return rg_program(ctx)


def _emit(ctx: ModuleContext, rule_name: str) -> Iterable[Finding]:
    if not ctx.path_matches(ctx.config.raceguard_modules):
        return
    prog = _program_for(ctx)
    for line, col, message in sorted(
            leak_findings(prog).get(rule_name, {}).get(ctx.path, ())):
        yield ctx.finding(SimpleNamespace(lineno=line, col_offset=col),
                          message)


@rule("unreleased-resource", "error",
      "owned resource with no release reachable from the shutdown surface")
def check_unreleased_resource(ctx: ModuleContext) -> Iterable[Finding]:
    """A class-owned acquisition (executor, HTTP server, file, socket,
    TemporaryDirectory, mmap, threaded service) whose release call is
    absent — or present but unreachable from the owner's
    stop()/close()/shutdown()/__exit__. Passing the attribute to another
    object (a Lifecycle registrar) transfers ownership and silences the
    rule. Whole-program: uses raceguard's binder and module set."""
    yield from _emit(ctx, "unreleased-resource")


@rule("unjoined-thread", "error",
      "owned started thread never joined (or join has no timeout)")
def check_unjoined_thread(ctx: ModuleContext) -> Iterable[Finding]:
    """An attribute-held Thread/Timer that is start()ed but never joined,
    joined only off the shutdown surface, or joined without a timeout on
    it. Fire-and-forget locals are exempt (request-scoped); stored threads
    are infrastructure and must be joined boundedly on stop()."""
    yield from _emit(ctx, "unjoined-thread")


@rule("stop-start-pairing", "warning",
      "start()-time wiring into foreign state with no stop()-time undo")
def check_stop_start_pairing(ctx: ModuleContext) -> Iterable[Finding]:
    """A class with start() that rebinds ANOTHER object's attribute during
    __init__/start (chaining a lifecycle hook, swapping an emitter sink)
    must write it back on its stop path — or carry the undo as a nested
    restore closure at the wiring site (the compose_sink idiom). Otherwise
    server generations double-chain and dead references accumulate."""
    yield from _emit(ctx, "stop-start-pairing")


@rule("leak-on-error-path", "warning",
      "local acquisition can leak when a later call raises")
def check_leak_on_error_path(ctx: ModuleContext) -> Iterable[Finding]:
    """`fh = open(...)` followed by a raise-capable call before the handle
    is stored/returned/passed on, with no enclosing try: the exception
    unwinds and the fd leaks. Use a context manager, try/finally, or
    transfer ownership first."""
    yield from _emit(ctx, "leak-on-error-path")


@rule("finalizer-unsafe", "error",
      "weakref/__del__ finalizer acquires a lock in its call closure")
def check_finalizer_unsafe(ctx: ModuleContext) -> Iterable[Finding]:
    """GC may run a finalizer at ANY allocation point — including while the
    thread holds the very lock the finalizer wants (the PR 5 devicepool
    self-deadlock). Finalizer callbacks must stay lock-free: enqueue into
    an atomic structure and drain it under the lock from normal code."""
    yield from _emit(ctx, "finalizer-unsafe")
