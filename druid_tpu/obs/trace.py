"""qtrace: end-to-end distributed query tracing.

Reference analogs:
  processing/.../query/QueryMetrics.java + MetricsEmittingQueryRunner — the
    per-phase timing dims the reference sprinkles through its runner stack
  opentelemetry-emitter (druid extensions) — span-per-phase query tracing

One trace per query: the trace id IS the queryId (a fresh id when the query
carries none), spans are (name, service, start, duration, attrs) nodes in a
parent tree. Spans cost two monotonic and two thread-CPU clock reads and a
dict — no device syncs, no system call that releases the interpreter lock,
no locks on the hot path (the store append takes the store lock once per
finished span) — and the whole subsystem no-ops unless a ROOT span is open
on the current thread, so untraced paths pay one thread-local read.

Work against wait: every finished span carries `attrs["cpuMs"]`, the CPU
time its thread burned between open and close (`time.thread_time()`: numpy
and XLA-client C code included; 0 while the thread waits for the
interpreter lock, the device or a socket). `durationMs - cpuMs` is the
span's WAIT — the device's under `engine/fetch/wait`, the socket's under
`broker/node/read`, the interpreter lock's everywhere else.

Propagation:
  * thread-local span stack: `span(name)` children nest under the current
    span; `attach(s)` re-activates a span on a worker thread (the broker's
    scatter pool).
  * wire: `with_traceparent(query, span)` stamps "traceId:spanId" into the
    query context the broker POSTs; the data node's `root_span` re-roots its
    spans under that remote parent; the node's finished spans travel back in
    the partials/rows response and the broker ingests them into its store —
    ONE assembled trace per query.
  * opt-out: context {"trace": false} disables tracing for the query
    everywhere (the stamp is simply never created).

  * late spans: work that happens AFTER its natural parent closed — the
    data node encoding the payload that carries the collected spans, the
    HTTP front writing the answer after the `query` root ended — opens
    with `late_span(anchor, ...)`: same trace, same store, parented to the
    finished anchor (or to the anchor's parent).

One clock with the device: in a process that has JAX loaded every span also
enters a `jax.profiler.TraceAnnotation` of the same name — a no-op unless a
profiler session is running — so a captured trace's `/host:CPU` plane holds
the qtrace phases per thread, on the profiler's clock, above `XLA Ops`
(open the trace in XProf/Perfetto). This module never imports JAX itself:
a broker-only process imports it without.

Storage: a bounded per-process ring buffer (TraceStore) serves
GET /druid/v2/trace/<queryId> on any node type. A trace in the store and a
root's response collector follow ONE cap policy (`_SpanBuffer`): past the
cap leaves are dropped and counted, ancestors kept, so a capped trace still
has its root; `dropped_spans()` is the process-wide count.
"""
from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time
import uuid
from typing import Dict, List, Optional

#: context key carrying the remote parent ("traceId:spanId"); the span id is
#: always our own hex (no ":"), so rsplit from the right survives arbitrary
#: user queryIds as trace ids
TRACEPARENT_KEY = "traceparent"
#: context key opting a query out of tracing ({"trace": false})
TRACE_KEY = "trace"

#: well-known span names (phase attribution keys — see obs/catalog.py for
#: the metrics derived from them)
COMPILE_SPAN = "engine/compile"
H2D_SPAN = "pool/h2d"
NODE_SPAN = "broker/node"


def _reseed_ids() -> None:
    global _ID_PREFIX, _ID_COUNTER
    _ID_PREFIX = uuid.uuid4().hex[:8]
    _ID_COUNTER = itertools.count(1)


_reseed_ids()
# a forked child (a peon) must not continue its parent's id sequence
os.register_at_fork(after_in_child=_reseed_ids)


def _new_id() -> str:
    """16 hex digits: a per-process random prefix and a counter. NOT a
    uuid4 a span: `os.urandom` releases the interpreter lock around its
    system call, so every span opened under load handed the lock to
    another request's thread and waited out a switch interval (5 ms) to
    get it back — 3% of `analyst-groupby`'s latency at 173 spans a request
    (my chip runs, PR 24)."""
    return f"{_ID_PREFIX}{next(_ID_COUNTER) & 0xFFFFFFFF:08x}"


class Span:
    """One timed phase. Mutated only by the thread that opened it; finished
    spans are immutable JSON dicts in the store/collector.

    A span is opened and closed by ONE thread (`attach` re-activates a span
    on a worker so that the worker's spans nest under it, and never
    finishes it), so `cpuMs` — that thread's CPU clock at close minus at
    open — is well defined. `time.thread_time()` is
    `clock_gettime(CLOCK_THREAD_CPUTIME_ID)`, made with the interpreter
    lock HELD (CPython's `_PyTime_GetThreadTimeWithInfo` has no
    `Py_BEGIN_ALLOW_THREADS`): unlike the `uuid4` a span that PR 24 took
    out, reading it hands the lock to nobody (measured: a loop of it keeps
    a Python loop's share of the lock beside four spinning threads, where
    `os.urandom` keeps a third of it — PERF.md §6, PR 36). Its resolution
    is the kernel's: nanoseconds where the clock is read in user space,
    but the benchmark's chip host accounts CPU by 10 ms ticks and takes
    ~6 us a read, so THERE a span's `cpuMs` is a multiple of 10 — a sample
    that is unbiased over many spans (compare sums and means over
    requests, not one short span) and may pass a short span's duration.

    `is_root` marks the span `root_span` opened: at its finish it stamps
    `droppedSpans` — what its collector (else its store, for this trace)
    has dropped so far — when that is not 0, so a capped trace says so on
    the one span the cap always keeps."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "service",
                 "start_ms", "duration_ms", "attrs", "_t0", "_c0", "_store",
                 "_collector", "is_root")

    def __init__(self, trace_id: str, span_id: str, parent_id: Optional[str],
                 name: str, service: str, attrs: Optional[dict] = None,
                 store: Optional["TraceStore"] = None,
                 collector: Optional["_SpanBuffer"] = None,
                 is_root: bool = False):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.service = service
        self.start_ms = time.time() * 1000.0
        self.duration_ms: Optional[float] = None
        self.attrs = dict(attrs or {})
        self._store = store
        self._collector = collector
        self.is_root = is_root
        self._t0 = time.monotonic()
        self._c0 = time.thread_time()

    def to_json(self) -> dict:
        return {"traceId": self.trace_id, "spanId": self.span_id,
                "parentId": self.parent_id, "name": self.name,
                "service": self.service,
                "startMs": round(self.start_ms, 3),
                "durationMs": None if self.duration_ms is None
                else round(self.duration_ms, 3),
                "attrs": self.attrs}

    def finish(self) -> None:
        if self.duration_ms is not None:
            return                       # idempotent (double __exit__)
        cpu_ms = (time.thread_time() - self._c0) * 1000.0
        self.duration_ms = (time.monotonic() - self._t0) * 1000.0
        self.attrs["cpuMs"] = round(cpu_ms, 3)
        if self.is_root:
            dropped = self._collector.dropped \
                if self._collector is not None \
                else self._store.dropped(self.trace_id)
            if dropped:
                self.attrs["droppedSpans"] = dropped
        j = self.to_json()
        if self._store is not None:
            self._store.add_json(j)
        if self._collector is not None:
            self._collector.add(j)

    def collected(self) -> List[dict]:
        """Finished spans of this span's request-local collector (the data
        node's response payload); empty unless opened with collect=True."""
        return self._collector.snapshot() \
            if self._collector is not None else []


# ---------------------------------------------------------------------------
# Thread-local current-span stack
# ---------------------------------------------------------------------------

_TLS = threading.local()


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def current_span() -> Optional[Span]:
    st = getattr(_TLS, "stack", None)
    return st[-1] if st else None


class _NullCtx:
    """Inactive span context — tracing off / no root open."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


_ANNOTATION = None


def _annotation(name: str):
    """A `jax.profiler.TraceAnnotation` for `name` when this process has
    JAX loaded (looked up in sys.modules — never imported here), else
    None. Outside a profiler session entering one costs a flag test."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return None
        _ANNOTATION = profiler.TraceAnnotation
    return _ANNOTATION(name)


class _SpanCtx:
    __slots__ = ("_span", "_note")

    def __init__(self, s: Span):
        self._span = s
        self._note = None

    def __enter__(self) -> Span:
        _stack().append(self._span)
        self._note = _annotation(self._span.name)
        if self._note is not None:
            self._note.__enter__()
        return self._span

    def __exit__(self, et, ev, tb):
        if self._note is not None:
            self._note.__exit__(et, ev, tb)
        st = _stack()
        if st and st[-1] is self._span:
            st.pop()
        elif self._span in st:       # unbalanced exit: still unwind
            st.remove(self._span)
        if et is not None:
            self._span.attrs.setdefault("error", f"{et.__name__}: {ev}")
        self._span.finish()
        return False


class _AttachCtx:
    """Re-activate an EXISTING span on this thread (no finish on exit) —
    the broker's scatter workers parent their per-node spans this way."""
    __slots__ = ("_span",)

    def __init__(self, s: Span):
        self._span = s

    def __enter__(self) -> Span:
        _stack().append(self._span)
        return self._span

    def __exit__(self, *exc):
        st = _stack()
        if st and st[-1] is self._span:
            st.pop()
        elif self._span in st:
            st.remove(self._span)
        return False


def attach(s: Optional[Span]):
    return _AttachCtx(s) if s is not None else _NULL_CTX


def span(name: str, **attrs):
    """Child span under the current span; a no-op context when no trace is
    active on this thread (the one thread-local read untraced paths pay)."""
    parent = current_span()
    if parent is None:
        return _NULL_CTX
    return _SpanCtx(Span(
        trace_id=parent.trace_id, span_id=_new_id(),
        parent_id=parent.span_id, name=name, service=parent.service,
        attrs=attrs, store=parent._store, collector=parent._collector))


def span_when(cond: bool, name: str, **attrs):
    """`span(name)` when `cond`, else the inactive context — the jit-cache
    sites wrap their dispatch in this so the builder-idiom miss (the
    compile event) gets its span without duplicating the call in an
    if/else."""
    return span(name, **attrs) if cond else _NULL_CTX


def late_span(anchor: Optional[Span], name: str, sibling: bool = False,
              **attrs):
    """A span opened after `anchor` FINISHED, in its trace and store:
    child of the anchor, or — `sibling=True` — of the anchor's parent. The
    data node times the encoding of the payload that already carries its
    collected spans this way (`datanode/encode`, beside `datanode/query`
    under the broker's `broker/node`), and the HTTP front the writing of
    an answer whose `query` root has closed (`http/respond`). Not added to
    the anchor's collector: the caller ships `to_json()` of the yielded
    span itself. Inactive when `anchor` is None (tracing off)."""
    if anchor is None:
        return _NULL_CTX
    return _SpanCtx(Span(
        trace_id=anchor.trace_id, span_id=_new_id(),
        parent_id=anchor.parent_id if sibling else anchor.span_id,
        name=name, service=anchor.service, attrs=attrs,
        store=anchor._store))


def trace_enabled(query) -> bool:
    v = query.context_map.get(TRACE_KEY, True)
    return str(v).strip().lower() not in ("0", "false", "no")


def root_span(name: str, query=None, service: str = "", store=None,
              collect: bool = False, **attrs):
    """Open a trace root for a query (trace id = queryId), re-rooting under
    a remote parent when the query context carries a traceparent stamp.
    When a trace is ALREADY active on this thread (the lifecycle opened the
    root and the broker re-enters), this degrades to a plain child span.
    Inactive (_NULL_CTX) when the query opts out via {"trace": false}."""
    if query is not None and not trace_enabled(query):
        return _NULL_CTX
    if current_span() is not None:
        return span(name, **attrs)
    ctxm = query.context_map if query is not None else {}
    parent_id = None
    tp = ctxm.get(TRACEPARENT_KEY)
    if isinstance(tp, str) and ":" in tp:
        trace_id, parent_id = tp.rsplit(":", 1)
    else:
        qid = ctxm.get("queryId")
        trace_id = str(qid) if qid else _new_id()
    if query is not None:
        attrs.setdefault("queryType", getattr(query, "query_type", ""))
        attrs.setdefault("dataSource", getattr(query, "datasource", ""))
    st = store if store is not None else trace_store()
    # the collector rides back in the response payload — bound it AS the
    # store bounds a trace, or a span-heavy query bloats every reply
    return _SpanCtx(Span(
        trace_id=trace_id, span_id=_new_id(), parent_id=parent_id,
        name=name, service=service, attrs=attrs, store=st,
        collector=_SpanBuffer(st.max_spans_per_trace) if collect else None,
        is_root=True))


def with_traceparent(query, s: Span):
    """Copy of `query` whose context carries this span as the remote
    parent — what the broker POSTs to a data node."""
    from dataclasses import replace
    ctx = dict(query.context_map)
    ctx[TRACEPARENT_KEY] = f"{s.trace_id}:{s.span_id}"
    return replace(query, context=tuple(sorted(ctx.items())))


# ---------------------------------------------------------------------------
# TraceStore: bounded per-process ring buffer of assembled traces
# ---------------------------------------------------------------------------

#: what one trace of the ring adds to the store's TOTAL span budget: the
#: per-trace cap it had before PR 36, so 256 traces still hold at most
#: 256 x 2,048 spans together
SPANS_PER_SLOT = 2048

_DROPPED_LOCK = threading.Lock()
_DROPPED = 0


def dropped_spans() -> int:
    """Spans this process dropped at a cap since it started — by a store
    or by a collector, a drop each (a node's collector and the store it
    shares a process with count the same leaf once each).
    `obs.dispatch.DispatchStats.snapshot()` carries it as
    `trace_dropped_spans`: above 0 over a window, every metric that sums
    spans under-reads there."""
    with _DROPPED_LOCK:
        return _DROPPED


class _SpanBuffer:
    """The finished spans of ONE trace under the ONE cap policy — a trace
    in the store, a `collect=True` root's response collector.

    Up to `cap` spans are kept as they arrive. Past it a LEAF is dropped
    and counted (`dropped`, and the process-wide `dropped_spans()`); a span
    that has children is kept. Spans arrive as they close, children before
    parents, so "has children" is: an earlier arrival, kept or dropped,
    named its id as `parentId` (`parents`, which stops growing at `cap`
    ids: with the `cap` spans kept before it, a buffer holds at most
    2 x cap whatever a runaway producer sends). So the spans a capped
    trace loses are leaves that closed late, and every ancestor up to the
    root — `query`, `datanode/query`, `engine/partials` — is there with
    its whole duration. Span ids dedupe among the spans kept."""

    __slots__ = ("cap", "spans", "ids", "parents", "dropped", "_lock")

    def __init__(self, cap: int):
        self.cap = cap
        self.spans: List[dict] = []
        self.ids: set = set()
        self.parents: set = set()
        self.dropped = 0
        self._lock = threading.Lock()

    def add(self, j: dict) -> int:
        """Returns how many spans the buffer grew by: 1 kept, 0 a duplicate
        or a dropped leaf."""
        global _DROPPED
        sid = j.get("spanId")
        with self._lock:
            if sid in self.ids:
                return 0
            parent = j.get("parentId")
            if parent is not None and len(self.parents) < self.cap:
                self.parents.add(parent)
            if len(self.spans) < self.cap or sid in self.parents:
                self.ids.add(sid)
                self.spans.append(j)
                return 1
            self.dropped += 1
        with _DROPPED_LOCK:
            _DROPPED += 1
        return 0

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self.spans)


class TraceStore:
    """trace id -> spans, LRU-by-creation ring: the oldest trace is evicted
    when `max_traces` is exceeded, or when the traces together hold more
    than `max_total_spans` = max_traces x min(max_spans_per_trace,
    SPANS_PER_SLOT). A trace follows `_SpanBuffer`'s cap policy: past
    `max_spans_per_trace` leaves are counted, not kept, ancestors kept (a
    runaway span producer must not eat the process, and a capped trace
    must not lose its root).

    The numbers: 8,192 spans a trace, so that the largest request a
    supported deployment makes is WHOLE — 480 per-segment enqueues x 9
    spans + five fetch waves x 4 + the request's own ~30 is ~4,400 (the
    request whose missing root refused PR 33 at the old 2,048); 256 traces
    and 524,288 spans in all, what 256 x 2,048 was. A span's dict is
    ~1.1 KB with its strings (measured, six attrs), so the default store's
    worst case is ~0.6 GB, as before — reached only by 64 or more traces
    at the per-trace cap; 256 `analyst-groupby` traces (~180 spans) are
    ~50 MB.

    Span ids dedupe — a data node sharing this process with the broker
    (in-process tests) records spans locally AND ships them back in the
    response; both paths land once."""

    def __init__(self, max_traces: int = 256,
                 max_spans_per_trace: int = 8192):
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self.max_total_spans = max_traces * min(max_spans_per_trace,
                                                SPANS_PER_SLOT)
        self._lock = threading.Lock()
        self._traces: "collections.OrderedDict[str, _SpanBuffer]" = \
            collections.OrderedDict()
        self._total = 0

    def add_json(self, j: dict) -> None:
        tid = j.get("traceId")
        if not tid or not j.get("spanId"):
            return
        with self._lock:
            t = self._traces.get(tid)
            if t is None:
                t = self._traces[tid] = _SpanBuffer(self.max_spans_per_trace)
            self._total += t.add(j)
            while len(self._traces) > self.max_traces or (
                    self._total > self.max_total_spans
                    and len(self._traces) > 1):
                _, old = self._traces.popitem(last=False)
                self._total -= len(old.spans)

    def ingest(self, spans) -> None:
        """Add remote span dicts (a data node's response payload)."""
        for j in spans or ():
            if isinstance(j, dict):
                self.add_json(j)

    def get(self, trace_id: str) -> Optional[dict]:
        """The assembled trace, spans sorted by start time; None when the
        id is unknown (or already evicted). The list is copied under the
        lock and sorted outside it: every `Span.finish` of every request
        thread waits on that lock."""
        with self._lock:
            t = self._traces.get(trace_id)
            if t is None:
                return None
            spans, dropped = list(t.spans), t.dropped
        spans.sort(key=lambda s: (s.get("startMs") or 0.0))
        return {"traceId": trace_id, "spanCount": len(spans),
                "droppedSpans": dropped, "spans": spans}

    def spans(self, trace_id: str) -> List[dict]:
        got = self.get(trace_id)
        return got["spans"] if got else []

    def dropped(self, trace_id: str) -> int:
        """Leaves this store has dropped of the trace so far."""
        with self._lock:
            t = self._traces.get(trace_id)
            return t.dropped if t is not None else 0

    def trace_ids(self) -> List[str]:
        with self._lock:
            return list(self._traces)


_STORE = TraceStore()


def trace_store() -> TraceStore:
    """The process-wide default store (every node type in this process)."""
    return _STORE


# ---------------------------------------------------------------------------
# Phase attribution -> per-query metrics
# ---------------------------------------------------------------------------

def spans_under(spans, root_span_id: Optional[str]) -> List[dict]:
    """The spans of ONE run: the root plus everything reachable from it by
    parentage. A client may legally reuse a queryId, landing several runs'
    spans in one store entry — per-run metrics must not sum across runs."""
    if root_span_id is None:
        return list(spans)
    children: Dict[Optional[str], List[dict]] = {}
    for s in spans:
        children.setdefault(s.get("parentId"), []).append(s)
    out = [s for s in spans if s.get("spanId") == root_span_id]
    stack = [root_span_id]
    while stack:
        for s in children.get(stack.pop(), ()):
            out.append(s)
            stack.append(s.get("spanId"))
    return out


def phase_breakdown(spans) -> Dict[str, float]:
    """Total duration per span name and, under `<name>:cpu`, the CPU its
    threads burned in it (`cpuMs` summed; absent where no span of the name
    carries one, as a node of an older build sends them) — the slow-query
    log's payload: which phase lasted, and which WORKED. Wire-ingested
    span dicts are unvalidated: nameless ones are skipped."""
    out: Dict[str, float] = {}
    for s in spans:
        d = s.get("durationMs")
        name = s.get("name")
        if d is not None and name:
            out[name] = round(out.get(name, 0.0) + d, 3)
            cpu = (s.get("attrs") or {}).get("cpuMs")
            if isinstance(cpu, (int, float)):
                key = f"{name}:cpu"
                out[key] = round(out.get(key, 0.0) + cpu, 3)
    return out


def emit_trace_metrics(emitter, query, qid: str, spans) -> None:
    """Druid-authentic per-query phase metrics derived from the assembled
    trace: query/compile/time (jit-cache misses), query/stage/h2d/time
    (device-pool cold staging), query/node/time (per remote node wait).
    Emitted once per query by the lifecycle — phases that did not occur
    (cache-hit runs) emit nothing, which is itself the signal."""
    base = dict(dataSource=query.datasource, type=query.query_type, id=qid)
    compile_ms = sum(s["durationMs"] for s in spans
                     if s.get("name") == COMPILE_SPAN
                     and s.get("durationMs") is not None)
    if compile_ms:
        emitter.metric("query/compile/time", compile_ms, **base)
    h2d_ms = sum(s["durationMs"] for s in spans
                 if s.get("name") == H2D_SPAN
                 and s.get("durationMs") is not None)
    if h2d_ms:
        emitter.metric("query/stage/h2d/time", h2d_ms, **base)
    for s in spans:
        if s.get("name") == NODE_SPAN and s.get("durationMs") is not None:
            emitter.metric("query/node/time", s["durationMs"],
                           server=str(s.get("attrs", {}).get("server", "")),
                           **base)
