"""Device-dispatch accounting: one counter per device-callable invocation.

A "dispatch" is one invocation of a jitted device callable on the query
path — a per-segment grouped-aggregate program, a batched multi-segment
program, a bitmap-algebra fill program, a sharded mesh program. The count
is the engine's dispatch-amortization scoreboard: the megakernel's
contract (a cold query in exactly ONE dispatch — engine/megakernel.py) is
asserted against deltas of this counter, and `query/dispatch/count` makes
the same number a tick-window metric so a planner regression that
reintroduces a fill wave or splits a fused program shows up on dashboards,
not just in tests.

Deliberately NOT derived from qtrace spans: spans are off for
{"trace": false} queries and the witness must count every dispatch.

Compiles are counted here too, where they happen: a `jax.monitoring`
listener on JAX's backend-compile event. The engine's `engine/compile`
spans open only on a miss of the engine's OWN program caches; a retrace
that `jax.jit` makes under a cached callable (new padded shape, new dtype)
builds an executable with no such span. The listener sees every one, and
stamps `backendCompiles` on the qtrace span open on the compiling thread,
so the step that recompiled is named. Costs nothing unless something
compiles.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from druid_tpu.obs.trace import current_span, dropped_spans
from druid_tpu.utils.emitter import Monitor

#: jax 0.9.0: fired around `compile_or_get_cached`, i.e. once per executable
#: BUILT — by the backend compiler or out of JAX's persistent cache
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: fired (just before the event above) when the persistent cache answered
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class DispatchStats:
    """Thread-safe per-kind dispatch counters (BatchStats discipline), and
    beside them the executables this process built: `backend_compiles`
    (every build), `backend_compile_ms` (their wall time) and
    `cache_retrievals` (the builds JAX's persistent cache answered —
    set-up pays for both kinds). The snapshot also carries
    `trace_dropped_spans`, qtrace's process-wide count of spans dropped at
    a cap (kept by obs/trace.py, which this module imports and never the
    reverse): it rides here because this scoreboard is what a reader of
    program counters already reads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._total = 0
        self._by_kind: Dict[str, int] = {}
        self._backend_compiles = 0
        self._backend_compile_ms = 0.0
        self._cache_retrievals = 0

    def record(self, kind: str) -> None:
        with self._lock:
            self._total += 1
            self._by_kind[kind] = self._by_kind.get(kind, 0) + 1

    def record_backend_compile(self, seconds: float) -> None:
        with self._lock:
            self._backend_compiles += 1
            self._backend_compile_ms += seconds * 1000.0

    def record_cache_retrieval(self) -> None:
        with self._lock:
            self._cache_retrievals += 1

    def count(self) -> int:
        with self._lock:
            return self._total

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out: Dict[str, float] = dict(self._by_kind)
            out["total"] = self._total
            out["backend_compiles"] = self._backend_compiles
            out["backend_compile_ms"] = self._backend_compile_ms
            out["cache_retrievals"] = self._cache_retrievals
        out["trace_dropped_spans"] = dropped_spans()
        return out


_STATS = DispatchStats()


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _STATS.record_backend_compile(duration_secs)
        sp = current_span()
        if sp is not None:
            sp.attrs["backendCompiles"] = \
                sp.attrs.get("backendCompiles", 0) + 1
    elif event == _CACHE_RETRIEVAL_EVENT:
        _STATS.record_cache_retrieval()


def _register_compile_listener() -> None:
    """Once per process, at import. A process without JAX (a broker-only
    deployment) has nothing to compile and nothing to listen to."""
    global _BACKEND_COMPILE_EVENT
    try:
        from jax import monitoring
    except ImportError:
        return
    try:
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        _BACKEND_COMPILE_EVENT = BACKEND_COMPILE_EVENT
    except ImportError:
        pass         # the literal above is the event's name in jax 0.9.0
    monitoring.register_event_duration_secs_listener(_on_jax_duration)


_register_compile_listener()


def record(kind: str) -> None:
    """Count one device dispatch of `kind` ("segment", "batched",
    "filterFill", "sharded") — called at the exact callable-invocation
    sites, never speculatively."""
    _STATS.record(kind)


def count() -> int:
    """Total dispatches this process has issued (test/bench delta basis)."""
    return _STATS.count()


def stats() -> DispatchStats:
    return _STATS


class DispatchMonitor(Monitor):
    """Emits `query/dispatch/count` per tick: dispatches since the last
    tick (delta, the FilterBitmapMonitor discipline)."""

    def __init__(self, source: Optional[DispatchStats] = None):
        self.source = source or _STATS
        self._last = self.source.count()

    def do_monitor(self, emitter):
        now = self.source.count()
        last, self._last = self._last, now
        emitter.metric("query/dispatch/count", now - last)
