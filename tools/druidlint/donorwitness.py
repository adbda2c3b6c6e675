"""donorwitness: a dynamic witness for donorguard's buffer-ownership
protocol — take → dispatch → re-park, proven on real pool objects.

donorguard proves the ownership discipline statically, but a dataflow
edge is not an identity: only the runtime can see WHICH array object was
popped, donated, re-parked, or silently dropped. The witness closes that
loop by tracking array identity (id + weakref) across the cycle the
engine actually runs:

  * `DeviceSegmentPool.take` — every leaf of a popped entry moves from
    the RESIDENT registry to the OUTSTANDING registry: the caller now
    owns it and owes the pool a re-park, a return, or an explicit
    discard.
  * `DeviceSegmentPool.get_or_build` — every leaf of the returned entry
    is registered RESIDENT (the pool references it); leaves that were
    outstanding are discharged (the re-park leg of the cycle).
  * the donating dispatch (`grouping._build_device_fn`'s product, the
    only donate_argnums construction in the tree) — before the call,
    any carry leaf still RESIDENT is a cached-entry donation (donating
    a buffer the pool still references poisons every future hit: the
    dynamic twin of donorguard's `donate-cached-entry`). After a
    SUCCESSFUL call, outstanding carry leaves are discharged and their
    device buffers deleted — donation is SIMULATED on CPU, where jit
    ignores donate_argnums, so a post-dispatch touch of a donated
    argument raises exactly as it would on TPU (`read-after-donate`,
    enforced in vivo while donation itself stays off).
  * `megakernel.discard_carries` — the explicit failure-path discharge;
    its leaves leave the outstanding registry (the fix donorguard's
    `take-without-repark` demands).

A buffer that dies — or is still live at teardown — while OUTSTANDING
was popped and never re-parked, returned, or discarded: the pool's byte
accounting (decremented at take) now lies about real device memory.
Both are violations.

Only the process-wide pool SINGLETON (devicepool._POOL at install time)
is witnessed: test fixtures build isolated pools with synthetic owner
tokens and drop takes deliberately. Host numpy leaves (fresh_carries
placeholders) carry no device buffer — they are skipped explicitly; the
protocol governs device buffers.

Session mode mirrors lock/leak/key/stallwitness: DRUID_TPU_DONOR_WITNESS=1
installs a process-wide singleton from tests/conftest.py and fails the
run on any violation in pytest_unconfigure.

Test-only: nothing in druid_tpu imports this module.
"""
from __future__ import annotations

import threading
import weakref
from typing import Callable, Dict, List, Optional, Tuple

#: process-wide session witness (see session_witness)
_SESSION: Optional["DonorWitness"] = None


def session_witness(root: Optional[str] = None) -> Optional["DonorWitness"]:
    """Process-wide singleton install (same double-conftest rationale as
    lockwitness.session_witness). First call (with `root`) installs;
    later calls return the same witness."""
    global _SESSION
    if _SESSION is None and root is not None:
        _SESSION = DonorWitness(root).install()
    return _SESSION


def end_session_witness() -> Optional["DonorWitness"]:
    """Uninstall and detach the session witness (reporting hook)."""
    global _SESSION
    w, _SESSION = _SESSION, None
    if w is not None:
        w.uninstall()
    return w


def _leaves(value, depth: int = 6) -> List[object]:
    """Array leaves of a pool entry / carry tuple (dtype+shape duck
    type), recursing through the container shapes entries actually use."""
    if depth <= 0:
        return []
    if hasattr(value, "dtype") and hasattr(value, "shape"):
        if type(value).__module__.partition(".")[0] == "numpy":
            return []             # host placeholder: no device buffer
        return [value]
    if isinstance(value, (tuple, list)):
        out: List[object] = []
        for v in value:
            out.extend(_leaves(v, depth - 1))
        return out
    if isinstance(value, dict):
        out = []
        for v in value.values():
            out.extend(_leaves(v, depth - 1))
        return out
    return []


def _describe(leaf) -> str:
    return f"arr({getattr(leaf, 'dtype', '?')}," \
           f"{tuple(getattr(leaf, 'shape', ()))})"


class DonorWitness:
    """Holds observed ownership state for one install()/uninstall() span."""

    def __init__(self, root: str):
        self.root = root
        # reentrant: weakref death callbacks can fire wherever a refcount
        # drops, including on a thread already inside a locked region
        self._meta = threading.RLock()
        #: id(leaf) → (weakref, description, origin key) for popped-but-
        #: not-yet-discharged buffers the caller owes the pool for
        self.outstanding: Dict[int, Tuple[object, str, str]] = {}
        #: id(leaf) → weakref for buffers a pool entry still references
        self.resident: Dict[int, object] = {}
        #: protocol violations (cached-entry donation, post-dispatch
        #: touch via simulated-donation delete, dropped/unreparked takes)
        self.violations: List[str] = []
        #: event counters: takes / reparks / dispatches / discards /
        #: donated leaves deleted
        self.counts: Dict[str, int] = {}
        self._installed = False
        self._saved: List[Tuple[object, str, object]] = []
        #: the production pool singleton captured at install(); accesses
        #: through any OTHER pool instance (test fixtures) are unrecorded
        self._prod_pool: Optional[object] = None

    # ---- registries -----------------------------------------------------
    def _count(self, kind: str) -> None:
        with self._meta:
            self.counts[kind] = self.counts.get(kind, 0) + 1

    def _ref(self, leaf, on_dead: Optional[Callable] = None):
        try:
            return weakref.ref(leaf, on_dead) if on_dead is not None \
                else weakref.ref(leaf)
        except TypeError:
            return None               # weakref-less type: untrackable

    def _note_take(self, value, key: str) -> None:
        self._count("take")
        for leaf in _leaves(value):
            lid = id(leaf)
            desc = _describe(leaf)

            def on_dead(_ref, lid=lid, desc=desc, key=key):
                # the buffer died while the pool was still owed its
                # re-park: ownership was dropped silently, and the pool's
                # byte accounting (decremented at take) now lies
                with self._meta:
                    if self.outstanding.pop(lid, None) is not None:
                        self.violations.append(
                            f"popped buffer {desc} (take of {key}) was "
                            f"garbage-collected while outstanding — no "
                            f"re-park, return, or explicit discard "
                            f"discharged the ownership the take popped")

            ref = self._ref(leaf, on_dead)
            if ref is None:
                continue
            with self._meta:
                self.resident.pop(lid, None)
                self.outstanding[lid] = (ref, desc, key)

    def _note_park(self, value) -> None:
        self._count("repark")
        for leaf in _leaves(value):
            lid = id(leaf)
            with self._meta:
                self.outstanding.pop(lid, None)
            ref = self._ref(leaf)
            if ref is not None:
                with self._meta:
                    self.resident[lid] = ref

    def _discharge(self, value, kind: str) -> None:
        self._count(kind)
        for leaf in _leaves(value):
            with self._meta:
                self.outstanding.pop(id(leaf), None)

    # ---- the donating dispatch -----------------------------------------
    def _before_dispatch(self, carries) -> None:
        self._count("dispatch")
        for leaf in _leaves(carries):
            with self._meta:
                ref = self.resident.get(id(leaf))
                got = ref() if ref is not None else None
                if got is leaf:
                    self.violations.append(
                        f"cached-entry donation: carry leaf "
                        f"{_describe(leaf)} entered a donated position "
                        f"while a pool entry still references it — pop it "
                        f"with take()/device_take() before the dispatch")

    def _after_dispatch(self, carries) -> None:
        """Success path: donation consumed the carries. Discharge the
        ownership and delete the buffers — jit on CPU ignored
        donate_argnums, so deleting here makes any later touch raise
        exactly as the donated-away buffer would on TPU."""
        for leaf in _leaves(carries):
            lid = id(leaf)
            with self._meta:
                owned = self.outstanding.pop(lid, None) is not None
            if not owned:
                continue              # fresh host zeros / caller-owned
            delete = getattr(leaf, "delete", None)
            if delete is None:
                continue
            try:
                delete()
                self._count("donated-delete")
            except Exception:  # druidlint: disable=swallowed-exception
                pass          # already invalidated: the goal holds

    # ---- install/uninstall ---------------------------------------------
    def install(self) -> "DonorWitness":
        if self._installed:
            return self
        witness = self

        from druid_tpu.data import devicepool
        # bind the singleton NOW: fixtures monkeypatch devicepool._POOL to
        # fresh pools, so a call-time re-read would witness those too
        self._prod_pool = devicepool._POOL

        real_take = devicepool.DeviceSegmentPool.take

        def take(pool_self, owner, key):
            value = real_take(pool_self, owner, key)
            if value is not None and pool_self is witness._prod_pool \
                    and witness._installed:
                witness._note_take(value, repr((owner,) + tuple(key)))
            return value

        self._saved.append((devicepool.DeviceSegmentPool, "take", real_take))
        devicepool.DeviceSegmentPool.take = take

        real_gob = devicepool.DeviceSegmentPool.get_or_build

        def get_or_build(pool_self, owner, key, build):
            value = real_gob(pool_self, owner, key, build)
            if pool_self is witness._prod_pool and witness._installed:
                witness._note_park(value)
            return value

        self._saved.append(
            (devicepool.DeviceSegmentPool, "get_or_build", real_gob))
        devicepool.DeviceSegmentPool.get_or_build = get_or_build

        from druid_tpu.engine import grouping, megakernel

        real_builder = grouping._build_device_fn

        def build_device_fn(*args, **kwargs):
            fn = real_builder(*args, **kwargs)

            def dispatched(*fargs, **fkwargs):
                carries = fargs[2] if len(fargs) > 2 else ()
                armed = witness._installed and carries
                if armed:
                    witness._before_dispatch(carries)
                out = fn(*fargs, **fkwargs)
                if armed:
                    witness._after_dispatch(carries)
                return out

            # a pallas-class program is BUILT before it runs
            # (grouping._build_kernel_program lowers it): the wrapper lowers
            # as its product does, or every witnessed build would latch
            # pallas off
            dispatched.lower = fn.lower
            return dispatched

        self._saved.append((grouping, "_build_device_fn", real_builder))
        grouping._build_device_fn = build_device_fn

        real_discard = megakernel.discard_carries

        def discard_carries(carries):
            if witness._installed:
                witness._discharge(carries, "discard")
            return real_discard(carries)

        self._saved.append((megakernel, "discard_carries", real_discard))
        megakernel.discard_carries = discard_carries

        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        # wrapped dispatch callables may live on in _JIT_CACHE entries;
        # they check _installed and pass through once the witness is gone
        self._installed = False
        for obj, attr, original in reversed(self._saved):
            setattr(obj, attr, original)
        self._saved.clear()

    def __enter__(self) -> "DonorWitness":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # ---- reporting ------------------------------------------------------
    def unreparked(self) -> List[str]:
        """Takes still outstanding: buffers the pool is owed at teardown."""
        with self._meta:
            out = []
            for lid, (ref, desc, key) in sorted(self.outstanding.items()):
                if ref() is not None:
                    out.append(
                        f"popped buffer {desc} (take of {key}) still "
                        f"outstanding at teardown — re-park it "
                        f"(device_cached/get_or_build) or discard it "
                        f"explicitly (megakernel.discard_carries)")
            return out

    def all_violations(self) -> List[str]:
        with self._meta:
            live = list(self.violations)
        return live + self.unreparked()

    def summary(self) -> str:
        with self._meta:
            c = self.counts
            n_viol = len(self.violations)
        return (f"{c.get('take', 0)} take(s), {c.get('repark', 0)} "
                f"re-park(s), {c.get('dispatch', 0)} donating "
                f"dispatch(es), {c.get('donated-delete', 0)} donated "
                f"leaf(ves) invalidated, {c.get('discard', 0)} explicit "
                f"discard(s), {n_viol + len(self.unreparked())} "
                f"violation(s)")
