"""Catalog of every DRUID_TPU_* environment flag.

One declaration per flag: default, latch-vs-live semantics, and a doc
line. The scattered ``os.environ`` reads across engine/, data/ and
storage/ stay where they are — locality matters for the latches — but
each read must name a flag declared here. Two consumers parse this
module WITHOUT importing it (the ``FLAGS`` literal is kept statically
evaluable for that reason — string keys, ``Flag(...)`` values with
constant arguments only):

  * druidlint's `flag-name` rule rejects any ``os.environ`` read of a
    ``DRUID_TPU_*`` name not declared here (typo guard, the
    `metric-name` pattern), and keyguard's `env-flag-latch` rule uses
    the ``semantics`` field to decide whether an in-function read of a
    flag can alias a cached program.
  * tests regenerate the README flags table from
    :func:`flags_table_markdown` and diff it against the committed one.

Semantics vocabulary:

  * ``latch`` — read once at import/process start into a module global
    (possibly overridable later through an explicit setter, which is a
    deliberate API call, not an aliasing hazard). A latch read inside a
    plan/build function would let a mid-process flip alias a cached
    program, so keyguard flags it.
  * ``live`` — consulted at call time by design. A live flag read in
    plan/build code must be a key member (``key_member=True``) or be
    provably trace-irrelevant (capacity bounds, persistence format
    bytes), which the catalog documents per flag.
"""
from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Flag", "FLAGS", "flags_table_markdown"]


@dataclass(frozen=True)
class Flag:
    default: str
    semantics: str            # "latch" | "live"
    doc: str
    #: live flags only: the read's effect joins every cache/plan key
    #: (so a mid-process flip cannot alias a cached program)
    key_member: bool = False

    def __post_init__(self):
        if self.semantics not in ("latch", "live"):
            raise ValueError(f"unknown semantics {self.semantics!r}")


#: every DRUID_TPU_* flag the package reads, keyed by full env name.
#: Keep this a plain dict literal of Flag(...) calls with constant
#: arguments — druidlint and keyguard evaluate it by AST, not import.
FLAGS = {
    "DRUID_TPU_BATCH": Flag(
        default="1", semantics="latch",
        doc="Cross-segment batching opt-out; 0 restores per-segment "
            "dispatch (engine/batching.py)."),
    "DRUID_TPU_CASCADE": Flag(
        default="1", semantics="latch",
        doc="Cascaded-encoding execution opt-out; 0 decodes to flat "
            "codes at staging time (data/cascade.py)."),
    "DRUID_TPU_DEVICE_BITMAP": Flag(
        default="1", semantics="latch",
        doc="Device-side filter bitmap construction opt-out "
            "(engine/filters.py)."),
    "DRUID_TPU_DEVICE_POOL_BYTES": Flag(
        default="", semantics="live",
        doc="Device segment pool budget override in bytes. Capacity "
            "bound only — never a trace input (data/devicepool.py)."),
    "DRUID_TPU_DONATE": Flag(
        default="auto", semantics="live", key_member=True,
        doc="Carry-buffer donation tri-state: 'on' forces "
            "donate_argnums (the real-TPU bench lever), 'off' disables "
            "it, 'auto' detects by backend. Live by design — the "
            "decision joins the jit program signature's mk= field "
            "(engine/contracts.py donation_supported, "
            "engine/grouping.py)."),
    "DRUID_TPU_DONOR_WITNESS": Flag(
        default="", semantics="latch",
        doc="Test-only: 1 arms the suite-wide donation/ownership "
            "witness (tools/druidlint/donorwitness.py) from "
            "tests/conftest.py — pool takes, donating dispatches and "
            "re-parks are tracked by array identity, and a cached-entry "
            "donation, post-dispatch touch of a donated argument, or "
            "un-reparked take at teardown fails the session."),
    "DRUID_TPU_LZ4": Flag(
        default="device", semantics="latch",
        doc="LZ4 frame handling: device decode (default) or 'host' "
            "staging comparison fallback (data/cascade.py)."),
    "DRUID_TPU_MEGAKERNEL": Flag(
        default="1", semantics="latch",
        doc="Fused megakernel path opt-out (engine/megakernel.py)."),
    "DRUID_TPU_PACKED": Flag(
        default="1", semantics="latch",
        doc="Bit-packed column staging opt-out (data/packed.py)."),
    "DRUID_TPU_PALLAS": Flag(
        default="", semantics="live", key_member=True,
        doc="Pallas kernel mode: 0 disables, 'interpret' forces "
            "interpreter mode. Live by design — availability is probed "
            "per build and the chosen strategy joins the plan "
            "signature's strat= field (engine/pallas_agg.py)."),
    "DRUID_TPU_SEGMENT_FORMAT": Flag(
        default="", semantics="live",
        doc="Segment writer format pin: 1 pins the V1 writer. Live by "
            "design — the chosen version is persisted as the format "
            "byte readers negotiate on, never a trace input "
            "(storage/format_v2.py)."),
    "DRUID_TPU_STALL_WITNESS": Flag(
        default="", semantics="latch",
        doc="Test-only: 1 arms the suite-wide stall witness "
            "(tools/druidlint/stallwitness.py) from tests/conftest.py — "
            "every blocking park issued from a druid_tpu call site is "
            "timed, and an untimed park outside a shutdown scope fails "
            "the session."),
    "DRUID_TPU_STANDING": Flag(
        default="1", semantics="latch",
        doc="Standing-query incremental maintenance opt-out; 0 "
            "restores re-scan on every tick (engine/standing.py)."),
    "DRUID_TPU_UNIDIM_TTL_S": Flag(
        default="900", semantics="latch",
        doc="Unidimensional result-cache TTL in seconds; <= 0 "
            "disables expiry (engine/engines.py)."),
}


def flags_table_markdown() -> str:
    """The README flags table, generated so it cannot drift from the
    catalog (tests diff this against the committed README section)."""
    lines = ["| Flag | Default | Semantics | Description |",
             "| --- | --- | --- | --- |"]
    for name in sorted(FLAGS):
        f = FLAGS[name]
        sem = f.semantics + (" (key member)" if f.key_member else "")
        default = f"`{f.default}`" if f.default else "(unset)"
        lines.append(f"| `{name}` | {default} | {sem} | {f.doc} |")
    return "\n".join(lines) + "\n"
