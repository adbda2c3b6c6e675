"""The reduction from a `jax.profiler` trace (`.xplane.pb`) to numbers.

What the chip records (looked at by hand on a v5e, PR 23): one plane per
chip named `/device:TPU:<n>` with the lines `XLA Modules` (one event per
executed program, named `jit_<fn>(<fingerprint>)`) and `XLA Ops` (one event
per HLO operation, named by its whole HLO text), and a `/host:CPU` plane
whose `python` line holds `TraceAnnotation`s. Device and host events share
one clock to within a millisecond or two.

- busy: the union of the `XLA Ops` intervals of a device, clipped to the
  traced window; `busy_s` is its mean over the devices;
- idle share: 1 - busy_s / window_s;
- top operations: seconds by `<module>/<op>` (fingerprint and HLO text cut
  off), mean over the devices;
- idle gaps: the stretches in which NO device ran an operation, each
  labelled by the innermost qtrace span open on the host at its middle, and
  summed by label.

The window is laid on the trace's clock by an anchor: a `TraceAnnotation`
named `ANCHOR` whose wall-clock start the caller recorded.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANCHOR = "bench_anchor"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
NO_REQUEST = "no request in flight"
GAP_PIECE_NS = 20e6


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint union of (start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(a, lo), min(b, hi)


def short_op(name: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` → `fusion.3`."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def short_module(name: str) -> str:
    """`jit_work(1696...)` → `jit_work`."""
    return name.split("(", 1)[0]


def read_planes(path: str) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """{plane: {line: [(event name, start ns, end ns)]}} of an xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Tuple[str, float, float]]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for e in line.events)
    return out


def anchor_ns(planes) -> Optional[float]:
    """Start of the ANCHOR annotation on the trace's clock."""
    for lines in planes.values():
        for events in lines.values():
            for name, start, _end in events:
                if name == ANCHOR:
                    return start
    return None


def label_at(spans: Sequence[dict], wall_ms: float) -> str:
    """Name of the span that covers `wall_ms` and started last."""
    best = None
    for s in spans:
        start, dur = s.get("startMs"), s.get("durationMs")
        if start is None or dur is None:
            continue
        if start <= wall_ms <= start + dur and \
                (best is None or start >= best[0]):
            best = (start, s.get("name") or "?")
    return NO_REQUEST if best is None else best[1]


def reduce(planes, anchor_wall_s: float, wall0_s: float, wall1_s: float,
           spans: Sequence[dict] = (), top: int = 10,
           require_device: bool = True) -> dict:
    """The numbers of one traced window [wall0_s, wall1_s] (wall clock).
    `require_device=False` is for a rehearsal on the CPU, whose trace has no
    device plane: busy is then 0 and no device metric is reported."""
    a_ns = anchor_ns(planes)
    if a_ns is None:
        raise ValueError(f"the trace holds no {ANCHOR!r} annotation")

    def to_ns(wall_s: float) -> float:
        return a_ns + (wall_s - anchor_wall_s) * 1e9

    def to_wall_ms(ns: float) -> float:
        return (anchor_wall_s + (ns - a_ns) / 1e9) * 1000.0

    lo, hi = to_ns(wall0_s), to_ns(wall1_s)
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
    if not devices and require_device:
        raise ValueError(f"the trace holds no device plane: {sorted(planes)}")
    busy_ns: List[float] = []
    op_ns: Dict[str, float] = {}
    every: List[Tuple[float, float]] = []
    for dev in devices:
        modules = sorted((s, e, short_module(n))
                         for n, s, e in planes[dev].get(MODULES_LINE, []))
        clipped = []
        for name, start, end in planes[dev].get(OPS_LINE, []):
            a, b = _clip(start, end, lo, hi)
            if b <= a:
                continue
            clipped.append((a, b))
            module = next((m for s, e, m in modules if s <= start <= e), "?")
            key = f"{module}/{short_op(name)}"
            op_ns[key] = op_ns.get(key, 0.0) + (b - a)
        merged = union(clipped)
        busy_ns.append(sum(b - a for a, b in merged))
        every.extend(merged)
    gaps: Dict[str, float] = {}
    cursor = lo
    for a, b in union(every) + [(hi, hi)]:
        # a long gap is labelled piece by piece: the host moves on inside it
        while a > cursor:
            piece = min(a, cursor + GAP_PIECE_NS)
            label = label_at(spans, to_wall_ms((cursor + piece) / 2.0))
            gaps[label] = gaps.get(label, 0.0) + (piece - cursor)
            cursor = piece
        cursor = max(cursor, b)
    n = max(len(devices), 1)

    def ranked(d: Dict[str, float], scale: float):
        return [[k, v / scale] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"devices": len(devices),
            "window_s": wall1_s - wall0_s,
            "busy_s": sum(busy_ns) / n / 1e9,
            "busy_s_by_device": [b / 1e9 for b in busy_ns],
            "device_ops": ranked(op_ns, n * 1e9),
            "idle_gaps": ranked(gaps, 1e9)}
