"""Per-layer metrics: one small data file each, one vocabulary here.

A file under `layers/` is `{name, unit, layer, moves, source, reduce, ...}`.
`source` picks what is read and `reduce` how the readings of a window become
one number. A reader that finds nothing to read returns None and the metric
is left out of the line. Nothing here imports jax or druid_tpu: spans,
counters and the reduced device trace are handed in.

source (with its parameters)              reads, per request
  span_ms {span}                          summed duration of the spans so named
  span_self_ms {span, minus: [...]}       the span minus its named descendants
  span_attr_sum {span, attr}              summed attribute of the spans so named
  span_count {span}                       how many spans are so named
  client_minus_span {span}                client wall (send → last byte) minus the span
  client_late_ms                          send time minus due time
source (window-wide)
  counter_delta {counter}                 an allow-listed program counter, after minus before
  device_trace {field}                    busy_ms | idle_share | bytes_needed_over_busy

reduce
  median_per_request, p95_per_request     over the requests that have a reading
  sum_per_request                         total over the window / requests answered
  count_in_window                         total over the window
  share_of_window                         a device_trace share, as a percentage
"""
from __future__ import annotations

import json
import math
import os
import statistics
from typing import Dict, List, Optional, Sequence

from benchmark.reference import engine as reference

#: the unpacked width the scan is charged for, per column and row
BYTES_PER_VALUE = 4


def load_layers(root: str) -> Dict[str, dict]:
    """Every layer-metric file under `layers/`, by its name."""
    out = {}
    directory = os.path.join(root, "layers")
    for f in sorted(os.listdir(directory)):
        if f.endswith(".json"):
            with open(os.path.join(directory, f)) as fh:
                spec = json.load(fh)
            if spec["name"] != f[:-5]:
                raise ValueError(f"layers/{f} names itself {spec['name']!r}")
            out[spec["name"]] = spec
    return out


def load_peaks(root: str, device_kind: str) -> dict:
    with open(os.path.join(root, "harness", "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: the table "
                       f"holds {sorted(table)}")
    return table[device_kind]


def bytes_needed(data, query: dict) -> int:
    """Bytes a scan of `query` has to read: rows in its intervals × the
    columns it reads (time included) × 4 B, the unpacked width. A lower
    bound on traffic for a store that keeps 4-byte columns, an upper one for
    a store that packs them — the share built on it says so."""
    return reference.rows_scanned(data, query) * \
        len(reference.columns_read(query)) * BYTES_PER_VALUE


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 1])."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def _descendants(spans: List[dict], root_id: str) -> List[dict]:
    children: Dict[Optional[str], List[dict]] = {}
    for s in spans:
        children.setdefault(s.get("parentId"), []).append(s)
    out, stack = [], [root_id]
    while stack:
        for s in children.get(stack.pop(), ()):
            out.append(s)
            stack.append(s.get("spanId"))
    return out


def _named(spans: List[dict], name: str) -> List[dict]:
    return [s for s in spans
            if s.get("name") == name and s.get("durationMs") is not None]


def _per_request(spec: dict, request: dict) -> Optional[float]:
    """One request's reading, or None when it has none."""
    source = spec["source"]
    rec = request["record"]
    if source == "client_late_ms":
        return (rec["send_s"] - rec["due_s"]) * 1000.0
    spans = request.get("spans")
    if not spans:
        return None
    hits = _named(spans, spec["span"])
    if source == "span_count":
        return float(len(hits))
    if source == "span_attr_sum":
        # a traced request without such a span moved nothing: 0, not unread
        return float(sum(s.get("attrs", {}).get(spec["attr"], 0) or 0
                         for s in hits))
    if not hits:
        return None
    if source == "span_ms":
        return sum(s["durationMs"] for s in hits)
    if source == "client_minus_span":
        return (rec["done_s"] - rec["send_s"]) * 1000.0 - \
            sum(s["durationMs"] for s in hits)
    if source == "span_self_ms":
        total = 0.0
        for s in hits:
            below = _descendants(spans, s["spanId"])
            total += s["durationMs"] - sum(
                d["durationMs"] for d in below
                if d.get("name") in spec["minus"]
                and d.get("durationMs") is not None)
        return total
    raise ValueError(f"{spec['name']}: unknown source {source!r}")


def evaluate(spec: dict, requests: List[dict], counters_before: Dict[str, float],
             counters_after: Dict[str, float], device: Optional[dict]) -> Optional[float]:
    """The metric's value over one window, or None when nothing was read.
    `requests`: the window's answered requests, each {record, spans};
    `device`: the reduced trace with `bytes_needed` and `peak_bytes_per_s`
    beside it, or None when no trace was taken."""
    source, reduce = spec["source"], spec["reduce"]
    if source == "device_trace":
        if device is None or device["busy_s"] <= 0:
            return None
        field = spec["field"]
        if field == "busy_ms":
            return device["busy_s"] * 1000.0
        if field == "idle_share":
            return 100.0 * (1.0 - device["busy_s"] / device["window_s"])
        if field == "bytes_needed_over_busy":
            return 100.0 * device["bytes_needed"] / (
                device["busy_s"] * device["peak_bytes_per_s"])
        raise ValueError(f"{spec['name']}: unknown device field {field!r}")
    if source == "counter_delta":
        key = spec["counter"]
        if key not in counters_before or key not in counters_after:
            return None
        delta = counters_after[key] - counters_before[key]
        if reduce == "sum_per_request":
            return delta / len(requests) if requests else None
        if reduce == "count_in_window":
            return float(delta)
        raise ValueError(f"{spec['name']}: {reduce!r} does not fit a counter")
    readings = [v for v in (_per_request(spec, r) for r in requests)
                if v is not None]
    if not readings:
        return None
    if reduce == "median_per_request":
        return statistics.median(readings)
    if reduce == "p95_per_request":
        return percentile(readings, 0.95)
    if reduce == "sum_per_request":
        return sum(readings) / len(requests)
    if reduce == "count_in_window":
        return float(sum(readings))
    raise ValueError(f"{spec['name']}: unknown reduce {reduce!r}")
