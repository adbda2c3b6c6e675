"""timeseries / topN / groupBy / timeBoundary in plain numpy over RAW columns.

Independent of the code under test: it reads the generator's `.npy` columns
(numbers as drawn, -1 for a null) and the configuration's schema, never a
`Segment`, a dictionary id or any `druid_tpu` result. A dimension's string
is `str(number)`, "" for a null (the configuration's guarantees say so:
null and the empty string are one value, as before Druid 0.13). Everything is exact: counts, long sums
(float64 bincount weights, checked to stay under 2**53), long max/min and
float32 max do not depend on the order of the rows.

The answer has the shape of the native result, so the comparison is `==`
(with the order among topN rows of equal metric left free, see
`harness/verify.py`).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from datetime import datetime, timezone
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

NULL_RAW = -1
HOUR_MS = 3_600_000
DAY_MS = 86_400_000
GRANULARITY_MS = {"hour": HOUR_MS, "day": DAY_MS}
THREADS = 8


def parse_instant(text: str) -> int:
    """ISO-8601 UTC instant → epoch milliseconds."""
    dt = datetime.fromisoformat(text.replace("Z", "+00:00"))
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=timezone.utc)
    return int(round(dt.timestamp() * 1000))


def parse_intervals(intervals: Sequence[str]) -> List[Tuple[int, int]]:
    out = []
    for text in intervals:
        a, b = text.split("/")
        out.append((parse_instant(a), parse_instant(b)))
    return out


class RawData:
    """The raw columns on disk, one directory of `.npy` files a segment,
    memory-mapped."""

    def __init__(self, raw_dir: str, config: dict):
        self.schema = {c["name"]: c for c in config["schema"]}
        self.segments: List[Dict[str, np.ndarray]] = []
        for entry in sorted(os.listdir(raw_dir)):
            d = os.path.join(raw_dir, entry)
            self.segments.append({
                f[:-4]: np.load(os.path.join(d, f), mmap_mode="r")
                for f in sorted(os.listdir(d)) if f.endswith(".npy")})

    def slices(self, intervals: Sequence[Tuple[int, int]]):
        """(segment columns, first row, end row) for every run of rows that
        lies in one of the intervals (time is sorted in a segment)."""
        for cols in self.segments:
            t = cols["__time"]
            for start, end in intervals:
                i0, i1 = np.searchsorted(t, [start, end], side="left")
                if i1 > i0:
                    yield cols, int(i0), int(i1)

    def rows_in(self, intervals: Sequence[Tuple[int, int]]) -> int:
        """Rows in the intervals, before any filter."""
        return sum(i1 - i0 for _c, i0, i1 in self.slices(intervals))

    def time_bounds(self, intervals=None) -> Optional[Tuple[int, int]]:
        if intervals is None:
            intervals = [(-2 ** 62, 2 ** 62)]
        lo, hi = None, None
        for cols, i0, i1 in self.slices(intervals):
            t = cols["__time"]
            lo = int(t[i0]) if lo is None else min(lo, int(t[i0]))
            hi = int(t[i1 - 1]) if hi is None else max(hi, int(t[i1 - 1]))
        return None if lo is None else (lo, hi)


# ---------------------------------------------------------------------------
# Filters
# ---------------------------------------------------------------------------

def _dim_raw(value) -> int:
    """The raw number of a dimension's string value."""
    return NULL_RAW if value in (None, "") else int(value)


def filter_mask(data: RawData, cols, i0: int, i1: int, f: Optional[dict]):
    if f is None:
        return np.ones(i1 - i0, dtype=bool)
    kind = f["type"]
    col = cols[f["dimension"]][i0:i1]
    is_dim = data.schema[f["dimension"]]["kind"] == "string"
    if kind == "selector":
        if not is_dim:
            raise ValueError("selector on a metric is outside this reference")
        return col == _dim_raw(f["value"])
    if kind == "in":
        if not is_dim:
            raise ValueError("in on a metric is outside this reference")
        return np.isin(col, [_dim_raw(v) for v in f["values"]])
    if kind == "bound":
        if is_dim or f.get("ordering") != "numeric":
            raise ValueError("only numeric bounds on a metric are inside "
                             "this reference")
        out = np.ones(i1 - i0, dtype=bool)
        if f.get("lower") is not None:
            lo = float(f["lower"])
            out &= (col > lo) if f.get("lowerStrict") else (col >= lo)
        if f.get("upper") is not None:
            hi = float(f["upper"])
            out &= (col < hi) if f.get("upperStrict") else (col <= hi)
        return out
    raise ValueError(f"filter {kind!r} is outside this reference")


# ---------------------------------------------------------------------------
# Grouped aggregation: per slice, then merged
# ---------------------------------------------------------------------------

class _Acc:
    """One aggregator's running value for every key."""

    #: kind -> (identity, dtype)
    KINDS = {"count": (0, np.int64), "longSum": (0, np.int64),
             "longMax": (np.iinfo(np.int64).min, np.int64),
             "longMin": (np.iinfo(np.int64).max, np.int64),
             "floatMax": (-np.inf, np.float32)}

    def __init__(self, agg: dict, size: int):
        self.agg, self.kind = agg, agg["type"]
        if self.kind not in self.KINDS:
            raise ValueError(f"aggregator {self.kind!r} is outside this "
                             f"reference")
        self.identity, dtype = self.KINDS[self.kind]
        self.value = np.full(size, self.identity, dtype=dtype)

    def partial(self, key, cols, i0, i1, mask, size):
        if self.kind == "count":
            return np.bincount(key, minlength=size)
        vals = cols[self.agg["fieldName"]][i0:i1][mask]
        if self.kind == "longSum":
            v = vals.astype(np.float64)
            if v.size and np.abs(v).max() * v.size >= 2 ** 53:
                raise OverflowError("long sum leaves float64's exact range")
            return np.bincount(key, weights=v, minlength=size).astype(np.int64)
        out = np.full_like(self.value, self.identity)
        if self.kind in ("longMax", "floatMax"):
            np.maximum.at(out, key, vals.astype(out.dtype))
        else:
            np.minimum.at(out, key, vals.astype(out.dtype))
        return out

    def merge(self, part) -> None:
        if self.kind in ("count", "longSum"):
            self.value += part
        elif self.kind in ("longMax", "floatMax"):
            np.maximum(self.value, part, out=self.value)
        else:
            np.minimum(self.value, part, out=self.value)

    def item(self, k: int):
        if self.kind == "floatMax":
            return float(self.value[k])      # float32 widened, as the wire has it
        return int(self.value[k])


def _aggregate(data: RawData, intervals, f, key_fn, size: int, aggs):
    """(rows per key, [one _Acc per aggregator]) over the filtered rows of
    the intervals; `key_fn(cols, i0, i1, mask)` gives each row's key."""
    accs = [_Acc(a, size) for a in aggs]
    rows = np.zeros(size, dtype=np.int64)

    def one(piece):
        cols, i0, i1 = piece
        mask = filter_mask(data, cols, i0, i1, f)
        key = key_fn(cols, i0, i1, mask)
        return (np.bincount(key, minlength=size),
                [a.partial(key, cols, i0, i1, mask, size) for a in accs])

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        for count, parts in pool.map(one, list(data.slices(intervals))):
            rows += count
            for a, p in zip(accs, parts):
                a.merge(p)
    return rows, accs


def _dim_space(data: RawData, dims: Sequence[str]):
    """Per dimension (offset, size) so that raw + offset lies in [0, size):
    the null (-1) and every value of the schema's range have a slot."""
    out = []
    for d in dims:
        spec = data.schema[d]
        if spec["kind"] != "string":
            raise ValueError(f"{d} is not a dimension")
        low = min(int(spec["low"]), NULL_RAW)
        out.append((-low, int(spec["high"]) - low))
    return out


def _dim_key_fn(dims, space):
    def key_fn(cols, i0, i1, mask):
        key = np.zeros(int(mask.sum()), dtype=np.int64)
        for d, (off, size) in zip(dims, space):
            key = key * size + (cols[d][i0:i1][mask].astype(np.int64) + off)
        return key
    return key_fn


def _decode(key: int, space) -> List[str]:
    out = []
    for off, size in reversed(space):
        raw = key % size - off
        out.append("" if raw == NULL_RAW else str(raw))
        key //= size
    return list(reversed(out))


# ---------------------------------------------------------------------------
# The four query types
# ---------------------------------------------------------------------------

def _group_by(data: RawData, q: dict) -> list:
    if q.get("granularity", "all") != "all":
        raise ValueError("groupBy: only granularity all is inside this reference")
    if q.get("limitSpec") or q.get("having") or q.get("postAggregations"):
        raise ValueError("groupBy: limitSpec/having/postAggregations are outside")
    dims = [d if isinstance(d, str) else d["dimension"] for d in q["dimensions"]]
    intervals = parse_intervals(q["intervals"])
    space = _dim_space(data, dims)
    total = 1
    for _off, size in space:
        total *= size
    rows, accs = _aggregate(data, intervals, q.get("filter"),
                            _dim_key_fn(dims, space), total, q["aggregations"])
    timestamp = min(s for s, _e in intervals)
    out = []
    for k in np.flatnonzero(rows).tolist():
        names = _decode(k, space)
        event = dict(zip(dims, names))
        for a in accs:
            event[a.agg["name"]] = a.item(k)
        out.append((names, {"version": "v1", "timestamp": timestamp,
                            "event": event}))
    # the native order of a groupBy without a limitSpec: by the dimension
    # values as strings, first dimension first
    out.sort(key=lambda pair: pair[0])
    return [row for _names, row in out]


def _top_n(data: RawData, q: dict) -> list:
    if q.get("granularity", "all") != "all":
        raise ValueError("topN: only granularity all is inside this reference")
    dim = q["dimension"] if isinstance(q["dimension"], str) \
        else q["dimension"]["dimension"]
    metric = q["metric"] if isinstance(q["metric"], str) else None
    if metric is None:
        raise ValueError("topN: only a numeric metric name is inside")
    intervals = parse_intervals(q["intervals"])
    space = _dim_space(data, [dim])
    rows, accs = _aggregate(data, intervals, q.get("filter"),
                            _dim_key_fn([dim], space), space[0][1],
                            q["aggregations"])
    by = next(a for a in accs if a.agg["name"] == metric)
    present = np.flatnonzero(rows)
    # descending by the metric; among equal metrics the order is free (the
    # comparison treats it so), here by key for a definite answer
    order = present[np.lexsort((present, -by.value[present]))]
    result = []
    for k in order[: int(q["threshold"])].tolist():
        row = {dim: _decode(k, space)[0]}
        for a in accs:
            row[a.agg["name"]] = a.item(k)
        result.append(row)
    if not result:
        return []
    return [{"timestamp": min(s for s, _e in intervals), "result": result}]


def _timeseries(data: RawData, q: dict) -> list:
    intervals = parse_intervals(q["intervals"])
    gran = q.get("granularity", "all")
    if gran == "all":
        start, step, n = min(s for s, _e in intervals), None, 1
    else:
        if len(intervals) != 1:
            raise ValueError("timeseries: one interval with a bucketed "
                             "granularity")
        step = GRANULARITY_MS[gran]
        start = intervals[0][0] // step * step
        n = -(-(intervals[0][1] - start) // step)

    def key_fn(cols, i0, i1, mask):
        if step is None:
            return np.zeros(int(mask.sum()), dtype=np.int64)
        return (cols["__time"][i0:i1][mask] - start) // step

    _rows, accs = _aggregate(data, intervals, q.get("filter"), key_fn, n,
                             q["aggregations"])
    # every bucket of the interval is answered, an empty one with zeros
    # (skipEmptyBuckets is false by default)
    for a in accs:
        if a.kind not in ("count", "longSum"):
            raise ValueError("timeseries: an empty bucket's max/min is "
                             "outside this reference")
    return [{"timestamp": start + j * (step or 0),
             "result": {a.agg["name"]: a.item(j) for a in accs}}
            for j in range(n)]


def _time_boundary(data: RawData, q: dict) -> list:
    if q.get("filter") or q.get("bound"):
        raise ValueError("timeBoundary: filter/bound are outside this reference")
    intervals = parse_intervals(q["intervals"]) if q.get("intervals") else None
    got = data.time_bounds(intervals)
    if got is None:
        return []
    lo, hi = got
    return [{"timestamp": lo, "result": {"minTime": lo, "maxTime": hi}}]


_TYPES = {"groupBy": _group_by, "topN": _top_n, "timeseries": _timeseries,
          "timeBoundary": _time_boundary}


def answer(data: RawData, query: dict) -> list:
    """The native result of `query` over the raw columns."""
    try:
        fn = _TYPES[query["queryType"]]
    except KeyError:
        raise ValueError(f"query type {query.get('queryType')!r} is outside "
                         f"this reference") from None
    return fn(data, query)


def rows_scanned(data: RawData, query: dict) -> int:
    """Rows in the query's intervals, before the filter (all rows for a
    query without intervals)."""
    if not query.get("intervals"):
        return sum(len(c["__time"]) for c in data.segments)
    return data.rows_in(parse_intervals(query["intervals"]))


def columns_read(query: dict) -> List[str]:
    """The columns a scan of this query has to read, `__time` included;
    empty for a query answered from metadata."""
    if query["queryType"] == "timeBoundary":
        return []
    names = {"__time"}
    for d in query.get("dimensions", []) + ([query["dimension"]]
                                            if "dimension" in query else []):
        names.add(d if isinstance(d, str) else d["dimension"])
    for a in query.get("aggregations", []):
        if "fieldName" in a:
            names.add(a["fieldName"])

    if query.get("filter"):
        names.add(query["filter"]["dimension"])
    return sorted(names)
