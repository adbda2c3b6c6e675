"""chip_smoke.py — the quickest proof that the served query path runs on the chip.

    python3 chip_smoke.py [--rows N] [--seed S] [--mesh]

One process, and the only one that touches JAX. It refuses to start without
a TPU (non-zero exit, no result line), then drives the path a user drives:

    headline dataset (headline_segments below, 100M rows / 8 segments,
    4 columns + time, generated from --seed)
      -> persisted with the default (V2) segment writer
      -> cli.build_historical(segments_dir=...)      mmap load, H2D staging
      -> cli.build_broker([historical url])          same process
      -> native JSON POSTed to /druid/v2 from a plain urllib client

Four queries, each sent twice with a changed filter literal (so the repeat
reuses the compiled program but no segment or result cache can answer it):
the headline groupBy, the same two dimensions under an `in` filter with
count/longSum/longMin, the headline topN, an hourly timeseries. Every answer
must equal a plain numpy computation over the same host columns EXACTLY
(count, long sum, long min and float max are order-independent), and every
request must show — from its own /druid/v2/trace/<queryId> — that the device
ran it with the expected strategy: `pallas` and `megakernel` through Mosaic
on one chip, one sharded program per query with --mesh. A Pallas latch, a
missing dispatch, an undonated megakernel repeat or a wrong answer is a
non-zero exit that prints what ran instead.

--mesh serves the same segments from ONE historical over all local chips
(DataNode(mesh=make_mesh())) and also proves the stacked bytes are spread
over every device. The last stdout line is the result object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import sys
import tempfile
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

HEADLINE_ROWS = 100_000_000
HEADLINE_SEED = 1234
SEGMENTS = 8
#: a cold first request pays projection sorts, H2D staging and compiles for
#: eight 12.5M-row segments — well past the client's default 300 s
QUERY_TIMEOUT_MS = 900_000
REPEATS = 2


class SmokeFailure(Exception):
    """A phase of the smoke failed; the message says what ran instead."""


def log(msg: str) -> None:
    print(msg, flush=True)


def headline_segments(rows: int, n_segments: int, seed: int = HEADLINE_SEED):
    """The headline dataset: one day, `rows` rows over `n_segments` segments,
    two string dimensions and two metrics, made from `seed`."""
    from druid_tpu.data.generator import ColumnSpec, DataGenerator
    from druid_tpu.utils.intervals import Interval
    schema = (
        ColumnSpec("dimA", "string", cardinality=100, distribution="uniform"),
        ColumnSpec("dimB", "string", cardinality=1000, distribution="zipf"),
        ColumnSpec("metLong", "long", low=0, high=10_000),
        ColumnSpec("metFloat", "float", distribution="normal", mean=100.0,
                   std=25.0),
    )
    return DataGenerator(schema, seed=seed).segments(
        n_segments, rows // n_segments,
        Interval.of("2026-01-01", "2026-01-02"), datasource="bench")


def host_rss_bytes() -> int:
    """This process's resident set right now (the machine with the chip
    ends a command that uses up its host memory)."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def require_tpu() -> dict:
    """The device as JAX reports it, or SmokeFailure when it is no TPU.
    Runs before any data exists: a CPU run must fail fast, not after
    minutes of generation."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SmokeFailure(f"no accelerator: JAX found {device}")
    return device


# ---------------------------------------------------------------------------
# What the device must have done, per query
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Expect:
    """The device-dispatch spans one request's trace must carry."""
    span: str                 # span name of the dispatches that did the work
    strategy: str             # strategy every one of them must name
    dispatches: int           # how many of them
    donates: bool = False     # repeats must hand carry buffers back donated


#: meshless historical, 12.5M-row segments: every query runs one program
#: per segment (above BATCH_MAX_SEGMENT_ROWS nothing batches)
ONE_CHIP = {
    "groupBy": Expect("engine/dispatch", "pallas", SEGMENTS),
    "groupByIn": Expect("engine/dispatch", "megakernel", SEGMENTS,
                        donates=True),
    "topN": Expect("engine/dispatch", "mm", SEGMENTS),
    "timeseries": Expect("engine/dispatch", "blocked", SEGMENTS),
}

#: one historical over a device mesh: ONE sharded program per query. Sorted
#: projections are per-segment layouts a stacked program cannot share, so
#: the 100k-group queries run the stacked XLA scatter.
MESH = {
    "groupBy": Expect("engine/sharded/dispatch", "mixed", 1),
    "groupByIn": Expect("engine/sharded/dispatch", "mixed", 1),
    "topN": Expect("engine/sharded/dispatch", "mm", 1),
    "timeseries": Expect("engine/sharded/dispatch", "blocked", 1),
}


# ---------------------------------------------------------------------------
# The plain reference: numpy over the generated host columns
# ---------------------------------------------------------------------------

HOUR_MS = 3_600_000


class HostColumns:
    """Every segment's columns concatenated, dimension ids unified over ONE
    value list per dimension — the reference never reads a dictionary id
    the engine chose. Kept narrow (int32 ids and longs, an hour index for
    time): at 100M rows these stay resident beside the served data on a
    40 GiB host."""

    def __init__(self, segments):
        import numpy as np

        from druid_tpu.utils.intervals import Interval
        self.interval = Interval(min(s.interval.start for s in segments),
                                 max(s.interval.end for s in segments))
        self.values: Dict[str, "np.ndarray"] = {}
        self.ids: Dict[str, "np.ndarray"] = {}
        for dim in ("dimA", "dimB"):
            per_seg = [np.asarray(s.dims[dim].dictionary.values)
                       for s in segments]
            self.values[dim] = np.unique(np.concatenate(per_seg))
            self.ids[dim] = np.concatenate(
                [np.searchsorted(self.values[dim], vals)
                 .astype(np.int32)[s.dims[dim].ids]
                 for s, vals in zip(segments, per_seg)])
        met_long = [s.metrics["metLong"].values for s in segments]
        assert all(np.abs(m).max() < 2 ** 31 for m in met_long)
        self.met_long = np.concatenate([m.astype(np.int32)
                                        for m in met_long])
        self.met_float = np.concatenate(
            [s.metrics["metFloat"].values for s in segments])
        self.hour = np.concatenate(
            [((s.time_ms - self.interval.start) // HOUR_MS).astype(np.int16)
             for s in segments])
        self.rows = int(self.hour.shape[0])
        # long sums ride float64 bincount weights: exact below 2^53
        assert self.rows * int(np.abs(self.met_long).max()) < 2 ** 53

    def in_mask(self, dim: str, values):
        import numpy as np
        return np.isin(self.ids[dim],
                       np.searchsorted(self.values[dim], list(values)))

    def fused_keys(self, dims, mask):
        """Fused dimension key of every masked row over `dims`."""
        import numpy as np
        key = np.zeros(int(mask.sum()), dtype=np.int64)
        for d in dims:
            key = key * len(self.values[d]) + self.ids[d][mask]
        return key

    def decode(self, dims, fused):
        """Fused keys -> one array of dimension values per dimension."""
        out = []
        for d in reversed(dims):
            out.append(self.values[d][fused % len(self.values[d])])
            fused = fused // len(self.values[d])
        return tuple(reversed(out))


def _group_reduce(h: HostColumns, dims, mask, aggs) -> Dict[tuple, tuple]:
    """{dimension values: aggregate tuple} over the masked rows; `aggs` is
    a sequence of ("count" | "lsum" | "lmin" | "fmax")."""
    import numpy as np
    size = 1
    for d in dims:
        size *= len(h.values[d])
    k = h.fused_keys(dims, mask)
    count = np.bincount(k, minlength=size)
    cols = []
    for a in aggs:
        if a == "count":
            cols.append(count)
        elif a == "lsum":
            cols.append(np.bincount(k, weights=h.met_long[mask],
                                    minlength=size).astype(np.int64))
        elif a == "lmin":
            out = np.full(size, np.iinfo(np.int32).max, dtype=np.int32)
            np.minimum.at(out, k, h.met_long[mask])
            cols.append(out)
        elif a == "fmax":
            out = np.full(size, -np.inf, dtype=np.float32)
            np.maximum.at(out, k, h.met_float[mask])
            cols.append(out)
        else:
            raise ValueError(a)
    present = np.flatnonzero(count)
    names = h.decode(dims, present)
    return {tuple(str(n[i]) for n in names):
            tuple(c[g].item() for c in cols)
            for i, g in enumerate(present)}


# ---------------------------------------------------------------------------
# The four queries: native JSON + reference, parameterised by the literal
# ---------------------------------------------------------------------------

@dataclass
class QuerySpec:
    name: str
    json: Callable[[int], dict]          # repeat index -> native query
    check: Callable[[int, list], None]   # repeat index, answer -> raises


def make_queries(h: HostColumns, datasource: str) -> List[QuerySpec]:
    import numpy as np
    interval = [str(h.interval)]
    a_vals = [str(v) for v in h.values["dimA"]]
    count = {"type": "count", "name": "rows"}
    lsum = {"type": "longSum", "name": "lsum", "fieldName": "metLong"}

    def differ(what, got, want):
        if got != want:
            keys = [k for k in want if got.get(k) != want[k]] + \
                [k for k in got if k not in want]
            k = keys[0]
            raise SmokeFailure(
                f"{what}: {len(keys)} group(s) differ from the numpy "
                f"reference (got {len(got)}, want {len(want)}); first "
                f"{k}: got {got.get(k)} want {want.get(k)}")

    # 1. the headline groupBy: 2 dims / 3 aggs / numeric bound filter
    def gb_json(i):
        return {"queryType": "groupBy", "dataSource": datasource,
                "intervals": interval, "granularity": "all",
                "dimensions": ["dimA", "dimB"],
                "aggregations": [count, lsum,
                                 {"type": "floatMax", "name": "fmax",
                                  "fieldName": "metFloat"}],
                "filter": {"type": "bound", "dimension": "metLong",
                           "lower": 100 + i, "upper": 9_900,
                           "ordering": "numeric"}}

    def gb_check(i, rows):
        mask = (h.met_long >= 100 + i) & (h.met_long <= 9_900)
        want = _group_reduce(h, ("dimA", "dimB"), mask,
                             ("count", "lsum", "fmax"))
        got = {(r["event"]["dimA"], r["event"]["dimB"]):
               (r["event"]["rows"], r["event"]["lsum"], r["event"]["fmax"])
               for r in rows}
        differ("groupBy", got, want)

    # 2. same dims under an `in` filter, count/longSum/longMin (the
    #    bitmap filter fuses into the kernel: strategy megakernel)
    def in_values(i):
        return a_vals[i: i + len(a_vals) // 2]

    def gbin_json(i):
        return {"queryType": "groupBy", "dataSource": datasource,
                "intervals": interval, "granularity": "all",
                "dimensions": ["dimA", "dimB"],
                "aggregations": [count, lsum,
                                 {"type": "longMin", "name": "lmin",
                                  "fieldName": "metLong"}],
                "filter": {"type": "in", "dimension": "dimA",
                           "values": in_values(i)}}

    def gbin_check(i, rows):
        want = _group_reduce(h, ("dimA", "dimB"),
                             h.in_mask("dimA", in_values(i)),
                             ("count", "lsum", "lmin"))
        got = {(r["event"]["dimA"], r["event"]["dimB"]):
               (r["event"]["rows"], r["event"]["lsum"], r["event"]["lmin"])
               for r in rows}
        differ("groupByIn", got, want)

    # 3. the headline topN: dimB by lsum, top 100, `in` filter on dimA
    threshold = 100

    def tn_values(i):
        return a_vals[i::2]

    def tn_json(i):
        return {"queryType": "topN", "dataSource": datasource,
                "intervals": interval, "granularity": "all",
                "dimension": "dimB", "metric": "lsum",
                "threshold": threshold, "aggregations": [count, lsum],
                "filter": {"type": "in", "dimension": "dimA",
                           "values": tn_values(i)}}

    def tn_check(i, rows):
        all_groups = _group_reduce(h, ("dimB",),
                                   h.in_mask("dimA", tn_values(i)),
                                   ("count", "lsum"))
        if len(rows) != 1:
            raise SmokeFailure(f"topN: {len(rows)} result rows, want 1")
        got = rows[0]["result"]
        # every returned entry is exact, and the returned lsums are exactly
        # the reference's top `threshold` (order among equal sums is free)
        for r in got:
            if all_groups.get((r["dimB"],)) != (r["rows"], r["lsum"]):
                raise SmokeFailure(
                    f"topN: {r} differs from the numpy reference "
                    f"{all_groups.get((r['dimB'],))}")
        want_sums = sorted((v[1] for v in all_groups.values()),
                           reverse=True)[:threshold]
        if [r["lsum"] for r in got] != want_sums:
            raise SmokeFailure("topN: returned sums are not the numpy "
                               "reference's top sums in descending order")

    # 4. hourly timeseries count + longSum
    def ts_json(i):
        return {"queryType": "timeseries", "dataSource": datasource,
                "intervals": interval, "granularity": "hour",
                "aggregations": [count, lsum],
                "filter": {"type": "bound", "dimension": "metLong",
                           "lower": 100 + i, "ordering": "numeric"}}

    def ts_check(i, rows):
        mask = h.met_long >= 100 + i
        n_buckets = -(-(h.interval.end - h.interval.start) // HOUR_MS)
        b = h.hour[mask]
        cnt = np.bincount(b, minlength=n_buckets)
        sums = np.bincount(b, weights=h.met_long[mask],
                           minlength=n_buckets).astype(np.int64)
        want = {h.interval.start + j * HOUR_MS: (int(cnt[j]), int(sums[j]))
                for j in range(n_buckets)}
        got = {r["timestamp"]: (r["result"]["rows"], r["result"]["lsum"])
               for r in rows}
        differ("timeseries", got, want)

    return [QuerySpec("groupBy", gb_json, gb_check),
            QuerySpec("groupByIn", gbin_json, gbin_check),
            QuerySpec("topN", tn_json, tn_check),
            QuerySpec("timeseries", ts_json, ts_check)]


# ---------------------------------------------------------------------------
# Persist, serve, ask
# ---------------------------------------------------------------------------

def persist_segments(segments, directory: str) -> int:
    """Write every segment with the default writer, one directory each;
    returns bytes on disk. Threads: the writer spends its time in numpy
    and the native codec, both of which release the interpreter lock."""
    from druid_tpu.storage.format_v2 import persist_segment_auto
    os.makedirs(directory, exist_ok=True)
    with ThreadPoolExecutor(max_workers=min(len(segments),
                                            os.cpu_count() or 1)) as pool:
        futures = [pool.submit(persist_segment_auto, s,
                               os.path.join(directory, f"segment_{i:03d}"))
                   for i, s in enumerate(segments)]
        return sum(f.result() for f in futures)


def _http_json(url: str, body: Optional[dict] = None, timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"{url} answered {e.code}: {e.read()[:600]!r}") from None


def ask(base_url: str, spec: QuerySpec, repeat: int, expect: Expect,
        run_id: str) -> dict:
    """One request end to end: POST, compare with the reference, then hold
    the request to what its own trace says the device did."""
    from druid_tpu.engine import megakernel, pallas_agg
    from druid_tpu.obs import dispatch

    qid = f"smoke-{run_id}-{spec.name}-{repeat}"
    query = dict(spec.json(repeat),
                 context={"queryId": qid, "timeout": QUERY_TIMEOUT_MS})
    dispatches_before = dispatch.count()
    donated_before = megakernel.stats().snapshot()["donatedBytes"]
    t0 = time.monotonic()
    rows = _http_json(f"{base_url}/druid/v2", query,
                      timeout=QUERY_TIMEOUT_MS / 1000.0)
    wall_s = time.monotonic() - t0
    spec.check(repeat, rows)

    trace = _http_json(f"{base_url}/druid/v2/trace/{qid}")
    device_spans = [s for s in trace["spans"]
                    if s["name"].endswith("/dispatch")
                    and s["name"].startswith("engine/")]
    ran = sorted({(s["name"], s["attrs"].get("strategy"))
                  for s in device_spans})
    record = {
        "query": spec.name, "repeat": repeat, "wall_s": round(wall_s, 3),
        "result_rows": len(rows[0]["result"]) if spec.name == "topN"
        else len(rows),
        "ran": ran, "spans": len(device_spans),
        "compiles": sum(1 for s in trace["spans"]
                        if s["name"] == "engine/compile"),
        "dispatches": dispatch.count() - dispatches_before,
        "donated_bytes": megakernel.stats().snapshot()["donatedBytes"]
        - donated_before,
        "host_rss_gb": round(host_rss_bytes() / 2 ** 30, 1),
    }
    log(f"request {json.dumps(record)}")

    if pallas_agg.broken_reason() is not None:
        raise SmokeFailure(f"{qid}: Pallas latched off — "
                           f"{pallas_agg.broken_reason()}")
    if record["dispatches"] <= 0:
        raise SmokeFailure(f"{qid}: the dispatch count did not rise — no "
                           f"device program ran for this request")
    if ran != [(expect.span, expect.strategy)]:
        raise SmokeFailure(f"{qid}: expected every device dispatch to be "
                           f"{(expect.span, expect.strategy)}, trace says "
                           f"{ran}")
    if record["spans"] != expect.dispatches:
        raise SmokeFailure(f"{qid}: expected {expect.dispatches} "
                           f"{expect.span} span(s), trace has "
                           f"{record['spans']}")
    if repeat > 0 and record["compiles"]:
        raise SmokeFailure(f"{qid}: the repeat compiled "
                           f"{record['compiles']} program(s) — a changed "
                           f"filter literal must reuse the program")
    if repeat > 0 and expect.donates and record["donated_bytes"] <= 0:
        raise SmokeFailure(f"{qid}: the megakernel repeat donated no "
                           f"carry bytes")
    return record


def sharded_bytes_by_device(mesh) -> Dict[str, int]:
    """Bytes each mesh device holds of the live arrays laid out over THIS
    mesh — the stacked segment shards; single-device clutter (aux
    constants, another node's blocks) is not counted."""
    import jax
    out: Dict[str, int] = {str(d): 0 for d in mesh.devices.flat}
    for a in jax.live_arrays():
        if getattr(a.sharding, "mesh", None) != mesh:
            continue
        for shard in a.addressable_shards:
            out[str(shard.device)] += int(shard.data.nbytes)
    return out


def run(segments, workdir: str, expectations: Dict[str, Expect],
        mesh=None, run_id: str = "0") -> dict:
    """Everything after data generation: reference columns, persist, serve,
    ask. Raises SmokeFailure on any miss; returns the run's facts.
    CONSUMES `segments` (a list, emptied once persisted): at 100M rows the
    generated copy must not stay resident beside the served one."""
    import jax

    from druid_tpu import cli
    from druid_tpu.data.devicepool import device_pool

    t0 = time.monotonic()
    host = HostColumns(segments)
    queries = make_queries(host, segments[0].id.datasource)
    seg_dir = os.path.join(workdir, "segments")
    disk_bytes = persist_segments(segments, seg_dir)
    n_segments = len(segments)
    log(f"persisted {n_segments} segment(s), {host.rows:,} rows, "
        f"{disk_bytes:,} bytes on disk ({time.monotonic() - t0:.1f}s with "
        f"the reference columns; host rss "
        f"{host_rss_bytes() / 2 ** 30:.1f} GiB)")
    # the generated segments have served their purpose: the reference
    # holds its own columns and the historical loads from disk
    segments.clear()

    servers = []
    try:
        t0 = time.monotonic()
        node, historical, loaded = cli.build_historical(
            "smoke-historical", segments_dir=seg_dir, port=0, mesh=mesh)
        servers.append(historical)
        if loaded != n_segments:
            raise SmokeFailure(f"historical loaded {loaded} of "
                               f"{n_segments} segments")
        _view, broker, http = cli.build_broker([historical.url], port=0)
        servers += [http, broker]
        base_url = f"http://127.0.0.1:{http.port}"
        log(f"serving: historical {historical.url} ({loaded} segments "
            f"mmap-loaded), broker {base_url} "
            f"({time.monotonic() - t0:.1f}s)")

        records = []
        for spec in queries:
            for repeat in range(REPEATS):
                records.append(ask(base_url, spec, repeat,
                                   expectations[spec.name], run_id))
    finally:
        for s in reversed(servers):
            s.stop()

    facts = {
        "rows": host.rows,
        "requests": records,
        "host_peak_rss_bytes":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024,
        "pool_resident_bytes": device_pool().snapshot().resident_bytes,
        "peak_bytes_in_use": {
            str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()},
    }
    if mesh is not None:
        by_device = sharded_bytes_by_device(mesh)
        facts["sharded_bytes"] = by_device
        total = max(sum(by_device.values()), 1)
        share = {d: round(b / total, 3) for d, b in by_device.items()}
        log(f"sharded bytes by device: {by_device} (shares {share})")
        fair = 1.0 / len(by_device)
        if min(share.values()) < fair / 2 or max(share.values()) > fair * 2:
            raise SmokeFailure(f"stacked bytes are not spread over the "
                               f"mesh: shares {share}")
    return facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=HEADLINE_ROWS,
                    help="total rows; a cut of scale only — the widths, "
                         "cardinalities and the eight segments stay")
    ap.add_argument("--seed", type=int, default=None,
                    help="data seed (default: the headline's)")
    ap.add_argument("--mesh", action="store_true",
                    help="one historical over ALL local chips")
    args = ap.parse_args(argv)

    t_start = time.monotonic()
    try:
        device = require_tpu()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr, flush=True)
        return 2
    # the repo before the first stdout line: run alone, without the
    # program, this script fails here having printed nothing
    from druid_tpu import native
    # the pure-python LZ4 fallback at this scale looks like a hang
    native.require()
    log(f"device: {json.dumps(device)}")

    if args.rows != HEADLINE_ROWS:
        log(f"ROW CUT: {args.rows:,} rows instead of the headline's "
            f"{HEADLINE_ROWS:,} (widths, cardinalities and the "
            f"{SEGMENTS} segments unchanged)")
    t0 = time.monotonic()
    seed = HEADLINE_SEED if args.seed is None else args.seed
    segments = headline_segments(args.rows, SEGMENTS, seed=seed)
    log(f"generated {sum(s.n_rows for s in segments):,} rows in "
        f"{len(segments)} segments from seed {seed} "
        f"({time.monotonic() - t0:.1f}s)")

    mesh = None
    if args.mesh:
        from druid_tpu.parallel import make_mesh
        mesh = make_mesh()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        facts = run(segments, workdir, MESH if args.mesh else ONE_CHIP,
                    mesh=mesh, run_id=str(os.getpid()))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    log(f"summary: {json.dumps({k: v for k, v in facts.items() if k != 'requests'})}")
    log(f"wall: {time.monotonic() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
