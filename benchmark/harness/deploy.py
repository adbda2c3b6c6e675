"""Deployment: data on disk, one historical and one broker in this process.

The only module of the benchmark, with `run.py`, that imports `jax` or
`druid_tpu`. The steps are `chip_smoke.py`'s (persist V2 →
`cli.build_historical` → `cli.build_broker` → HTTP), taken as a copy so that
a later PR cannot change the yardstick by changing the smoke.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.datagen import basic

DATASOURCE = "basic"


class BenchFailure(Exception):
    """The benchmark cannot give a result; the message says why."""


def host_rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; BenchFailure when it is no TPU or holds
    fewer chips than the cell asks for. Runs before any data exists."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise BenchFailure(f"no accelerator: JAX found {device}")
    if device["count"] < chips:
        raise BenchFailure(f"the cell needs {chips} chip(s): JAX found {device}")
    return device


# ---------------------------------------------------------------------------
# Data: raw columns for the reference, V2 segments for the program
# ---------------------------------------------------------------------------

def data_signature(config: dict, seed: int) -> str:
    """What the files on disk depend on: the seed and the data-shaping keys
    of the configuration (not its name, chips or mesh, so two
    configurations over the same data could share — they do not, each
    keeps its own directory, but a stale directory is never reused)."""
    shape = {k: config[k] for k in ("schema", "segments", "rows_per_segment",
                                    "first_day_ms")}
    blob = json.dumps({"seed": int(seed), "shape": shape}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


def build_segment(config: dict, index: int, cols: Dict[str, np.ndarray]):
    """Raw columns → a `Segment`, through the constructors
    `DataGenerator.segment` itself uses. A dimension's dictionary is the
    lexicographically sorted strings of the values present."""
    from druid_tpu.data.dictionary import Dictionary
    from druid_tpu.data.segment import (NumericColumn, Segment, SegmentId,
                                        StringDimColumn, ValueType)
    from druid_tpu.utils.intervals import Interval
    dims, metrics = {}, {}
    for spec in config["schema"]:
        raw = cols[spec["name"]]
        if spec["kind"] == "string":
            base = int(raw.min())
            present = np.flatnonzero(np.bincount(raw.astype(np.int64) - base))
            strings = ["" if v + base == basic.NULL_RAW else str(v + base)
                       for v in present.tolist()]
            order = np.argsort(np.asarray(strings, dtype=object), kind="stable")
            lut = np.zeros(int(present.max()) + 1, dtype=np.int32)
            lut[present[order]] = np.arange(len(order), dtype=np.int32)
            dims[spec["name"]] = StringDimColumn(
                lut[raw.astype(np.int64) - base],
                Dictionary([strings[i] for i in order]))
        elif spec["kind"] == "long":
            metrics[spec["name"]] = NumericColumn(raw.astype(np.int64),
                                                  ValueType.LONG)
        elif spec["kind"] == "float":
            metrics[spec["name"]] = NumericColumn(raw.astype(np.float32),
                                                  ValueType.FLOAT)
        else:
            raise BenchFailure(f"unknown column kind {spec['kind']!r}")
    start = basic.segment_start_ms(config, index)
    sid = SegmentId(DATASOURCE, Interval(start, start + basic.DAY_MS), "v1", 0)
    return Segment(sid, cols["__time"], dims, metrics, sorted_by_time=True)


def make_segments(config: dict, seed: int, indices, seg_dir: str,
                  raw_dir: str) -> int:
    """Make, write and persist the day segments `indices`, one at a time so
    that peak memory is one segment's: raw columns as `.npy` for the
    reference, the `Segment` with the default (V2) writer for the program.
    Returns the bytes the writer put on disk."""
    from druid_tpu.storage.format_v2 import persist_segment_auto
    total = 0
    for index in indices:
        cols = basic.make_segment(config, seed, index)
        d = os.path.join(raw_dir, f"segment_{index:03d}")
        os.makedirs(d, exist_ok=True)
        for name, arr in cols.items():
            np.save(os.path.join(d, f"{name}.npy"), arr)
        total += persist_segment_auto(
            build_segment(config, index, cols),
            os.path.join(seg_dir, f"segment_{index:03d}"))
    return total


def _data_workers(n_segments: int) -> int:
    """Processes that make the data: the writer holds the interpreter lock
    for seconds a segment (its LZ4 tokenizer is Python), so threads do not
    help; a few cores stay free for this process and the runtime."""
    return max(1, min(n_segments, (os.cpu_count() or 1) - 3, 10))


def ensure_data(config: dict, seed: int, directory: str) -> Tuple[str, str, dict]:
    """The configuration's data for `seed` under `directory` (ONE data set
    per configuration: another seed's files are replaced, so the cache
    never grows past one). Made by child processes (`harness/makedata.py`)
    that are held to the CPU, so none of them can touch the chip this
    process holds. Returns (segments dir, raw dir, facts)."""
    seg_dir = os.path.join(directory, "segments")
    raw_dir = os.path.join(directory, "raw")
    marker = os.path.join(directory, "complete.json")
    want = data_signature(config, seed)
    t0 = time.monotonic()
    try:
        with open(marker) as f:
            if json.load(f).get("signature") == want:
                return seg_dir, raw_dir, {"generated": False, "seconds":
                                          time.monotonic() - t0}
    except (OSError, ValueError):
        pass
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(seg_dir)
    os.makedirs(raw_dir)
    config_path = os.path.join(directory, "config.json")
    with open(config_path, "w") as f:
        json.dump(config, f)
    n = int(config["segments"])
    workers = _data_workers(n)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "makedata.py")
    children = [subprocess.Popen(
        [sys.executable, script, "--config", config_path, "--seed", str(seed),
         "--segments", ",".join(str(i) for i in range(k, n, workers)),
         "--seg-dir", seg_dir, "--raw-dir", raw_dir],
        env=env, stdout=subprocess.PIPE, text=True) for k in range(workers)]
    disk_bytes, failed = 0, []
    for k, child in enumerate(children):
        out, _ = child.communicate()
        if child.returncode != 0:
            failed.append(k)
        else:
            disk_bytes += int(out.strip().splitlines()[-1])
    if failed:
        raise BenchFailure(f"data worker(s) {failed} of {workers} failed")
    with open(marker, "w") as f:
        json.dump({"signature": want, "seed": int(seed)}, f)
    return seg_dir, raw_dir, {"generated": True, "workers": workers,
                              "segment_bytes": disk_bytes,
                              "seconds": time.monotonic() - t0}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

class Deployment:
    """One historical and one broker in this process, as the configuration
    says: `mesh` null = a meshless node on one chip, "all" = ONE node over
    every local chip (`build_historical(mesh=make_mesh())`)."""

    def __init__(self, config: dict, seg_dir: str):
        from druid_tpu import cli
        self._servers: List = []
        mesh = None
        if config.get("mesh") == "all":
            from druid_tpu.parallel import make_mesh
            mesh = make_mesh()
        elif config.get("mesh") is not None:
            raise BenchFailure(f"unknown mesh {config['mesh']!r}")
        try:
            self.node, historical, loaded = cli.build_historical(
                "bench-historical", segments_dir=seg_dir, port=0, mesh=mesh)
            self._servers.append(historical)
            if loaded != int(config["segments"]):
                raise BenchFailure(f"historical loaded {loaded} of "
                                   f"{config['segments']} segments")
            _view, broker, http = cli.build_broker([historical.url], port=0)
            self._servers += [http, broker]
        except BaseException:
            self.stop()
            raise
        self.host = "127.0.0.1"
        self.port = http.port

    def stop(self) -> None:
        while self._servers:
            self._servers.pop().stop()


# ---------------------------------------------------------------------------
# What the benchmark reads from the program besides its answers
# ---------------------------------------------------------------------------

def read_counters() -> Dict[str, float]:
    """The allow-list of program counters a layer metric may name
    (`<object>.<key>`), read now. All are cumulative or gauges the program
    keeps anyway; reading them costs a lock each."""
    import dataclasses

    from druid_tpu.data.devicepool import device_pool
    from druid_tpu.engine import batching, megakernel
    from druid_tpu.obs import dispatch
    out: Dict[str, float] = {}
    groups = {"dispatch": dispatch.stats().snapshot(),
              "megakernel": megakernel.stats().snapshot(),
              "batching": batching.stats().snapshot(),
              "pool": dataclasses.asdict(device_pool().snapshot())}
    for group, snap in groups.items():
        for key, value in snap.items():
            if isinstance(value, (int, float)):
                out[f"{group}.{key}"] = value
    return out


def pallas_broken_reason() -> Optional[str]:
    from druid_tpu.engine import pallas_agg
    return pallas_agg.broken_reason()


def memory_peak_bytes() -> int:
    """Peak device memory on the fullest chip (0 where the backend reports
    none, as the CPU does)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class TraceDrain:
    """Copies finished traces out of the process-wide store while the
    window runs: the store keeps 256 and a window can hold more. An
    in-process read, not an HTTP request that would add load."""

    def __init__(self, prefix: str, period_s: float = 0.5):
        from druid_tpu.obs.trace import trace_store
        self._store = trace_store()
        self._prefix = prefix
        self._period = period_s
        self._stop = threading.Event()
        self.traces: Dict[str, dict] = {}
        self._thread = threading.Thread(target=self._run, name="trace-drain")

    def _sweep(self) -> None:
        for tid in self._store.trace_ids():
            if tid.startswith(self._prefix):
                got = self._store.get(tid)
                if got is not None:
                    # a later sweep replaces an earlier, shorter copy
                    self.traces[tid] = got

    def _run(self) -> None:
        while not self._stop.wait(self._period):
            self._sweep()

    def start(self) -> "TraceDrain":
        self._thread.start()
        return self

    def stop(self) -> Dict[str, dict]:
        self._stop.set()
        self._thread.join()
        self._sweep()
        return self.traces
