"""Raw columns of the upstream JMH `basic` schema, from a seed, numpy only.

    benchmarks/src/main/java/org/apache/druid/benchmark/datagen/
    BenchmarkSchemas.java, schema "basic" (as recalled; every value not
    confirmed from a file of this repo is listed under `assumed` in the
    configuration files)

A column is described by one entry of a configuration file's `schema` list
and made by `make_column`; a segment is one day of `rows` rows whose
timestamps are uniform in the day and sorted. Every segment has a random
stream of its own (`[seed, segment index]`), so segments can be made in any
order and on any thread and come out the same.

What is returned is RAW: the numbers the generator drew, -1 for a null.
A dimension's string value is `str(number)` (unpadded decimal, as upstream's
generator makes them) and "" for a null; the dictionary ids a store derives
from them are the store's business and the reference never sees one.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

DAY_MS = 86_400_000
NULL_RAW = -1


def _narrow_int(low: int, high: int):
    """Smallest signed dtype that holds [min(low, NULL_RAW), high)."""
    for dt in (np.int16, np.int32, np.int64):
        if np.iinfo(dt).min <= min(low, NULL_RAW) and high - 1 <= np.iinfo(dt).max:
            return dt
    raise ValueError((low, high))


def _bounded_zipf(rng, n: int, low: int, high: int, exponent: float, dtype):
    """Zipf over the integers [low, high) drawn by probability table: rank
    k (1-based) has weight k**-exponent and maps to value low + k - 1.
    (numpy's rng.zipf is unbounded and refuses exponent 1.0.)"""
    ranks = np.arange(1, high - low + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(exponent))
    cdf /= cdf[-1]
    k = np.searchsorted(cdf, rng.random(n), side="right")
    return (low + np.minimum(k, high - low - 1)).astype(dtype)


def make_column(spec: dict, rng, n: int) -> np.ndarray:
    """One raw column of `n` rows. `spec` keys: name, kind ("string" |
    "long" | "float"), distribution ("sequential" | "uniform" | "zipf" |
    "normal"), low/high (half-open integer range), exponent, mean/sd,
    null_share."""
    dist = spec["distribution"]
    kind = spec["kind"]
    if dist == "normal":
        return rng.normal(spec["mean"], spec["sd"], size=n).astype(np.float32)
    low, high = int(spec["low"]), int(spec["high"])
    dtype = np.float32 if kind == "float" else _narrow_int(low, high)
    if dist == "sequential":
        out = (low + np.arange(n, dtype=np.int64) % (high - low)).astype(dtype)
    elif dist == "uniform":
        out = rng.integers(low, high, size=n).astype(dtype)
    elif dist == "zipf":
        out = _bounded_zipf(rng, n, low, high, spec["exponent"], dtype)
    else:
        raise ValueError(f"unknown distribution {dist!r} in {spec['name']}")
    null_share = float(spec.get("null_share", 0.0))
    if null_share >= 1.0:
        out[:] = NULL_RAW
    elif null_share > 0.0:
        out[rng.random(n) < null_share] = NULL_RAW
    return out


def segment_start_ms(config: dict, index: int) -> int:
    return int(config["first_day_ms"]) + index * DAY_MS


def make_segment(config: dict, seed: int, index: int) -> Dict[str, np.ndarray]:
    """Raw columns of day segment `index`: `__time` (int64 ms, sorted) and
    one array per schema column."""
    rng = np.random.default_rng([int(seed), int(index)])
    n = int(config["rows_per_segment"])
    cols: Dict[str, np.ndarray] = {
        "__time": segment_start_ms(config, index)
        + np.sort(rng.integers(0, DAY_MS, size=n)).astype(np.int64)}
    for spec in config["schema"]:
        cols[spec["name"]] = make_column(spec, rng, n)
    return cols
