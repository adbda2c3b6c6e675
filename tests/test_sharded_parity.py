"""≥8-way sharded parity, driven the way the DRIVER runs multichip: a
fresh interpreter with `XLA_FLAGS=--xla_force_host_platform_device_count=8`
(the `dryrun_multichip` idiom), asserting the sharded mesh path is
BIT-IDENTICAL — floats included, compared with `==`, no tolerance — to
the serial decoded oracle across groupBy / timeseries / topN.

Exactness is only contractual for exact-merge aggregators (count,
longSum in int64, long/double min/max): their device collectives
(widened psum, pmax/pmin) are order-insensitive, so the sharded merge
and the host merge compute literally the same values. Float SUMS are
deliberately absent — summation order differs between the tree merge
and the collective, and their parity is tolerance-based (covered by
tests/test_distributed.py).

The inner run also counter-asserts the tentpole's merge discipline:
exactly one sharded dispatch per query (distributed.sharded_stats()),
ZERO batched and ZERO per-segment dispatches while the mesh is active —
i.e. the broker-side host merge is gone, not just idle — and the stack
that fed it is compressed-resident in the device pool.

The opt-out cross-product (DRUID_TPU_PACKED=0 / DRUID_TPU_CASCADE=0 are
import-time latches, hence subprocess per variant) proves parity does
not depend on which slots happen to be compressed.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

INNER = r"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from druid_tpu.data import devicepool
from druid_tpu.data.generator import ColumnSpec, DataGenerator
from druid_tpu.engine import QueryExecutor
import druid_tpu.engine.batching as batching
import druid_tpu.engine.engines as engines
from druid_tpu.parallel import distributed, make_mesh, use_mesh
from druid_tpu.query.aggregators import (CountAggregator, DoubleMaxAggregator,
                                         DoubleMinAggregator,
                                         LongMinAggregator, LongSumAggregator)
from druid_tpu.query.filters import BoundFilter, InFilter
from druid_tpu.query.model import (DefaultDimensionSpec, GroupByQuery,
                                   TimeseriesQuery, TopNQuery)
from druid_tpu.utils.intervals import Interval

import jax
assert len(jax.devices()) >= 8, jax.devices()

IV = Interval.of("2026-03-01", "2026-03-09")
SCHEMA = (ColumnSpec("dimA", "string", cardinality=7),
          ColumnSpec("dimB", "string", cardinality=31),
          ColumnSpec("metLong", "long", low=0, high=1000),
          ColumnSpec("metDouble", "double", low=-5.0, high=5.0))
# 11 segments on an 8-device mesh: K pads to 16, so the zero-pad
# segments' all-invalid decode is part of what parity covers
SEGMENTS = DataGenerator(SCHEMA, seed=23).segments(
    11, 2000, IV, datasource="parity")

AGGS = [CountAggregator("rows"),
        LongSumAggregator("lsum", "metLong"),
        LongMinAggregator("lmin", "metLong"),
        DoubleMaxAggregator("dmax", "metDouble"),
        DoubleMinAggregator("dmin", "metDouble")]
FLT = InFilter("dimA", [f"v{i:08d}" for i in range(5)])

QUERIES = [
    ("groupby", GroupByQuery.of(
        "parity", [IV], [DefaultDimensionSpec("dimA"),
                         DefaultDimensionSpec("dimB")],
        AGGS, granularity="day", filter=FLT)),
    ("timeseries", TimeseriesQuery.of(
        "parity", [IV], AGGS, granularity="day",
        filter=BoundFilter("metLong", lower=10, upper=900,
                           ordering="numeric"))),
    ("topn", TopNQuery.of(
        "parity", [IV], DefaultDimensionSpec("dimB"), "lsum", 10,
        AGGS, granularity="all", filter=FLT)),
]

# serial decoded oracle first, with the dispatch shape unconstrained
oracle = {name: QueryExecutor(SEGMENTS).run(q) for name, q in QUERIES}

# sharded runs: count every non-sharded dispatch that sneaks through
calls = {"batched": 0, "per_segment": 0}
_orig_batch = batching.run_with_batching


def _count_batch(*a, **k):
    calls["batched"] += 1
    return _orig_batch(*a, **k)


def _count_per_segment(*a, **k):
    calls["per_segment"] += 1
    raise AssertionError("per-segment dispatch on the sharded path")


batching.run_with_batching = _count_batch
engines.enqueue_grouped_aggregate = _count_per_segment

mesh = make_mesh(8)
before = distributed.sharded_stats().snapshot()
with use_mesh(mesh):
    sharded = {name: QueryExecutor(SEGMENTS).run(q) for name, q in QUERIES}
after = distributed.sharded_stats().snapshot()

assert calls["batched"] == 0, calls
assert calls["per_segment"] == 0, calls
assert after[0] - before[0] == len(QUERIES), (before, after)
assert after[1] - before[1] == len(QUERIES) * len(SEGMENTS), (before, after)
snap = devicepool.device_pool().snapshot()
assert snap.stacked_entries >= 1, snap
print(f"STACKED_RATIO {snap.stacked_ratio:.3f}")

for name, _ in QUERIES:
    a, b = oracle[name], sharded[name]
    assert len(a) > 0, name
    assert a == b, (name, a[:3], b[:3])   # bit-identical, floats included
    print(f"PARITY OK {name} rows={len(a)}")
print("ALL PARITY OK")
"""

VARIANTS = [
    pytest.param({}, id="packed+cascade+bitmap"),
    pytest.param({"DRUID_TPU_PACKED": "0"}, id="packed-off"),
    pytest.param({"DRUID_TPU_CASCADE": "0"}, id="cascade-off"),
]


@pytest.mark.parametrize("extra_env", VARIANTS)
def test_sharded_bit_identical_to_serial_oracle(extra_env):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                        "DRUID_TPU_PACKED", "DRUID_TPU_CASCADE")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.update(extra_env)
    proc = subprocess.run([sys.executable, "-c", INNER], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    for name in ("groupby", "timeseries", "topn"):
        assert f"PARITY OK {name}" in out, out
    assert "ALL PARITY OK" in out, out
    if not extra_env:
        # everything on: the resident stack must actually be compressed
        ratio = float(out.split("STACKED_RATIO ")[1].split()[0])
        assert ratio > 1.0, out
