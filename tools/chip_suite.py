"""One-command on-chip validation ladder. Run on the real TPU:

    python tools/chip_suite.py [--rows N]

Stages (each gates the next):
  1. sanity     — a TPU is visible, tiny matmul executes
  2. pallas     — the fused groupBy kernel compiles and matches the
                  mixed-strategy result exactly (chip_pallas_test inline)
  3. strategies — per-strategy timings on the headline shape so
                  select_strategy cutovers are measured, not assumed
  4. extended   — the other tracked BASELINE.md configs (timeseries,
                  selector-filtered topN, HLL cardinality, theta sketch)

ONE process: it touches JAX and so holds the chip, and starts no child
that would need it (run `python bench.py` as its own command). Exit code 0
only when every stage passes; no TPU, a kernel that will not build and a
strategy that raises are all failures. `chip_smoke.py` at the repo root is
the served-path proof; `profile_headline.py` remains for per-phase
profiling.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def log(msg):
    print(msg, flush=True)


def stage_sanity() -> bool:
    import jax
    import jax.numpy as jnp
    t0 = time.time()
    devs = jax.devices()
    log(f"[sanity] devices={devs} ({time.time() - t0:.1f}s)")
    if devs[0].platform != "tpu":
        log(f"[sanity] no TPU: platform is {devs[0].platform!r}")
        return False
    t0 = time.time()
    y = jnp.ones((512, 512)) @ jnp.ones((512, 512))
    ok = float(np.asarray(y)[0, 0]) == 512.0
    log(f"[sanity] matmul {'ok' if ok else 'WRONG'} "
        f"({time.time() - t0:.1f}s)")
    return ok


def _headline(rows: int, n_segments: int = 1):
    """The EXACT shape bench.py gates on (shared helpers in bench.py)."""
    import bench
    return bench.headline_segments(rows, n_segments), bench.headline_groupby()


class _spied_selection:
    """Record which strategy select_strategy actually returns — a forced
    strategy that falls through must not have its timing mislabeled."""

    def __enter__(self):
        from druid_tpu.engine import grouping
        self.grouping = grouping
        self.real = grouping.select_strategy
        self.chosen = []

        def spy(*a, **kw):
            out = self.real(*a, **kw)
            self.chosen.append(out[0])
            return out

        grouping.select_strategy = spy
        return self

    def __exit__(self, *exc):
        self.grouping.select_strategy = self.real


def stage_pallas(rows: int) -> bool:
    """Fused pallas kernel vs mixed strategy: exact result parity."""
    from druid_tpu.engine import QueryExecutor
    from druid_tpu.engine import pallas_agg
    if not pallas_agg.backend_ok():
        log(f"[pallas] kernel path unavailable on a TPU (gated off by "
            f"DRUID_TPU_PALLAS, or latched: {pallas_agg.broken_reason()})")
        return False
    segs, q = _headline(rows)
    saved = os.environ.get("DRUID_TPU_PALLAS")

    def run_with(strategy_env):
        os.environ.pop("DRUID_TPU_PALLAS", None)
        if strategy_env is not None:
            os.environ["DRUID_TPU_PALLAS"] = strategy_env
        ex = QueryExecutor(segs)
        t0 = time.time()
        out = ex.run(q)
        warm = time.time() - t0
        t0 = time.time()
        out = ex.run(q)
        log(f"[pallas] {strategy_env or 'default'}: {len(out)} groups "
            f"(warm {warm:.1f}s, hot {time.time() - t0:.3f}s)")
        return {(r['event']['dimA'], r['event']['dimB']):
                (r['event']['rows'], r['event']['lsum'],
                 round(r['event']['fmax'], 3)) for r in out}

    try:
        got = run_with(None)            # pallas eligible
        want = run_with("0")            # XLA strategies only
    finally:
        # restore the operator's setting for the later stages
        if saved is None:
            os.environ.pop("DRUID_TPU_PALLAS", None)
        else:
            os.environ["DRUID_TPU_PALLAS"] = saved
    if pallas_agg.broken_reason() is not None:
        log(f"[pallas] latched off: {pallas_agg.broken_reason()}")
        return False
    if got != want:
        diff = sum(1 for k in want if got.get(k) != want[k])
        log(f"[pallas] MISMATCH: {diff} differing groups of {len(want)}")
        return False
    log(f"[pallas] exact match over {len(want)} groups")
    return True


def stage_strategies(rows: int) -> bool:
    """Time each eligible groupBy strategy on the headline shape; a forced
    strategy that falls through is reported under what actually ran."""
    from druid_tpu.engine import QueryExecutor
    from druid_tpu.engine import grouping
    segs, q = _headline(rows)
    timings = {}
    ok = True
    forced = grouping.FORCE_STRATEGY
    for strat in ("mixed", "windowed", "projection"):
        try:
            grouping.FORCE_STRATEGY = strat
            with _spied_selection() as sel:
                ex = QueryExecutor(segs)
                ex.run(q)                      # warm
                ts = []
                for _ in range(3):
                    t0 = time.time()
                    ex.run(q)
                    ts.append(time.time() - t0)
            actual = sel.chosen[-1] if sel.chosen else strat
            label = strat if actual == strat \
                else f"{strat}->fell-through-to-{actual}"
            timings[label] = min(ts)
            log(f"[strategies] {label}: {min(ts) * 1e3:.0f}ms "
                f"({rows / min(ts) / 1e6:.0f}M rows/s)")
        except Exception as e:
            log(f"[strategies] {strat}: FAILED — {type(e).__name__}: "
                f"{str(e)[:120]}")
            ok = False
        finally:
            grouping.FORCE_STRATEGY = forced
    if timings:
        best = min(timings, key=timings.get)
        log(f"[strategies] best: {best} ({timings[best] * 1e3:.0f}ms)")
    return ok


def stage_extended(rows: int) -> bool:
    """The OTHER tracked BASELINE.md configs: Wikipedia-style timeseries
    (count+longSum), selector-filtered TopN with doubleSum, HLL
    cardinality, theta sketch — rates per config on the headline data."""
    from druid_tpu.engine import QueryExecutor
    from druid_tpu.query.aggregators import (CountAggregator,
                                             DoubleSumAggregator,
                                             HyperUniqueAggregator,
                                             LongSumAggregator)
    from druid_tpu.query.filters import SelectorFilter
    from druid_tpu.query.model import TimeseriesQuery, TopNQuery
    import bench
    segs = bench.headline_segments(rows, 1)
    iv = bench.headline_interval()
    sel = list(segs[0].dims["dimA"].dictionary.values)[0]
    import druid_tpu.ext  # noqa: F401 (theta aggregator)
    from druid_tpu.ext import ThetaSketchAggregator
    configs = [
        ("timeseries count+longSum", TimeseriesQuery.of(
            "bench", [iv], [CountAggregator("n"),
                            LongSumAggregator("s", "metLong")],
            granularity="hour")),
        ("topN doubleSum+selector", TopNQuery.of(
            "bench", [iv], "dimB", "ds", 100,
            [DoubleSumAggregator("ds", "metFloat")],
            granularity="all", filter=SelectorFilter("dimA", sel))),
        ("hll cardinality", TimeseriesQuery.of(
            "bench", [iv], [HyperUniqueAggregator("u", "dimB")],
            granularity="all")),
        ("theta sketch", TimeseriesQuery.of(
            "bench", [iv], [ThetaSketchAggregator("u", "dimB")],
            granularity="all")),
    ]
    ex = QueryExecutor(segs)
    ok = True
    for name, q in configs:
        try:
            t0 = time.time()
            ex.run(q)
            warm = time.time() - t0
            ts = []
            for _ in range(3):
                t0 = time.time()
                ex.run(q)
                ts.append(time.time() - t0)
            log(f"[extended] {name}: {min(ts) * 1e3:.0f}ms "
                f"({rows / min(ts) / 1e6:.0f}M rows/s, warm {warm:.1f}s)")
        except Exception as e:
            log(f"[extended] {name}: FAILED {type(e).__name__}: "
                f"{str(e)[:120]}")
            ok = False
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=12_500_000)
    args = ap.parse_args()
    for name, fn in [("sanity", stage_sanity),
                     ("pallas", lambda: stage_pallas(args.rows)),
                     ("strategies", lambda: stage_strategies(args.rows)),
                     ("extended", lambda: stage_extended(args.rows))]:
        if not fn():
            log(f"FAILED at stage {name}")
            return 1
    log("ALL STAGES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
