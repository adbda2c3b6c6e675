"""Bench smoke gate: `python bench.py`, EXPLICITLY pinned to the CPU, must
exit 0 and print ONE valid JSON line with the headline + comparison fields
and the device it ran on; without a TPU and without that pin it must exit
non-zero and print no number, as it must when a section raises.

Tiny row counts keep it fast, the explicit CPU pin keeps it hermetic, and
the assertions are on CONTRACT (exit code, parseable one-line JSON, fields
present, counts) — never on a rate or a ratio of rates: a CPU timing says
nothing about the device the benchmark exists to measure."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent

BENCH_ENV = {
    "DRUID_TPU_BENCH_PLATFORM": "cpu",
    "DRUID_TPU_BENCH_ROWS": "40000",
    "DRUID_TPU_BENCH_SEGMENTS": "2",
    "DRUID_TPU_BENCH_ITERS": "1",
    "DRUID_TPU_BENCH_BATCH_SEGMENTS": "4",
    "DRUID_TPU_BENCH_BATCH_ROWS": "1024",
    "DRUID_TPU_BENCH_CASCADE_SEGMENTS": "4",
    "DRUID_TPU_BENCH_CASCADE_ROWS": "2048",
    "DRUID_TPU_BENCH_SEGIO_ROWS": "4096",
    "DRUID_TPU_BENCH_CLIENTS": "4",
    "DRUID_TPU_BENCH_CLIENT_QUERIES": "3",
    "DRUID_TPU_BENCH_SCHED_ROWS": "1024",
    "DRUID_TPU_BENCH_SOAK": "2",
    "DRUID_TPU_BENCH_STANDING_ROWS": "3000",
    "DRUID_TPU_BENCH_STANDING_WAVES": "3",
    "DRUID_TPU_BENCH_STANDING_SUBS": "16",
}


def _run_bench(extra_env=None, drop=()):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)       # the bench must pin its own
    # conftest forces an 8-virtual-device CPU fleet for the mesh tests;
    # inheriting it would make the bench subprocess run every program on a
    # 1/8-size device and blow the smoke budget
    env.pop("XLA_FLAGS", None)
    env.update(BENCH_ENV)
    env.update(extra_env or {})
    for k in drop:
        env.pop(k, None)
    return subprocess.run(
        [sys.executable, str(REPO_ROOT / "bench.py")],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=420)


def test_bench_exits_zero_with_one_json_line():
    proc = _run_bench()
    assert proc.returncode == 0, (
        f"bench.py rc={proc.returncode}\nstdout:{proc.stdout}\n"
        f"stderr:{proc.stderr[-2000:]}")
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected ONE stdout JSON line, got {lines!r}"
    out = json.loads(lines[0])
    assert out["metric"] == "groupby+topn_scan_rate"
    assert out["value"] > 0 and "error" not in out
    # every line names the device it ran on — here the explicit CPU pin
    assert out["platform"] == "cpu"
    assert out["device_kind"] and out["device_count"] >= 1
    assert not [k for k in out if k.endswith("_error")]
    assert "pallas_broken" not in out
    # the batch-comparison fields the perf gate reads
    assert out["per_segment_rate"] > 0
    assert out["batched_rate"] > 0
    assert out["batch_speedup"] > 0
    assert out["batch_segments"] == 4
    # the sharded-mesh comparison (contract only: rates positive, both
    # merge tails timed, and the stack really held compressed bytes —
    # the bench env strips XLA_FLAGS so this usually runs on a 1-device
    # mesh; the ≥8-way ordering is asserted on real hardware and parity
    # in tests/test_sharded_parity.py)
    assert out["sharded_decoded_rate"] > 0
    assert out["sharded_packed_rate"] > 0
    assert out["sharded_merge_host_ms"] > 0
    assert out["sharded_merge_device_ms"] > 0
    assert out["sharded_devices"] >= 1
    assert out["sharded_stack_ratio"] > 1.0
    # the compressed-domain cold-miss comparison (contract only: rates
    # positive and the pool really held compressed bytes — the ≥3x
    # capacity bar lives in test_packed.py where the shape is controlled)
    assert out["packed_rate"] > 0
    assert out["decoded_rate"] > 0
    assert out["pack_ratio"] > 1.0
    # the device-bitmap filter comparison (contract only: rates positive
    # and the warm run really hit resident filter results — throughput
    # ordering is asserted on real hardware, not shared CI)
    assert out["filter_host_rate"] > 0
    assert out["filter_device_rate"] > 0
    assert out["filter_speedup"] > 0
    assert out["filter_cache_hit_rate"] > 0
    # the megakernel comparison. The contract is the dispatch count: a
    # cold fused query is exactly ONE device dispatch, the staged path
    # pays the bitmap fill wave too.
    assert out["fused_rate"] > 0
    assert out["staged_rate"] > 0
    assert out["dispatch_count_fused"] == 1
    assert out["dispatch_count_staged"] >= 2
    assert out["donated_tick_rate"] > 0
    # the cascaded-encodings comparison (contract only: rates positive,
    # the pool really held cascade-encoded bytes, and the code-domain
    # run-space path really executed — throughput ordering is asserted on
    # real hardware, the filter-bench discipline)
    assert out["rle_rate"] > 0
    assert out["packed_only_rate"] > 0
    assert out["cascade_ratio"] > 1.0
    assert out["code_domain_rate"] > 0
    # the segment-format V1-vs-V2 comparison (contract only: rates
    # positive; disk_ratio > 1 needs rows where the fixed per-part
    # overheads amortize, which the smoke row count deliberately is not —
    # the size win is asserted in test_format_v2.py on a controlled
    # shape. The wire ordering IS hard: compressed partials must be
    # strictly smaller on this repeated-states shape at any size.)
    assert out["v1_load_rate"] > 0
    assert out["v2_load_rate"] > 0
    assert out["disk_ratio"] > 0
    assert 0 < out["wire_bytes_v2"] < out["wire_bytes_v1"]
    # the non-default-register sketch shape (log2m=12 rider)
    assert out["hll_log2m12_rate"] > 0
    # the qtrace-overhead fields
    assert out["traced_rate"] > 0
    assert out["untraced_rate"] > 0
    # the concurrent-client scheduler comparison (contract only: this
    # shared CI hardware cannot promise the ≥1.3x the real bench shows)
    assert out["sched_clients"] == 4
    assert out["sched_off_rate"] > 0
    assert out["sched_on_rate"] > 0
    assert out["sched_speedup"] > 0
    for mode in ("off", "on"):
        assert out[f"sched_{mode}_p50_ms"] > 0
        assert out[f"sched_{mode}_p99_ms"] >= out[f"sched_{mode}_p50_ms"]
    # the standing-query comparison (contract only: rates positive, the
    # hub really deduped N subscribers onto ONE standing program; the
    # standing-vs-rescan throughput ordering is asserted on real hardware
    # like the other comparisons — shared CI cannot promise it)
    assert out["standing_rate"] > 0
    assert out["rescan_rate"] > 0
    assert out["standing_speedup"] > 0
    assert out["standing_fanout_subs"] == 16
    assert out["standing_fanout_hub_ms"] > 0
    assert out["standing_fanout_independent_ms"] > 0
    assert out["standing_fanout_speedup"] > 0
    assert out["standing_programs"] == 1
    # the soak-mode drift fields (contract: present and near-zero on the
    # countable axes; rss is allocator-noisy, so presence only)
    assert out["soak_waves"] == 2
    assert abs(out["soak_thread_drift"]) <= 1
    assert abs(out["soak_fd_drift"]) <= 4
    assert isinstance(out["soak_rss_drift_kb"], int)


def test_bench_fails_without_its_device():
    """No TPU is an error, not a CPU run under the chip's name: with
    JAX held to the CPU and no explicit platform pin — or with a platform
    that does not exist — the bench exits non-zero and prints no number."""
    for extra, drop in (({"JAX_PLATFORMS": "cpu"},
                         ("DRUID_TPU_BENCH_PLATFORM",)),
                        ({"DRUID_TPU_BENCH_PLATFORM": "nosuchplatform"}, ())):
        proc = _run_bench(extra, drop=drop)
        assert proc.returncode != 0, proc.stderr[-2000:]
        assert proc.stdout.strip() == "", proc.stdout
        assert "bench:" in proc.stderr


def test_bench_section_failure_exits_nonzero():
    """A section that raises keeps the ONE JSON line (with its *_error
    field and every other section's numbers) and fails the process."""
    # the segment-io section parses its row count first: a bad value makes
    # exactly that section raise
    proc = _run_bench({"DRUID_TPU_BENCH_SEGIO_ROWS": "not-a-number"})
    assert proc.returncode != 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert out["segio_error"].startswith("ValueError")
    assert [k for k in out if k.endswith("_error")] == ["segio_error"]
    assert out["value"] > 0 and out["hll_log2m12_rate"] > 0
    assert "segio_error" in proc.stderr
