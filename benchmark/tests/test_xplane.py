"""The trace reduction, against a trace recorded on the chip and by hand."""
import json
import os

import pytest

from benchmark.harness import xplane
from benchmark.tests.util import BENCH

DATA = os.path.join(BENCH, "tests", "data")


def test_union_and_short_names():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == \
        [(0, 3), (5, 7)]
    assert xplane.short_op("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.3"
    assert xplane.short_module("jit_work(16969304421831028761)") == "jit_work"


def _planes(ops_by_device, anchor_ns=1000.0):
    planes = {"/host:CPU": {"python": [(xplane.ANCHOR, anchor_ns,
                                        anchor_ns + 10)]}}
    for d, ops in enumerate(ops_by_device):
        planes[f"/device:TPU:{d}"] = {
            xplane.MODULES_LINE: [("jit_f(1)", 0.0, 1e12)],
            xplane.OPS_LINE: ops}
    return planes


def test_known_intervals_two_devices_and_gap_labels():
    """Window = 1 s starting at the anchor. Device 0 is busy 0.1–0.3 s and
    0.25–0.4 s (overlapping: 0.3 s), device 1 busy 0.2–0.3 s (0.1 s): mean
    0.2 s. No device runs in 0–0.1 s and 0.4–1.0 s."""
    s = 1e9
    planes = _planes([
        [("%a = f32[] add()", 1000 + 0.1 * s, 1000 + 0.3 * s),
         ("%b = f32[] mul()", 1000 + 0.25 * s, 1000 + 0.4 * s),
         ("%a = f32[] add()", 1000 - 5 * s, 1000 - 4 * s)],   # before: clipped
        [("%a = f32[] add()", 1000 + 0.2 * s, 1000 + 0.3 * s)]])
    spans = [{"name": "query", "startMs": 100_000.0, "durationMs": 700.0},
             {"name": "engine/partials", "startMs": 100_000.0,
              "durationMs": 50.0},
             {"name": "broker/merge", "startMs": 100_400.0,
              "durationMs": 300.0}]
    got = xplane.reduce(planes, anchor_wall_s=100.0, wall0_s=100.0,
                        wall1_s=101.0, spans=spans)
    assert got["devices"] == 2
    assert got["window_s"] == pytest.approx(1.0)
    assert got["busy_s_by_device"] == pytest.approx([0.3, 0.1])
    assert got["busy_s"] == pytest.approx(0.2)
    ops = dict(got["device_ops"])
    assert ops["jit_f/a"] == pytest.approx((0.2 + 0.1) / 2)
    assert ops["jit_f/b"] == pytest.approx(0.15 / 2)
    gaps = dict(got["idle_gaps"])
    # 0–0.05 s under engine/partials (the innermost), 0.05–0.1 s under query,
    # 0.4–0.7 s under broker/merge, 0.7–1.0 s with no request in flight
    assert gaps["engine/partials"] == pytest.approx(0.05, abs=0.011)
    assert gaps["query"] == pytest.approx(0.05, abs=0.011)
    assert gaps["broker/merge"] == pytest.approx(0.3, abs=0.011)
    assert gaps[xplane.NO_REQUEST] == pytest.approx(0.3, abs=0.011)
    assert sum(gaps.values()) == pytest.approx(0.7)


def test_no_anchor_and_no_device_are_errors():
    with pytest.raises(ValueError, match="anchor"):
        xplane.reduce({"/device:TPU:0": {}}, 0.0, 0.0, 1.0)
    planes = {"/host:CPU": {"python": [(xplane.ANCHOR, 0.0, 1.0)]}}
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce(planes, 0.0, 0.0, 1.0)
    assert xplane.reduce(planes, 0.0, 0.0, 1.0,
                         require_device=False)["busy_s"] == 0.0


def test_trace_recorded_on_the_chip():
    """`chip_sample.xplane.pb`: three calls of one jitted matmul-and-sum on a
    v5e with 50 ms sleeps between them (tests/record_trace.py, PR 23). The
    expected numbers were read off the file by hand and are beside it."""
    with open(os.path.join(DATA, "chip_sample.json")) as f:
        want = json.load(f)
    planes = xplane.read_planes(os.path.join(DATA, "chip_sample.xplane.pb"))
    assert "/device:TPU:0" in planes
    assert xplane.anchor_ns(planes) == pytest.approx(want["anchor_ns"])
    got = xplane.reduce(planes, want["anchor_wall_s"], want["wall0_s"],
                        want["wall1_s"])
    assert got["devices"] == 1
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert got["window_s"] == pytest.approx(want["wall1_s"] - want["wall0_s"])
    assert got["device_ops"][0][0] == want["top_op"]
    assert got["device_ops"][0][1] == pytest.approx(want["top_op_s"], rel=1e-6)
    idle = 1.0 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(want["idle_share"], rel=1e-9)
    assert dict(got["idle_gaps"]) == {
        xplane.NO_REQUEST: pytest.approx(got["window_s"] - got["busy_s"])}
