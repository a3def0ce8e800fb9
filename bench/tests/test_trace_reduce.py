"""The reduction from a profiler trace to busy time, top operations and
idle gaps, on a small trace recorded on a TPU v5e and kept beside this
file (a jitted matmul run three times inside a ``bench.window``
annotation, with host sleeps between)."""
import pathlib

import pytest

from bench import trace_reduce as R

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_small.xplane.pb"


def test_union_and_gaps():
    busy = R.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert R.gaps(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert R.clip(busy, 1, 6) == [(1, 3), (5, 6)]


def test_label_is_the_innermost_open_span():
    spans = [("outer", 0.0, 10.0, {}), ("inner", 2.0, 4.0, {}),
             ("bench.window", 0.0, 10.0, {}), ("unfinished", 6.0, None, {})]
    segs = R.segments(spans, skip=("bench.window",))
    assert segs == [(0.0, 2.0, "outer"), (2.0, 4.0, "inner"),
                    (4.0, 10.0, "outer")]
    idle = {}
    R.attribute((1.0, 12.0), segs, [g[0] for g in segs], idle)
    assert idle == {"outer": 7.0, "inner": 2.0, "no span open": 2.0}


def test_summary_of_a_recorded_trace():
    pd = R.read(str(FIXTURE))
    lo, hi = R.window_ns(pd, "bench.window")
    ops = R.device_ops(pd)
    assert ops, "no device plane with XLA operations"
    s = R.summarize(str(FIXTURE), host_spans=[], window_name="bench.window",
                    window_t0=0.0, n_devices=1)
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < s["busy_s"] < s["window_s"]
    # busy time is the union of the operations inside the window
    dev = sorted(ops)[0]
    busy = R.union(R.clip([(a, b) for a, b, _ in ops[dev]], lo, hi))
    assert s["busy_s"] == pytest.approx(sum(b - a for a, b in busy) * 1e-9)
    assert s["breakdown"]["device_ops"][0][1] > 0
    assert len(s["breakdown"]["device_ops"]) <= R.TOP
    idle = sum(v for _, v in s["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-6)
    # a host span over the first half of the window takes its share
    half = s["window_s"] / 2
    t = R.summarize(str(FIXTURE), window_name="bench.window",
                    host_spans=[("bench.window", 0.0, s["window_s"], {}),
                                ("first_half", 0.0, half, {})],
                    window_t0=0.0, n_devices=1)
    split = dict(t["breakdown"]["idle_gaps"])
    assert set(split) == {"first_half", "no span open"}
    assert sum(split.values()) == pytest.approx(idle, rel=1e-6)
    assert 0 < split["first_half"] < half


def test_no_window_gives_nothing():
    assert R.summarize(str(FIXTURE), host_spans=[], window_name="absent",
                       window_t0=0.0, n_devices=1) is None
