"""The yardstick's arithmetic: the union of spans, and the reduction of
a profiler trace to busy time, top operations and idle gaps, on
hand-made planes (exact answers) and on small recordings kept under
``data/`` (a TPU v5e trace of the save cell and of the resume cell cut
to their first 400 operations, and a span file the program wrote)."""

import json
import os

import pytest

from perfbench import spans, xplane
from perfbench.manifest import load_module, BENCH_DIR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def brute_union(intervals):
    """Length of the union, by sweeping the sorted end points."""
    points = sorted({p for iv in intervals for p in iv})
    total = 0
    for lo, hi in zip(points, points[1:]):
        if any(b <= lo and hi <= e for b, e in intervals):
            total += hi - lo
    return total


@pytest.mark.parametrize(
    "intervals,want",
    [
        ([], 0.0),
        ([(0, 1)], 1.0),
        ([(0, 1), (2, 3)], 2.0),
        ([(0, 2), (1, 3)], 3.0),
        ([(0, 10), (1, 2), (3, 4)], 10.0),
        ([(5, 6), (0, 1), (0.5, 5.5)], 6.0),
    ],
)
def test_union_seconds(intervals, want):
    assert spans.union_seconds(intervals) == pytest.approx(want)


def test_recorded_span_file():
    got = spans.read_spans(os.path.join(DATA, "spans_recorded.json"))
    assert {"stage", "write", "read", "consume", "Snapshot.take"} <= set(got)
    assert len(got["write"]) == 6 and len(got["read"]) == 6
    for name in ("write", "read"):
        busy = spans.busy_seconds(got, name)
        assert busy == pytest.approx(brute_union(got[name]))
        assert 0 < busy <= sum(e - b for b, e in got[name]) + 1e-12
    take_b, take_e = got["Snapshot.take"][0]
    assert all(take_b <= b and e <= take_e for b, e in got["write"])
    assert spans.busy_seconds(got, "no such span") is None


def plane(name, line, events):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def test_reduction_on_hand_made_planes():
    planes = [
        plane(
            "/device:TPU:0",
            "XLA Ops",
            [
                ["%fusion.1 = f32[8]{0} fusion(...)", 0, 100],
                ["%fusion.1 = f32[8]{0} fusion(...)", 200, 100],
                ["%copy.2 = f32[8]{0} copy(...)", 250, 100],  # overlaps: 50 new
                ["%fusion.3 = ...", 1000, 10],
            ],
        ),
        plane("/device:TPU:0 extra", "Steps", [["0", 0, 5000]]),  # not an op line
        plane(
            "/host:CPU",
            "python3",
            [["pb.step", 90, 120], ["pb.async_save", 340, 700], ["other", 0, 9999]],
        ),
    ]
    got = xplane.reduce_planes(planes, chips=1)
    assert got["busy_s"] == pytest.approx(260e-9)
    assert got["device_ops"][0] == ["%fusion.1", pytest.approx(200e-9)]
    assert [n for n, _ in got["device_ops"]] == ["%fusion.1", "%copy.2", "%fusion.3"]
    gaps = dict(got["idle_gaps"])
    # gaps: 100..200 (step covers all of it) and 350..1000 (async_save all of it)
    assert gaps["pb.step"] == pytest.approx(100e-9)
    assert gaps["pb.async_save"] == pytest.approx(650e-9)
    assert "other" not in gaps
    assert sum(gaps.values()) == pytest.approx(750e-9)


def test_idle_chips_count_and_nothing_read_gives_nothing():
    one = plane("/device:TPU:0", "XLA Ops", [["%a = x", 0, 1000]])
    assert xplane.reduce_planes([one], chips=4)["busy_s"] == pytest.approx(250e-9)
    assert xplane.reduce_planes([plane("/host:CPU", "t", [["pb.step", 0, 5]])], 1) is None
    assert xplane.reduce_planes([plane("/device:TPU:0", "Steps", [["0", 0, 5]])], 1) is None
    assert not xplane.is_device_plane("/device:CUSTOM:Megascale Trace")


@pytest.mark.parametrize("which", ["save", "resume"])
def test_reduction_on_a_recorded_trace(which):
    with open(os.path.join(DATA, f"xplane_{which}_sample.json")) as f:
        planes = json.load(f)
    got = xplane.reduce_planes(planes, chips=1)
    ops = [
        (s, s + d)
        for p in planes
        if xplane.is_device_plane(p["name"])
        for ln in p["lines"]
        for _, s, d in ln["events"]
    ]
    assert len(ops) == 400
    assert got["busy_s"] == pytest.approx(brute_union(ops) / 1e9)
    span = (max(e for _, e in ops) - min(b for b, _ in ops)) / 1e9
    assert 0 < got["busy_s"] <= span
    seconds = [s for _, s in got["device_ops"]]
    assert seconds == sorted(seconds, reverse=True) and len(seconds) <= 10
    assert all(" = " not in name and len(name) <= 80 for name, _ in got["device_ops"])
    idle = span - got["busy_s"]
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    assert all(
        n.startswith(xplane.ANNOTATION_PREFIX) or n == "(no annotation)"
        for n, _ in got["idle_gaps"]
    )


def test_device_idle_readers_return_nothing_without_a_trace():
    for name in ("device_idle_pct.save", "device_idle_pct.resume"):
        reader = load_module(os.path.join(BENCH_DIR, "layers", name + ".py"))
        assert reader.read({}) is None
        assert reader.read({"device": {"busy_s": 0.5, "window_s": 2.0}}) == pytest.approx(75.0)
