"""The device's idle gaps by the stage the host was in
(``perfbench/idle_by_phase.py``): the program's spans put on the
profiler's clock through the roots' anchors, on a real profile of the
CPU backend; the split on hand-made intervals against a brute force;
the readers; and the reduction's accepted keys on the two recorded
samples, which the split's keys must leave as they are."""

import json
import os
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import idle_by_phase, spans, xplane
from perfbench.manifest import BENCH_DIR, load_module
from torchsnapshot_tpu import Snapshot, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SAVE_READERS = {
    "save_idle_staging_ms": "staging",
    "save_idle_outside_library_ms": "outside_library",
}
RESTORE_READERS = {
    "resume_idle_h2d_ms": "h2d",
    "resume_idle_consume_ms": "consume",
    "resume_idle_read_ms": "read",
    "resume_idle_outside_pipeline_ms": "outside_pipeline",
}


def _reader(name):
    return load_module(os.path.join(BENCH_DIR, "layers", name + ".py")).read


class _Holder:
    def __init__(self, sd):
        self.sd = sd

    def state_dict(self):
        return self.sd

    def load_state_dict(self, sd):
        self.sd = sd


# ------------------------------------------------ a real profile, mapped


@pytest.fixture
def untraced_after():
    assert not tracing.enabled()
    yield
    if tracing.enabled():
        tracing.disable()


def _host_notes(planes, name):
    return [
        (start, start + dur)
        for plane in planes
        if not xplane.is_device_plane(plane["name"])
        for line in plane["lines"]
        for n, start, dur in line["events"]
        if n == name
    ]


def test_a_real_profile_puts_the_restore_inside_its_annotation(tmp_path, untraced_after):
    """``pb.restore`` (the benchmark's annotation, on the profiler's
    clock) around a traced restore: the library's ``Snapshot.restore``
    span, mapped by the root's anchor, lies inside it within 0.1 ms at
    each end, and so does every span of the restore."""
    path = str(tmp_path / "snap")
    state = {"w": jnp.arange(1 << 16, dtype=jnp.float32), "b": np.arange(5.0)}
    Snapshot.take(path, {"m": _Holder(state)})
    target = {"m": _Holder({"w": jnp.zeros(1 << 16, jnp.float32), "b": np.zeros(5)})}

    spans_path = str(tmp_path / "spans.json")
    tracing.enable(spans_path)
    time.sleep(0.05)  # the span file's clock runs apart from the profile's
    trace = xplane.DeviceTrace(str(tmp_path / "profile"), chips=1)
    trace.start(time.monotonic())
    with jax.profiler.TraceAnnotation("pb.restore"):
        Snapshot(path).restore(target)
    trace.stop(time.monotonic())
    tracing.disable()
    assert np.array_equal(np.asarray(target["m"].sd["w"]), np.arange(1 << 16))

    profile = xplane.find_xplane(trace.log_dir)
    anchors = idle_by_phase.load_anchors(profile)
    assert [a[0] for a in anchors] == ["tpusnapshot.restore"]
    (note,) = _host_notes(xplane.load_xplane(profile), "pb.restore")
    got = spans.read_spans(spans_path)
    offset = idle_by_phase.offset_ns(anchors)
    (root,) = idle_by_phase.mapped(got, "Snapshot.restore", offset)
    slack = 0.1e6  # ns
    assert note[0] - slack <= root[0] < root[1] <= note[1] + slack, (note, root)
    # the anchor itself opened inside the annotation, before the root span
    assert note[0] <= anchors[0][1] <= root[0] + slack
    for name in got:
        for begin, end in idle_by_phase.mapped(got, name, offset):
            assert note[0] - slack <= begin <= end <= note[1] + slack, name


def test_without_tracing_the_profile_holds_no_anchor(tmp_path):
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": _Holder({"w": jnp.ones(8)})})
    trace = xplane.DeviceTrace(str(tmp_path / "profile"), chips=1)
    trace.start(time.monotonic())
    Snapshot(path).restore({"m": _Holder({"w": jnp.zeros(8)})})
    trace.stop(time.monotonic())
    assert idle_by_phase.load_anchors(xplane.find_xplane(trace.log_dir)) == []


# ------------------------------------------------- the split, by hand


def brute_split(busy, named_spans, offset, classes):
    """Every stretch between two end points, given to the first class
    with a span over its middle."""
    ordered, rest = classes
    tests = [test for _, test in ordered]
    intervals = [
        (b * 1e9 + offset, e * 1e9 + offset, k)
        for name, ivs in named_spans.items()
        for k in [next((i for i, t in enumerate(tests) if t(name)), None)]
        if k is not None
        for b, e in ivs
    ]
    out = {name: 0.0 for name, _ in ordered}
    out[rest] = 0.0
    for (_, gb), (ge, _) in zip(busy, busy[1:]):
        points = sorted(
            {gb, ge} | {p for b, e, _ in intervals for p in (b, e) if gb < p < ge}
        )
        for lo, hi in zip(points, points[1:]):
            mid = (lo + hi) / 2
            owners = [k for b, e, k in intervals if b <= mid < e]
            name = ordered[min(owners)][0] if owners else rest
            out[name] += (hi - lo) / 1e9
    return out


def _random_case(seed, names):
    rng = random.Random(seed)
    edges = sorted(rng.sample(range(0, 100_000), 2 * rng.randint(2, 12)))
    busy = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)]
    named = {}
    for _ in range(rng.randint(1, 30)):
        begin = rng.uniform(-5e-6, 100e-6)
        named.setdefault(rng.choice(names), []).append(
            (begin, begin + rng.uniform(0, 30e-6))
        )
    return busy, named, rng.uniform(-3e3, 3e3)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize(
    "classes,names",
    [
        (
            idle_by_phase.RESTORE,
            ["consume.h2d_overlap", "consume.device_put", "consume", "consume.verify",
             "consume.verify_wait", "read", "read.io", "read.open", "restore.plan",
             "Snapshot.restore"],
        ),
        (
            idle_by_phase.SAVE,
            ["capture.clone", "capture_host_stage", "stage.d2h", "stage.copy",
             "stage.fetch_wait", "stage", "write", "Snapshot.take", "restore.plan"],
        ),
    ],
    ids=["restore", "save"],
)
def test_the_parts_add_up_to_the_gaps_and_follow_the_order(seed, classes, names):
    busy, named, offset = _random_case(seed, names)
    got = idle_by_phase.split(busy, named, offset, classes)
    assert sum(got.values()) == pytest.approx(idle_by_phase.gap_seconds(busy), rel=1e-6)
    want = brute_split(busy, named, offset, classes)
    assert got.keys() == want.keys()
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-6, abs=1e-15), name


def test_the_first_open_class_takes_the_instant():
    # one gap of 100 ns between two operations; the spans in seconds,
    # on a clock 1000 ns behind the profile's
    busy = [[0, 1000], [1100, 1200]]
    ns = 1e-9
    named = {
        "read.io": [(0 * ns, 95 * ns)],  # 1000..1095
        "consume.verify": [(20 * ns, 60 * ns)],  # 1020..1060
        "consume.verify_wait": [(60 * ns, 80 * ns)],  # a wait: not consume
        "consume.h2d_overlap": [(40 * ns, 50 * ns)],  # 1040..1050
        "restore.plan": [(95 * ns, 200 * ns)],  # 1095..1100 in the gap
    }
    got = idle_by_phase.split(busy, named, 1000.0, idle_by_phase.RESTORE)
    assert got == pytest.approx(
        {"h2d": 10 * ns, "consume": 30 * ns, "read": 55 * ns, "outside_pipeline": 5 * ns}
    )
    save = {
        "stage.d2h": [(10 * ns, 30 * ns)],
        "stage.fetch_wait": [(0 * ns, 100 * ns)],  # a wait: library_other
        "write": [(20 * ns, 40 * ns)],
        "Snapshot.take": [(0 * ns, 100 * ns)],  # a root: takes nothing
    }
    got = idle_by_phase.split(busy, save, 1000.0, idle_by_phase.SAVE)
    assert got == pytest.approx(
        {"staging": 20 * ns, "write": 10 * ns, "library_other": 70 * ns,
         "outside_library": 0.0}
    )
    del save["stage.fetch_wait"]
    got = idle_by_phase.split(busy, save, 1000.0, idle_by_phase.SAVE)
    assert got["outside_library"] == pytest.approx(70 * ns)


def test_the_offset_is_the_anchors_median():
    assert idle_by_phase.offset_ns([]) is None
    anchors = [["tpusnapshot.take", 5_000, 1.0], ["tpusnapshot.restore", 9_010, 5.0],
               ["tpusnapshot.restore", 20_020, 16.0]]
    assert idle_by_phase.offset_ns(anchors) == pytest.approx(4_010.0)


# ----------------------------------------------- the recorded samples


@pytest.mark.parametrize("which", ["save", "resume"])
def test_the_accepted_keys_of_the_recorded_samples_stay_as_they_were(which):
    """``busy_s``, ``devices_seen``, ``device_ops`` and ``idle_gaps`` as
    the reduction gave them before the split's keys existed."""
    with open(os.path.join(DATA, f"xplane_{which}_sample.json")) as f:
        planes = json.load(f)
    with open(os.path.join(DATA, "xplane_samples_reduced.json")) as f:
        pinned = json.load(f)[which]
    got = json.loads(json.dumps(xplane.reduce_planes(planes, chips=1)))
    for key in ("busy_s", "devices_seen", "device_ops", "idle_gaps"):
        assert got[key] == pinned[key], key
    busy = idle_by_phase.busy_intervals(planes)
    assert busy == sorted(busy)
    assert all(b < e < nb for (b, e), (nb, _) in zip(busy, busy[1:]))
    assert sum(e - b for b, e in busy) / 1e9 == pytest.approx(got["busy_s"], rel=1e-9)
    assert idle_by_phase.gap_seconds(busy) == pytest.approx(
        sum(s for _, s in got["idle_gaps"]), rel=1e-6
    )
    # without spans every gap is the rest's
    keys = idle_by_phase.device_keys(planes, [["tpusnapshot.restore", 0, 0.0]])
    rest = idle_by_phase.split(keys["busy_intervals"], {}, 0.0, idle_by_phase.RESTORE)
    assert rest["outside_pipeline"] == pytest.approx(idle_by_phase.gap_seconds(busy))


# ---------------------------------------------------------- the readers


def _obs(named, busy, anchors):
    return {"spans": named, "device": {"busy_s": 1.0, "window_s": 2.0,
                                       "busy_intervals": busy, "anchors": anchors}}


def test_the_readers_give_the_parts_in_ms_and_nothing_without_the_keys():
    busy, named, _ = _random_case(3, ["read", "consume", "consume.h2d_overlap",
                                      "stage.copy", "write", "capture.clone"])
    anchors = [["tpusnapshot.restore", 2_500, 1.5]]  # offset 1000 ns
    obs = _obs(named, busy, anchors)
    restore = idle_by_phase.split(busy, named, 1000.0, idle_by_phase.RESTORE)
    save = idle_by_phase.split(busy, named, 1000.0, idle_by_phase.SAVE)
    for name, part in RESTORE_READERS.items():
        assert _reader(name)(obs) == pytest.approx(1e3 * restore[part])
    for name, part in SAVE_READERS.items():
        assert _reader(name)(obs) == pytest.approx(1e3 * save[part])
    total = sum(_reader(name)(obs) for name in RESTORE_READERS)
    assert total == pytest.approx(1e3 * idle_by_phase.gap_seconds(busy), rel=1e-6)
    # a trace reduced without the keys (as ``reduce_planes`` gives it
    # today), a program without anchors, no spans, no trace at all
    for nothing in (
        {"spans": named, "device": {"busy_s": 1.0, "window_s": 2.0}},
        _obs(named, busy, []),
        _obs({}, busy, anchors),
        {"spans": named},
        {},
    ):
        for name in list(RESTORE_READERS) + list(SAVE_READERS):
            assert _reader(name)(nothing) is None, (name, nothing)


def test_a_device_trace_hands_the_readers_both_keys(tmp_path, untraced_after, monkeypatch):
    """``DeviceTrace.reduce`` gives what ``reduce_planes`` gave, unchanged,
    and beside it the first device's busy intervals and the anchors of a
    real profile, so that a traced run's readers read a number. The CPU
    backend has no device plane: the recorded resume sample stands in
    for the device planes of the profile that was taken."""
    with open(os.path.join(DATA, "xplane_resume_sample.json")) as f:
        planes = json.load(f)
    with open(os.path.join(DATA, "xplane_samples_reduced.json")) as f:
        pinned = json.load(f)["resume"]
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": _Holder({"w": jnp.arange(1 << 12, dtype=jnp.float32)})})
    spans_path = str(tmp_path / "spans.json")
    tracing.enable(spans_path)
    trace = xplane.DeviceTrace(str(tmp_path / "profile"), chips=1)
    trace.start(time.monotonic())
    Snapshot(path).restore({"m": _Holder({"w": jnp.zeros(1 << 12, jnp.float32)})})
    trace.stop(time.monotonic())
    tracing.disable()
    monkeypatch.setattr(xplane, "load_xplane", lambda _: planes)

    reduced = json.loads(json.dumps(trace.reduce()))
    for key in ("busy_s", "devices_seen", "device_ops", "idle_gaps"):
        assert reduced[key] == pinned[key], key
    assert reduced["window_s"] == trace.window_s
    assert reduced["busy_intervals"] == idle_by_phase.busy_intervals(planes)
    assert [a[0] for a in reduced["anchors"]] == ["tpusnapshot.restore"]
    obs = {"device": reduced, "spans": spans.read_spans(spans_path)}
    parts = [_reader(name)(obs) for name in RESTORE_READERS]
    assert all(part is not None and part >= 0 for part in parts)
    assert sum(parts) == pytest.approx(1e3 * idle_by_phase.gap_seconds(reduced["busy_intervals"]))
