"""The device's idle time by the stage the host was in.

The gaps between the device's operations in a profiled window (those
``xplane.reduce_planes`` sums into ``idle_gaps``) are split by what the
program was doing: each idle instant goes to the first class, in the
order below, that has one of the program's spans open on any thread,
and to the class of the rest where none is. The parts of a window
therefore add up to its gap seconds.

The program's spans (``obs["spans"]``, ``spans.read_spans``) are on its
own clock, seconds since ``tracing.enable``. While it records, each take
or restore root enters a profiler annotation ``tpusnapshot.<kind>`` that
carries that clock's reading as it began (``ts_us``). The annotation's
start in the profile less that reading is the one offset that puts every
span on the profile's nanoseconds (``offset_ns``); nothing assumes the
profiler's clock to be the wall clock.

What this reads of a profile, beside what ``reduce_planes`` returns:
``busy_intervals``, the merged operation intervals of the first device
that ran any (those ``idle_gaps`` uses), and ``anchors``,
``[name, start_ns, ts_us]`` a root the profile saw (``device_keys``).
A reader finds them under ``obs["device"]``; where they are missing, as
in a trace reduced without them or of a program without anchors, it
reads None.
"""

import statistics
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench import xplane
from perfbench.phase_spans import STAGE_WORK

ANCHOR_PREFIX = "tpusnapshot."
# The operation roots: open for the whole operation, so a class that
# took them would take every instant of it.
ROOTS = ("Snapshot.take", "Snapshot.restore")

Spans = Dict[str, List[Tuple[float, float]]]
Classes = Tuple[Sequence[Tuple[str, Callable[[str], bool]]], str]


def _named(*names: str) -> Callable[[str], bool]:
    return frozenset(names).__contains__


def _consume_work(name: str) -> bool:
    """The scheduler's ``consume`` span, and every ``consume.*`` sub-step
    but the waits (``read_wait``, ``executor_wait``, ``verify_wait``,
    ``h2d_wait``, ``pool_wait``, ``loop_wait``)."""
    return name == "consume" or (
        name.startswith("consume.") and not name.endswith("_wait")
    )


# (classes in order, the class of the rest)
RESTORE: Classes = (
    (
        ("h2d", _named("consume.h2d_overlap", "consume.device_put")),
        ("consume", _consume_work),
        ("read", _named("read", "read.open", "read.io")),
    ),
    "outside_pipeline",
)
SAVE: Classes = (
    (
        (
            "staging",
            _named(
                "capture.clone",
                "capture_host_stage",
                *(f"stage.{s}" for s in STAGE_WORK),
            ),
        ),
        ("write", _named("write")),
        ("library_other", lambda name: name not in ROOTS),
    ),
    "outside_library",
)


def load_anchors(path: str) -> List[list]:
    """``[name, start_ns, ts_us]`` of every root annotation in the
    profiler's ``.xplane.pb`` at ``path``, in the order of the file."""
    from jax.profiler import ProfileData

    found = []
    for plane in ProfileData.from_file(path).planes:
        if xplane.is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(ANCHOR_PREFIX):
                    continue
                ts_us = dict(ev.stats).get("ts_us")
                if ts_us is not None:
                    found.append([ev.name, int(ev.start_ns), float(ts_us)])
    return found


def busy_intervals(planes: List[dict]) -> List[List[int]]:
    """The merged operation intervals of the first device plane that has
    operations: ``reduce_planes``' ``busy``, whose gaps are its
    ``idle_gaps``."""
    for plane in planes:
        if xplane.is_device_plane(plane["name"]):
            ops = xplane._op_intervals(plane)
            if ops:
                return [[b, e] for b, e in xplane._merged([(b, e) for b, e, _ in ops])]
    return []


def device_keys(planes: List[dict], anchors: List[list]) -> Dict[str, list]:
    """The two keys this module reads under ``obs["device"]``."""
    return {"busy_intervals": busy_intervals(planes), "anchors": anchors}


def offset_ns(anchors: Iterable[Sequence]) -> Optional[float]:
    """Profile nanoseconds at the program's clock's 0: the median over
    the anchors of ``start_ns - ts_us * 1e3``. None without anchors."""
    offsets = [start - ts_us * 1e3 for _, start, ts_us in anchors]
    return statistics.median(offsets) if offsets else None


def mapped(spans: Spans, name: str, offset: float) -> List[Tuple[float, float]]:
    """The spans called ``name`` on the profile's nanoseconds."""
    return [(b * 1e9 + offset, e * 1e9 + offset) for b, e in spans.get(name, ())]


def split(
    busy: Sequence[Sequence[int]], spans: Spans, offset: float, classes: Classes
) -> Dict[str, float]:
    """Seconds of the gaps between ``busy`` (sorted, disjoint intervals
    in profile nanoseconds) by class: a sweep over every end point, each
    stretch inside a gap given to the first class with a span open."""
    ordered, rest = classes
    names = [name for name, _ in ordered] + [rest]
    points: List[Tuple[float, int, int]] = []  # (ns, class or -1 for a gap, +1/-1)
    for span_name, intervals in spans.items():
        k = next((i for i, (_, test) in enumerate(ordered) if test(span_name)), None)
        if k is None:
            continue
        for b, e in intervals:
            points.append((b * 1e9 + offset, k, 1))
            points.append((e * 1e9 + offset, k, -1))
    for (_, gap_begin), (gap_end, _) in zip(busy, busy[1:]):
        points.append((float(gap_begin), -1, 1))
        points.append((float(gap_end), -1, -1))
    points.sort()
    seconds = [0.0] * len(names)
    open_spans = [0] * len(ordered)
    in_gap = 0
    last = None
    for t, k, step in points:
        if in_gap and last is not None and t > last:
            owner = next((i for i, n in enumerate(open_spans) if n), len(ordered))
            seconds[owner] += (t - last) / 1e9
        last = t
        if k < 0:
            in_gap += step
        else:
            open_spans[k] += step
    return dict(zip(names, seconds))


def gap_seconds(busy: Sequence[Sequence[int]]) -> float:
    return sum(b - e for (_, e), (b, _) in zip(busy, busy[1:])) / 1e9


def idle_ms(obs, classes: Classes, part: str) -> Optional[float]:
    """Milliseconds of the traced window's device-idle gaps put down to
    ``part``; None where the trace has no busy intervals or no anchor,
    or the program recorded no span."""
    device, spans = obs.get("device"), obs.get("spans")
    if not device or not spans:
        return None
    busy, anchors = device.get("busy_intervals"), device.get("anchors")
    if not busy or not anchors:
        return None
    return 1e3 * split(busy, spans, offset_ns(anchors), classes)[part]
