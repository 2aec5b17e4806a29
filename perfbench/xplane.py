"""From the JAX profiler's ``.xplane.pb`` to device busy time, the
operations that took most of it, and the idle gaps by what the host was
doing.

The reduction works on a neutral form (``planes`` below) so that it can
be checked on a small recorded trace kept as JSON under
``tests/data/``; ``load_xplane`` turns the profiler's file into that
form with nothing but JAX.

    planes = [{"name": str, "lines": [{"name": str,
               "events": [[name, start_ns, duration_ns], ...]}]}]
"""

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

from perfbench.spans import union_seconds

# Lines of a device plane that hold one event per executed operation.
_OP_LINES = ("XLA Ops",)
# The benchmark's own host annotations all start with this.
ANNOTATION_PREFIX = "pb."
_TOP = 10


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return found[-1] if found else None


def load_xplane(path: str, keep_host_prefix: str = ANNOTATION_PREFIX) -> List[dict]:
    """Device planes whole; host planes cut to the benchmark's own
    annotations (a host plane holds every TraceMe of the runtime)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name not in _OP_LINES:
                continue
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if device or ev.name.startswith(keep_host_prefix)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def op_name(event_name: str) -> str:
    """``%fusion.6`` from the instruction's whole text, which is what the
    profiler calls an operation."""
    return event_name.split(" = ", 1)[0][:80]


def _op_intervals(plane: dict) -> List[Tuple[int, int, str]]:
    return sorted(
        (start, start + dur, op_name(name))
        for line in plane["lines"]
        if line["name"] in _OP_LINES
        for name, start, dur in line["events"]
    )


def _merged(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for b, e in sorted(intervals):
        if out and b <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([b, e])
    return [(b, e) for b, e in out]


def reduce_planes(planes: List[dict], chips: int) -> Optional[Dict[str, Any]]:
    """``busy_s`` (union of device operations, mean over the chips
    used), the span of the device's activity, the ``_TOP`` operations by
    time and the ``_TOP`` host annotations by idle device time under
    them. None where no operation ran on a device."""
    devices = [p for p in planes if is_device_plane(p["name"])]
    per_device = []
    by_op: Dict[str, float] = {}
    first: List[Tuple[int, int, str]] = []
    for plane in devices:
        ops = _op_intervals(plane)
        if not ops:
            continue
        first = first or ops
        per_device.append(union_seconds((b, e) for b, e, _ in ops) / 1e9)
        for b, e, name in ops:
            by_op[name] = by_op.get(name, 0.0) + (e - b) / 1e9
    if not per_device:
        return None
    # Devices that ran nothing still count as idle chips of the cell.
    busy_s = sum(per_device) / max(chips, len(per_device))
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:_TOP]
    scale = 1.0 / len(per_device)  # seconds a chip, like busy_s

    busy = _merged([(b, e) for b, e, _ in first])
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    notes = sorted(
        (start, start + dur, name)
        for plane in planes
        if not is_device_plane(plane["name"])
        for line in plane["lines"]
        for name, start, dur in line["events"]
        if name.startswith(ANNOTATION_PREFIX)
    )
    by_note: Dict[str, float] = {}
    for gb, ge in gaps:
        covered = 0
        for nb, ne, name in notes:
            if nb >= ge:
                break
            overlap = min(ge, ne) - max(gb, nb)
            if overlap > 0:
                by_note[name] = by_note.get(name, 0.0) + overlap / 1e9
                covered += overlap
        rest = (ge - gb) - covered
        if rest > 0:
            by_note["(no annotation)"] = by_note.get("(no annotation)", 0.0) + rest / 1e9
    top_gaps = sorted(by_note.items(), key=lambda kv: -kv[1])[:_TOP]
    return {
        "busy_s": busy_s,
        "devices_seen": len(per_device),
        "device_ops": [[name, secs * scale] for name, secs in top_ops],
        "idle_gaps": [[name, secs] for name, secs in top_gaps],
    }


def idle_pct(reduced: Optional[Dict[str, Any]]) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, mean over the chips; None where nothing was traced."""
    if not reduced or not reduced.get("window_s"):
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


class DeviceTrace:
    """One profiler capture of a few seconds inside a traced run."""

    def __init__(self, log_dir: str, chips: int) -> None:
        self.log_dir = log_dir
        self.chips = chips
        self.started_at = None
        self.window_s = None

    @property
    def running(self) -> bool:
        return self.started_at is not None and self.window_s is None

    @property
    def done(self) -> bool:
        return self.window_s is not None

    def start(self, now: float) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.started_at = now

    def stop(self, now: float) -> None:
        import jax

        self.window_s = now - self.started_at
        jax.profiler.stop_trace()

    def reduce(self) -> Optional[Dict[str, Any]]:
        if not self.done:
            return None
        path = find_xplane(self.log_dir)
        if path is None:
            return None
        planes = load_xplane(path)
        reduced = reduce_planes(planes, self.chips)
        if reduced is not None:
            from perfbench import idle_by_phase  # it imports this module

            reduced["window_s"] = self.window_s
            # What the idle-by-phase readers read besides: the first
            # device's busy intervals and the program's anchors.
            reduced.update(
                idle_by_phase.device_keys(planes, idle_by_phase.load_anchors(path))
            )
        return reduced
