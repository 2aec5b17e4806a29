"""Host spans of the program (``torchsnapshot_tpu.tracing``), reduced.

``union_seconds`` is the benchmark's own, so that no change to the
program can change the yardstick.
"""

import json
from typing import Dict, Iterable, List, Tuple


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(begin, end)`` intervals."""
    total = 0.0
    end = None
    for b, e in sorted(intervals):
        if end is None or b > end:
            total += e - b
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def read_spans(path: str) -> Dict[str, List[Tuple[float, float]]]:
    """``name -> [(begin_s, end_s)]`` from the Chrome-trace JSON that
    ``tracing.flush()`` writes: async ``b``/``e`` pairs matched by id,
    in seconds since ``tracing.enable``. A span that never ended is left
    out."""
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    begins = {}
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for ev in events:
        if ev.get("ph") == "b":
            begins[ev["id"]] = ev
        elif ev.get("ph") == "e":
            begin = begins.pop(ev["id"], None)
            if begin is not None:
                spans.setdefault(begin["name"], []).append(
                    (begin["ts"] / 1e6, ev["ts"] / 1e6)
                )
    return spans


def busy_seconds(spans: Dict[str, List[Tuple[float, float]]], name: str):
    """Union of the spans called ``name``, or None where there are none."""
    intervals = spans.get(name)
    return union_seconds(intervals) if intervals else None
