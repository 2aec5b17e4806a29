"""What the readers of the phase metrics share: thread-seconds of the
program's sub-step spans (``stage.<sub-step>`` inside a take's staging,
``consume.<sub-step>``, ``read.io`` and ``restore.<stretch>`` inside a
restore), and how many threads were inside them at once.

Thread-seconds are the sum of a name's span lengths: sixteen threads a
second each are sixteen thread-seconds, where ``spans.union_seconds``
reads one second. A program that has the spans at all and none of one
name spent 0 s there; a program without them (a parent commit, an
untraced run) gives None.
"""

from typing import Dict, Iterable, List, Optional, Tuple

from perfbench.spans import union_seconds

Spans = Dict[str, List[Tuple[float, float]]]

# The sub-steps of staging in which a thread works; ``stage.fetch_wait``
# is a staging thread waiting for the D2H pool's threads, which are
# counted where they work.
STAGE_WORK = ("alloc", "slice", "d2h", "copy", "compress", "checksum")


def _intervals(spans: Spans, names: Iterable[str]) -> List[Tuple[float, float]]:
    return [iv for name in names for iv in spans.get(name, ())]


def has(spans: Optional[Spans], prefix: str) -> bool:
    """Whether the program recorded any span under ``prefix``."""
    return bool(spans) and any(name.startswith(prefix) for name in spans)


def thread_seconds(spans: Spans, *names: str) -> float:
    return sum(e - b for b, e in _intervals(spans, names))


def threads_at_once(spans: Spans, *names: str) -> Optional[float]:
    """Thread-seconds over the union: the mean number of threads inside
    the named spans while any was. None where none was."""
    intervals = _intervals(spans, names)
    busy = union_seconds(intervals)
    if not busy:
        return None
    return sum(e - b for b, e in intervals) / busy


def stage_thread_seconds_per_save(obs, *substeps: str) -> Optional[float]:
    """Thread-seconds of the named staging sub-steps a save of the
    window; None for a run without saves or without staging spans."""
    saves, spans = obs.get("saves"), obs.get("spans")
    if not saves or not has(spans, "stage."):
        return None
    return thread_seconds(spans, *(f"stage.{s}" for s in substeps)) / len(saves)


def restore_thread_seconds_per_cycle(obs, *names: str) -> Optional[float]:
    """Thread-seconds of the named spans a cycle of the window; None for
    a run without cycles or a program without the restore's stretches."""
    cycles, spans = obs.get("cycles"), obs.get("spans")
    if not cycles or not has(spans, "restore."):
        return None
    return thread_seconds(spans, *names) / len(cycles)
