"""Share of the restores' time in which the H2D engine had a transfer in
flight: union of the program's ``consume.h2d_overlap`` spans over the
summed restore seconds."""

from perfbench.phase_spans import has
from perfbench.spans import busy_seconds


def read(obs):
    cycles, spans = obs.get("cycles"), obs.get("spans")
    if not cycles or not has(spans, "restore."):
        return None
    busy = busy_seconds(spans, "consume.h2d_overlap") or 0.0
    return 100.0 * busy / sum(c["restore_s"] for c in cycles)
