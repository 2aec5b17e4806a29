"""Share of the restores' time in which at least one storage read was in
flight: union of the program's ``read`` spans over the summed restore
seconds."""

from perfbench.spans import busy_seconds


def read(obs):
    cycles, spans = obs.get("cycles"), obs.get("spans")
    if not cycles or not spans:
        return None
    busy = busy_seconds(spans, "read")
    if busy is None:
        return None
    return 100.0 * busy / sum(c["restore_s"] for c in cycles)
