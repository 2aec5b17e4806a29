"""Share of the drains' time in which at least one storage write was in
flight: union of the program's ``write`` spans over the summed seconds
to durable."""

from perfbench.spans import busy_seconds


def read(obs):
    saves, spans = obs.get("saves"), obs.get("spans")
    if not saves or not spans:
        return None
    busy = busy_seconds(spans, "write")
    if busy is None:
        return None
    return 100.0 * busy / sum(s["durable_s"] for s in saves)
