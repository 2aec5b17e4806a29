"""How many storage reads are inside ``f.read`` at once while any is:
thread-seconds of the program's ``read.io`` spans over their union."""

from perfbench.phase_spans import threads_at_once


def read(obs):
    cycles, spans = obs.get("cycles"), obs.get("spans")
    if not cycles or not spans:
        return None
    return threads_at_once(spans, "read.io")
