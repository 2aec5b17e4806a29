"""Milliseconds a save spends trying to capture its cut as device
clones, kept or not: union of the program's ``capture.clone`` spans over
the window's saves. A capture that cannot clone pays this before it
stages to the host. None where no save tried (``stage="host"``) or the
program has no such span."""

from perfbench.spans import busy_seconds


def read(obs):
    saves, spans = obs.get("saves"), obs.get("spans")
    if not saves or not spans:
        return None
    cloned_s = busy_seconds(spans, "capture.clone")
    if cloned_s is None:
        return None
    return 1e3 * cloned_s / len(saves)
