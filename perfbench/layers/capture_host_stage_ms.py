"""Milliseconds a save spends staging the state to the host inside
``async_save``: union of the program's ``capture_host_stage`` spans over
the window's saves. None (left out) where every save kept its cut as
device clones, or the program has no such span."""

from perfbench.spans import busy_seconds


def read(obs):
    saves, spans = obs.get("saves"), obs.get("spans")
    if not saves or not spans:
        return None
    staged_s = busy_seconds(spans, "capture_host_stage")
    if staged_s is None:
        return None
    return 1e3 * staged_s / len(saves)
