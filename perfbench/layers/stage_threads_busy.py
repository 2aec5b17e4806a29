"""How many of the 16 staging and 32 D2H threads work at once while any
does: thread-seconds of the working ``stage.*`` spans over their union
(a staging thread that waits for its leaf's fetches does not count)."""

from perfbench.phase_spans import STAGE_WORK, threads_at_once


def read(obs):
    saves, spans = obs.get("saves"), obs.get("spans")
    if not saves or not spans:
        return None
    return threads_at_once(spans, *(f"stage.{s}" for s in STAGE_WORK))
