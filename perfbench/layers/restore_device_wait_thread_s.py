"""Thread-seconds a cycle that consumes of read payloads were held back
because the device budget (90 % of what was free when the reads began)
had no room for their region's chunks yet (the program's
``restore.device_budget_wait`` spans; admissions wait side by side, so
this is no wall time). 0 where the program has the span and none fired
in the window, which a ``restore.release_template`` span beside none
shows; None where it recorded neither: a program without them, or a
restore that was never near the budget."""

from perfbench.phase_spans import has, restore_thread_seconds_per_cycle


def read(obs):
    spans = obs.get("spans")
    if not (
        has(spans, "restore.device_budget_wait")
        or has(spans, "restore.release_template")
    ):
        return None
    return restore_thread_seconds_per_cycle(obs, "restore.device_budget_wait")
