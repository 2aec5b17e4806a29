"""Thread-seconds a save spends between a slice dispatched and its bytes
on the host (the program's ``stage.d2h`` spans), wherever its staging
runs."""

from perfbench.phase_spans import stage_thread_seconds_per_save


def read(obs):
    return stage_thread_seconds_per_save(obs, "d2h")
