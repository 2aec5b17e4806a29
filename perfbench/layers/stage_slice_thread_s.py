"""Thread-seconds a save spends dispatching device slices (the
program's ``stage.slice`` spans: one ``slice_in_dim`` a chunk, under the
interpreter's lock), wherever its staging runs."""

from perfbench.phase_spans import stage_thread_seconds_per_save


def read(obs):
    return stage_thread_seconds_per_save(obs, "slice")
