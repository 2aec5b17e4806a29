"""Thread-seconds a save spends on the payloads' checksums (the
program's ``stage.checksum`` spans), wherever its staging runs."""

from perfbench.phase_spans import stage_thread_seconds_per_save


def read(obs):
    return stage_thread_seconds_per_save(obs, "checksum")
