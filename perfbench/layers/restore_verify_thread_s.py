"""Thread-seconds a cycle spends verifying: the crc32 folds and whole-
object checks alone (the program's ``consume.verify`` spans, which since
PR 30 leave out the wait for a streamed object's lock)."""

from perfbench.phase_spans import restore_thread_seconds_per_cycle


def read(obs):
    return restore_thread_seconds_per_cycle(obs, "consume.verify")
