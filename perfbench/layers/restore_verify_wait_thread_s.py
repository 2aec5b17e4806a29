"""Thread-seconds a cycle's consume threads wait for a streamed object's
lock before they may fold its crc32 (the program's
``consume.verify_wait`` spans): the fold is in order, so the parts of
one object queue here."""

from perfbench.phase_spans import restore_thread_seconds_per_cycle


def read(obs):
    return restore_thread_seconds_per_cycle(obs, "consume.verify_wait")
