"""Thread-seconds a cycle's reads spend inside ``f.read`` (the program's
``read.io`` spans): with the reads in flight, what one read waits for
the directory."""

from perfbench.phase_spans import restore_thread_seconds_per_cycle


def read(obs):
    return restore_thread_seconds_per_cycle(obs, "read.io")
