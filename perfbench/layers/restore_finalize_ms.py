"""Milliseconds of a cycle's ``restore`` after its last consume: device
concatenation, ``load_state_dict``, template release, the report (the
program's ``restore.finalize`` spans)."""

from perfbench.phase_spans import restore_thread_seconds_per_cycle


def read(obs):
    seconds = restore_thread_seconds_per_cycle(obs, "restore.finalize")
    return None if seconds is None else 1e3 * seconds
