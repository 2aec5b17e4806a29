"""Milliseconds of a cycle's ``restore`` before its first read is
dispatched: metadata, manifest, templates, the read plan (the program's
``restore.plan`` spans)."""

from perfbench.phase_spans import restore_thread_seconds_per_cycle


def read(obs):
    seconds = restore_thread_seconds_per_cycle(obs, "restore.plan")
    return None if seconds is None else 1e3 * seconds
