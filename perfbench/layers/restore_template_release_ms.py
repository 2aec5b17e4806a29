"""Milliseconds of a cycle's ``restore`` spent letting the template go:
above HBM/2 the arrays to land do not fit beside the device templates
they replace, and each Stateful's template is released before its reads
start (the program's ``restore.release_template`` spans, inside
``restore.plan``). None where the program recorded no such span: a
program without it, or a restore whose template fitted and stayed."""

from perfbench.phase_spans import has, restore_thread_seconds_per_cycle


def read(obs):
    if not has(obs.get("spans"), "restore.release_template"):
        return None
    seconds = restore_thread_seconds_per_cycle(obs, "restore.release_template")
    return None if seconds is None else 1e3 * seconds
