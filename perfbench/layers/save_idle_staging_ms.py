"""Milliseconds of one save's traced window in which the device ran no
operation while the program staged: a ``capture.clone``, a
``capture_host_stage`` or a working ``stage.*`` span open on some
thread (``stage.fetch_wait`` is a wait).

None where the trace holds no anchor of the program's roots or no
busy intervals, or the program recorded no span
(``perfbench/idle_by_phase.py``)."""

from perfbench.idle_by_phase import SAVE, idle_ms


def read(obs):
    return idle_ms(obs, SAVE, "staging")
