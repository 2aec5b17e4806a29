"""Milliseconds of one save's traced window in which the device ran no
operation and no span of the program was open: the training loop's own
host time.

None where the trace holds no anchor of the program's roots or no
busy intervals, or the program recorded no span
(``perfbench/idle_by_phase.py``)."""

from perfbench.idle_by_phase import SAVE, idle_ms


def read(obs):
    return idle_ms(obs, SAVE, "outside_library")
