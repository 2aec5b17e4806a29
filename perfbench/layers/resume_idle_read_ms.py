"""Milliseconds of the profiled kill -> resume cycle in which the device
ran no operation while the restore read (``read``, ``read.open``,
``read.io``) and neither consumed nor transferred.

None where the trace holds no anchor of the program's roots or no
busy intervals, or the program recorded no span
(``perfbench/idle_by_phase.py``)."""

from perfbench.idle_by_phase import RESTORE, idle_ms


def read(obs):
    return idle_ms(obs, RESTORE, "read")
