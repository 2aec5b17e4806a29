"""Milliseconds of the profiled kill -> resume cycle in which the device
ran no operation while a host-to-device transfer of the restore was
under way (``consume.h2d_overlap``, ``consume.device_put``).

None where the trace holds no anchor of the program's roots or no
busy intervals, or the program recorded no span
(``perfbench/idle_by_phase.py``)."""

from perfbench.idle_by_phase import RESTORE, idle_ms


def read(obs):
    return idle_ms(obs, RESTORE, "h2d")
