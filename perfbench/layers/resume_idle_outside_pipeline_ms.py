"""Milliseconds of the profiled kill -> resume cycle in which the device
ran no operation and the restore's pipeline was not at work: the plan
and the finalize, the template, the manager, the loop's own host time.

None where the trace holds no anchor of the program's roots or no
busy intervals, or the program recorded no span
(``perfbench/idle_by_phase.py``)."""

from perfbench.idle_by_phase import RESTORE, idle_ms


def read(obs):
    return idle_ms(obs, RESTORE, "outside_pipeline")
