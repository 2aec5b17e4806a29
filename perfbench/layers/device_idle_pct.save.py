"""Share of the profiled window, the save call and the drain that follows it, in
which no operation ran on the device: 1 - union of device operations
over the window, mean over the chips."""

from perfbench.xplane import idle_pct


def read(obs):
    return idle_pct(obs.get("device"))
