"""Mean iteration time in milliseconds while a save was pending, to be
read against ``step_free_ms``: what the drain costs the loop a step. The
mean, not the median: about half of these iterations are slowed and half
are not, so their median falls on either side from run to run."""


def read(obs):
    return obs.get("step_in_drain_ms")
