"""Median iteration time in milliseconds while no save was pending: the
job's own step."""


def read(obs):
    return obs.get("step_free_ms")
