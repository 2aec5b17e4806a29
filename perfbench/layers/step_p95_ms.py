"""95th percentile of all iteration times of the window in milliseconds:
the step, with the ``async_save`` call before it and the ``wait`` after
it where they fall (host clock)."""

import numpy as np


def read(obs):
    iteration_s = obs.get("iteration_s")
    if not iteration_s:
        return None
    return float(np.percentile(iteration_s, 95)) * 1e3
