"""Milliseconds of training a save costs the loop: the time of every
iteration in which a save was called or pending, less as many free steps
(``step_free_ms``), over the saves of the window. With the call, the
drain's contention and the final ``wait()`` in it (host clock)."""


def read(obs):
    return obs.get("loop_ms_lost_per_save")
