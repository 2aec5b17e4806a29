"""Seconds from the ``async_save`` call to ``wait()`` having returned
(step marker written, older steps pruned), mean over all saves of the
window: the work at risk when the job is killed (host clock)."""


def read(obs):
    saves = obs.get("saves")
    if not saves:
        return None
    return sum(s["durable_s"] for s in saves) / len(saves)
