"""Milliseconds from ``restore`` returning to the first resumed step
finished, mean over the cycles (host clock)."""


def read(obs):
    cycles = obs.get("cycles")
    if not cycles:
        return None
    return 1e3 * sum(c["first_step_s"] for c in cycles) / len(cycles)
