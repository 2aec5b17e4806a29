"""Milliseconds a cycle spends making the zeroed state that a new stage
starts its optimizer from, before its ``restore`` (host clock, fenced),
mean over the cycles. None for a loop that makes none apart."""


def read(obs):
    cycles = obs.get("cycles")
    if not cycles or any("fresh_state_s" not in c for c in cycles):
        return None
    return 1e3 * sum(c["fresh_state_s"] for c in cycles) / len(cycles)
