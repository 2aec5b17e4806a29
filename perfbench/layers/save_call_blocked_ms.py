"""Milliseconds ``async_save`` blocks the loop, mean of all calls of the
window (host clock around the call)."""


def read(obs):
    saves = obs.get("saves")
    if not saves:
        return None
    return 1e3 * sum(s["blocked_s"] for s in saves) / len(saves)
