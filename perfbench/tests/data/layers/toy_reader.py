"""A per-layer reader the tests add by name: the number of cycles."""


def read(obs):
    cycles = obs.get("cycles")
    return len(cycles) if cycles else None
