"""State bytes over mean ``restore`` seconds, as a share of the H2D rate
probed in the same run on the same devices."""


def read(obs):
    cycles, probes = obs.get("cycles"), obs.get("probes")
    if not cycles or not probes or not probes.get("h2d_gbps"):
        return None
    mean_s = sum(c["restore_s"] for c in cycles) / len(cycles)
    return 100.0 * (obs["state_bytes"] / mean_s / 1e9) / probes["h2d_gbps"]
