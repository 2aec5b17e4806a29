"""State bytes over mean seconds to durable, as a share of the D2H rate
probed in the same run on the same devices."""


def read(obs):
    saves, probes = obs.get("saves"), obs.get("probes")
    if not saves or not probes or not probes.get("d2h_gbps"):
        return None
    mean_s = sum(s["durable_s"] for s in saves) / len(saves)
    return 100.0 * (obs["state_bytes"] / mean_s / 1e9) / probes["d2h_gbps"]
