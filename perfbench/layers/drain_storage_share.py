"""State bytes over mean seconds to durable, as a share of the durable
object-write rate probed in the same run into the same directory."""


def read(obs):
    saves, probes = obs.get("saves"), obs.get("probes")
    if not saves or not probes or not probes.get("storage_write_gbps"):
        return None
    mean_s = sum(s["durable_s"] for s in saves) / len(saves)
    return 100.0 * (obs["state_bytes"] / mean_s / 1e9) / probes["storage_write_gbps"]
