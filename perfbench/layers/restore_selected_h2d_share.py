"""The bytes a cycle's ``restore`` landed (``restored_bytes``: what the
named Statefuls hold, not the whole snapshot's) over mean ``restore``
seconds, as a share of the H2D rate probed in the same run on the same
devices: the stand-in for a roofline share in a cell that restores a
part, as ``restore_h2d_share`` is where the whole state lands. None for
a loop that records no ``restored_bytes`` (a restore of everything)."""


def read(obs):
    cycles, probes = obs.get("cycles"), obs.get("probes")
    if not cycles or not probes or not probes.get("h2d_gbps"):
        return None
    if any("restored_bytes" not in c for c in cycles):
        return None
    landed = sum(c["restored_bytes"] for c in cycles) / len(cycles)
    mean_s = sum(c["restore_s"] for c in cycles) / len(cycles)
    return 100.0 * (landed / mean_s / 1e9) / probes["h2d_gbps"]
