"""State bytes over the seconds of host staging inside ``async_save``
(the ``capture_host_stage`` spans), as a share of the D2H rate probed in
the same run on the same devices: how much of the link the capture uses
while the loop stands still. None where no save staged to the host."""

from perfbench.spans import busy_seconds


def read(obs):
    saves, spans, probes = obs.get("saves"), obs.get("spans"), obs.get("probes")
    if not saves or not spans or not probes or not probes.get("d2h_gbps"):
        return None
    staged_s = busy_seconds(spans, "capture_host_stage")
    if not staged_s:
        return None
    # One state a span: a save that staged, staged everything.
    staged_saves = len(spans["capture_host_stage"])
    gbps = staged_saves * obs["state_bytes"] / staged_s / 1e9
    return 100.0 * gbps / probes["d2h_gbps"]
