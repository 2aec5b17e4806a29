"""What the device had left, as a share of its limit, where the last save
of the window decided how to capture its cut: (limit - peak - clone
bytes) / limit, on the device where the clones fit worst. Positive where
the clones fitted beside the process's peak, negative where they did
not. The program's three counters of that decision, which its take
report carries, read from its metrics registry (the registry's gauges
``tpusnapshot_capture_*``). None where no save was called, the program
has no such counters, or the device reports no memory."""

NAMES = (
    "tpusnapshot_capture_clone_bytes",
    "tpusnapshot_capture_device_peak_bytes",
    "tpusnapshot_capture_device_limit_bytes",
)


def read(obs):
    if not obs.get("saves"):
        return None
    from torchsnapshot_tpu import telemetry

    values = telemetry.snapshot()
    clone, peak, limit = (values.get(name) for name in NAMES)
    if clone is None or peak is None or not limit:
        return None
    return 100.0 * (limit - peak - clone) / limit
