"""Bytes a cycle's ``restore`` read from storage over the bytes of the
objects stored under the step, mean over the cycles (the program's
counts in its report of each restore: bytes read into pooled buffers
filled before, into new ones and into memory the storage plug-in
allocated). 1.0: each stored byte read once; 2.0: read once for each of
two devices that hold it. None where the loop records no such count, or
the program's report has none of the fields."""


def read(obs):
    cycles, stored = obs.get("cycles"), obs.get("stored_object_bytes")
    if not cycles or not stored:
        return None
    if any(c.get("read_bytes") is None for c in cycles):
        return None
    return sum(c["read_bytes"] for c in cycles) / len(cycles) / stored
